"""The held expert layer's combine as a Pallas kernel (ops/moe_combine.py),
in interpret mode on the CPU, held to its contract against the ``lax`` form
it stands in for (``lm_common._combine_lax``).

Tolerance, from the contract.  Both forms take the same bfloat16 rows and
float32 weights and round each product ``w * row`` to float32 alike; they
differ in the ORDER of a token's k-term float32 sum (the kernel adds a
token's rows as they lie, by expert; XLA's reduce pairs the slots as it
likes).  Per element the comparison allows ``k * 2**-23 * sum_j |w_j *
row_j|`` over the token's computed pairs, and nothing where a token has
none: y is exactly 0 there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from sat_tpu.models import lm_common
from sat_tpu.ops import moe_combine
from tests.test_tracing import _dots3_config, _dsa_config


def _routing(T, k, done, seed, live=None):
    """(back [T, k]: each routed pair's place in the sorted order, a
    bijection; order [T * k]: its inverse, what the dispatch's argsort
    gives; weights [T, k]).  ``live`` [T]: how many of each token's slots
    lie under ``done`` (None: as the bijection falls)."""
    rng = np.random.default_rng(seed)
    weights = rng.uniform(0.05, 1.0, size=(T, k)).astype(np.float32)
    if live is None:
        back = rng.permutation(T * k).astype(np.int32).reshape(T, k)
    else:
        under, past = list(rng.permutation(done)), list(done + rng.permutation(T * k - done))
        back = np.empty((T, k), np.int32)
        for t in range(T):
            for n, j in enumerate(rng.permutation(k)):
                back[t, j] = under.pop() if n < live[t] else past.pop()
    return back, np.argsort(back.reshape(-1)).astype(np.int32), weights


def _rows(P, H, done, seed, past=np.nan):
    rows = np.random.default_rng(seed + 1).standard_normal((P, H)).astype(np.float32)
    rows[done:] = past              # what the products never wrote may hold anything
    return jnp.asarray(rows, jnp.bfloat16)


def _allowed(rows, back, weights, done):
    """The contract's bound per element: k * 2**-23 * sum_j |w_j * row_j|
    over each token's computed pairs."""
    rows = np.abs(np.asarray(rows.astype(jnp.float32), np.float64))
    T, k = back.shape
    total = np.zeros((T, rows.shape[1]))
    for j in range(k):
        live = back[:, j] < done
        total[live] += weights[live, j, None] * rows[back[live, j]]
    return k * 2.0 ** -23 * total


# T, k, H, P, done, live slots per token (None: as they fall)
CASES = {
    "an_eighth_live": (1024, 8, 256, 1024, 1000, None),
    "nothing_landed": (1024, 8, 128, 1024, 0, None),
    "every_row_written": (1024, 8, 128, 1024, 1024, None),      # as many again landed beyond the rows
    "every_expert_held": (1024, 8, 128, 8192, 8192, None),      # done = P = T * k
    "a_teacher_forced_length": (4116, 2, 128, 1040, 700, None),     # T no multiple of 8, P no multiple of a block
    "rows_past_a_whole_block": (1024, 8, 384, 2000, 512, None),     # three tiles' columns; done ends a block
    "no_slot_or_every_slot": (1024, 8, 128, 2560, 2560, "none_or_all"),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_the_kernel_keeps_the_contract(case):
    T, k, H, P, done, live = CASES[case]
    if live == "none_or_all":
        live = np.array([0, k] * (T // 4) + [1] * (T // 2))
        assert live.sum() == done
    back, order, weights = _routing(T, k, done, seed=len(case), live=live)
    rows = _rows(P, H, done, seed=len(case))
    args = rows, jnp.asarray(order), jnp.asarray(weights), jnp.int32(done)
    got = moe_combine.combine_kernel(*args, interpret=True)
    assert got.shape == (T, H) and got.dtype == jnp.float32
    got = np.asarray(got)
    assert np.isfinite(got).all()                   # no row at or past ``done`` reached y
    want = np.asarray(lm_common._combine_lax(*args))
    assert (np.abs(got - want) <= _allowed(rows, back, weights, done)).all()
    none_live = (back >= done).all(axis=1)
    assert (got[none_live] == 0).all()              # exactly
    if live is not None:
        assert none_live.sum() == T // 4 and (np.abs(got[live == k]).sum(axis=1) > 0).all()
    if done == 0:
        assert none_live.all()


@pytest.mark.parametrize("case", ["an_eighth_live", "every_expert_held", "a_teacher_forced_length"])
def test_the_op_s_gradient_is_the_lax_form_s(case):
    """``moe_combine`` is differentiable by a ``custom_vjp`` (two P-row
    gathers): in ``out`` to the bit (one product a row), in ``weights`` to
    the float32 rounding of an H-term sum; rows at or past ``done`` and
    pairs not computed get exactly 0.  (Finite rows past ``done`` here: the
    ``lax`` form's own derivative multiplies what it masked by 0.)"""
    T, k, H, P, done, _ = CASES[case]
    back, order, weights = _routing(T, k, done, seed=7)
    rows = _rows(P, H, done, seed=7, past=1e3)
    order, done_ = jnp.asarray(order), jnp.int32(done)
    seen = jnp.cos(jnp.arange(T * H, dtype=jnp.float32)).reshape(T, H)

    def loss(form):
        return lambda rows, weights: jnp.sum(seen * form(rows, order, weights, done_))

    d_rows, d_weights = jax.grad(loss(moe_combine.moe_combine), (0, 1))(rows, jnp.asarray(weights))
    want_rows, want_weights = jax.grad(loss(lm_common._combine_lax), (0, 1))(rows, jnp.asarray(weights))
    assert d_rows.dtype == jnp.bfloat16 and np.array_equal(np.asarray(d_rows, np.float32), np.asarray(want_rows, np.float32))
    assert (np.asarray(d_rows[done:], np.float32) == 0).all()
    d_weights, want_weights = np.asarray(d_weights), np.asarray(want_weights)
    terms = np.abs(np.asarray(rows[:done].astype(jnp.float32))).sum(axis=1).max()
    assert (np.abs(d_weights - want_weights) <= H * 2.0 ** -23 * terms).all()
    assert (d_weights[back >= done] == 0).all() and (np.abs(d_weights[back < done]) > 0).all()


def test_the_tpu_interpreter_finds_no_uninitialised_read():
    """Under the TPU interpreter every buffer starts as NaN: a tile of y
    not zeroed, a row of the float32 copy read before it was made, or a row
    past ``done`` would show in y."""
    T, k, H, P, done, _ = CASES["rows_past_a_whole_block"]
    back, order, weights = _routing(T, k, done, seed=5)
    args = _rows(P, H, done, seed=5), jnp.asarray(order), jnp.asarray(weights), jnp.int32(done)
    got = np.asarray(moe_combine.combine_kernel(
        *args, interpret=pltpu.InterpretParams(uninitialized_memory="nan")
    ))
    assert np.isfinite(got).all()
    assert (np.abs(got - np.asarray(lm_common._combine_lax(*args))) <= _allowed(args[0], back, weights, done)).all()


def test_the_shapes_and_the_backend_choose_the_form(monkeypatch):
    assert not moe_combine.takes(4096, 8, 5120, 16384)              # no TPU, no hook: the lax form
    monkeypatch.setattr(moe_combine, "FORCE_INTERPRET", True)
    assert moe_combine.takes(4096, 8, 5120, 16384) and moe_combine.takes(4096, 8, 6144, 8192)   # the two cells' prefill
    assert moe_combine.takes(4116, 8, 5120, 16384)                  # a teacher-forced length
    assert not moe_combine.takes(24, 8, 5120, 192)                  # a step's 192 pairs
    assert not moe_combine.takes(4096, 8, 5120 + 64, 16384)         # no whole lane tiles
    assert not moe_combine.takes(1 << 20, 8, 128, 16384)            # no tile of y fits the core
    # the two cells where every expert is held keep the lax form: their prefill's
    # pairs are more rows than SMEM lists, their steps' fewer than the kernel takes
    assert not moe_combine.takes(50176, 6, 2048, 301056) and not moe_combine.takes(50176, 4, 2048, 200704)
    assert not moe_combine.takes(768, 6, 2048, 4608) and not moe_combine.takes(768, 4, 2048, 3072)
    # the widest tile that divides H and fits: H / 2 at the published shapes
    assert (moe_combine._tile(4096, 5120), moe_combine._tile(4096, 6144)) == (2560, 3072)
    assert moe_combine._tile(4116, 6144) == 2048


def test_an_h_the_kernel_does_not_take_is_served_by_the_lax_form(monkeypatch):
    T, k, H, P, done = 1024, 8, 192, 1024, 900
    back, order, weights = _routing(T, k, done, seed=2)
    args = _rows(P, H, done, seed=2), jnp.asarray(order), jnp.asarray(weights), jnp.int32(done)
    with pytest.raises(ValueError, match="whole 128-lane"):
        moe_combine.combine_kernel(*args, interpret=True)
    monkeypatch.setattr(moe_combine, "FORCE_INTERPRET", True)
    y, fetched, fused = lm_common._combine_held(*args)
    assert (int(fetched), int(fused)) == (T * k, 0)
    assert np.array_equal(np.asarray(y), np.asarray(lm_common._combine_lax(*args)))
    # and one it takes goes through the kernel, which fetches the computed rows
    args = (_rows(P, 256, done, seed=2),) + args[1:]
    y, fetched, fused = lm_common._combine_held(*args)
    assert (int(fetched), int(fused)) == (done, 1) and np.isfinite(np.asarray(y)).all()


# ---------------------------------------------------------------------------
# through the layer
# ---------------------------------------------------------------------------

def _layer(config, bias=None):
    c = config
    H, E, I, held = c.hidden_size, c.num_experts, c.moe_intermediate_size, lm_common.held_experts(c)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))

    def linear(*shape):
        return (jax.random.normal(next(keys), shape) * shape[-2] ** -0.5).astype(jnp.bfloat16)

    return {
        "ffn_norm": jnp.ones((H,), jnp.float32),
        "feed_forward": {
            "gate": linear(H, E), "expert_bias": jnp.zeros((E,), jnp.float32) if bias is None else bias,
            "w1": linear(held, H, I), "w3": linear(held, H, I), "w2": linear(held, I, H),
            "shared": {"w1": linear(H, I), "w3": linear(H, I), "w2": linear(I, H)},
        },
    }


@pytest.mark.parametrize("crowded", [False, True], ids=["balanced", "over_the_rows"])
def test_the_held_layer_is_the_same_layer_through_the_kernel(crowded, monkeypatch):
    """``moe_ffn`` at a share and a prefill's pairs (2,048 tokens x 4 = 8,192)
    with the kernel forced (interpret mode here) against the ``lax``
    combine: the same counts, choices and ``HeldPairs`` but for what the
    combine fetched, the same output to a rounding of the layer's term in
    the stream's bfloat16.  ``crowded``: a bias sends every token to the
    experts held here, so twice the rows' pairs land: ``over`` stays
    counted and those pairs stay left out."""
    config = _dsa_config(hidden_size=256, num_experts=32, num_experts_per_tok=4, first_expert=8)
    T, k = 2048, config.num_experts_per_tok
    bias = jnp.zeros((32,), jnp.float32).at[8:12].set(10.0) if crowded else None
    p = _layer(config, bias=bias)
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(1), (T, 256))).astype(jnp.bfloat16)

    def layer():        # a trace of its own each time: the hook is no part of a cache's key
        return jax.jit(lambda p, x: lm_common.moe_ffn(p, config, x, 1e-20))(p, x)

    want, counts, experts, plain = layer()
    monkeypatch.setattr(moe_combine, "FORCE_INTERPRET", True)
    got, counts_, experts_, fused = layer()
    assert np.array_equal(counts_, counts) and np.array_equal(experts_, experts)
    assert fused[:4] == plain[:4]                   # held, routed, over, visited
    assert (int(plain.fetched), int(plain.fused)) == (T * k, 0)
    assert (int(fused.fetched), int(fused.fused)) == (int(fused.held), 1)
    rows = lm_common.held_pair_rows(config, T)
    if crowded:
        assert (int(fused.held), int(fused.over)) == (rows, T * k - rows)
    else:
        assert 0 < int(fused.held) < rows and int(fused.over) == 0
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # y differs in its float32 sum's last bits, so a few terms round to the
    # neighbouring bfloat16: a unit in the last place of the term or of
    # the output, no more; a token with no pair computed here (y exactly 0
    # in both) comes out equal to the bit
    term = np.abs(got - np.asarray(x, np.float32))
    assert (np.abs(got - want) <= 2.0 ** -7 * (np.abs(want) + term)).all()
    assert (got == want).mean() > 0.99
    none_here = ~np.isin(np.asarray(experts), np.arange(8, 12)).any(axis=1)
    if crowded:
        assert not none_here.any()
    else:
        assert none_here.any() and np.array_equal(got[none_here], want[none_here])


# ---------------------------------------------------------------------------
# through the search: what the decoders report of the combine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [False, True], ids=["lax", "kernel"])
@pytest.mark.parametrize("toy", [_dsa_config, _dots3_config], ids=["glm_moe_dsa", "dots3_note"])
def test_the_search_reports_what_the_combine_fetched(toy, fused, monkeypatch):
    """``BeamResult.decoder_stats["moe_combine"]`` ``[prefill | steps, rows
    fetched | calls through the kernel | calls]`` beside ``moe_pairs``,
    from both decoders that hold a share.  With the kernel (its hook, and
    the line between a step and a prefill lowered to this toy's 36
    positions) every call of the prefill went through it and fetched the
    pairs held; the steps, and everything without the hook, fetched a row
    for every routed pair.  The drain's two gauges are made of these."""
    from sat_tpu.models import decoders
    from sat_tpu.ops.beam_search import beam_search_jit

    monkeypatch.setattr(moe_combine, "FORCE_INTERPRET", fused)
    monkeypatch.setattr(moe_combine, "_MIN_PAIRS", 36 * 3)
    # the hook is no part of a trace's key: a Config of its own a case (a
    # stream of whole lane tiles, which the toys' 64 is not)
    config = toy(hidden_size=128, num_data_workers=12 + fused)
    params = decoders.init_params(jax.random.PRNGKey(0), config)
    contexts = jax.random.normal(jax.random.PRNGKey(1), (2, config.num_ctx, config.dim_ctx))
    out = beam_search_jit(params, config, contexts, 1, beam_size=3, valid_size=100)
    pairs, combine = np.asarray(out.decoder_stats["moe_pairs"]), np.asarray(out.decoder_stats["moe_combine"])
    layers = config.num_hidden_layers - config.num_dense_layers
    assert pairs.shape == (2, 3) and combine.shape == (2, 3)
    assert 0 < pairs[0, 0] < pairs[0, 1] and (pairs[:, 2] == 0).all()
    steps = config.max_caption_length
    assert combine[:, 2].tolist() == [layers * 2, layers * steps]       # a call an expert layer and image / and step
    assert combine[:, 1].tolist() == [layers * 2 * fused, 0]
    assert combine[:, 0].tolist() == [pairs[0, 0] if fused else pairs[0, 1], pairs[1, 1]]
    assert np.isfinite(np.asarray(out.log_scores)).all()
