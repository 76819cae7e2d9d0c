"""The GLM-5.2 caption decoder (models/glm_moe_dsa.py: a compressed query,
an indexer that chooses the positions latent attention runs over, shared
by the layers after it; an expert layer that holds a share of its experts)
at toy widths on the CPU, held against the plain float32 reference under
benchmark/reference (which imports nothing of the program, has the expanded
form only and selects by ``lax.top_k`` on its own scores), on seeded weights
whose values are bfloat16-representable.  ``index_topk`` is 16 against a
sequence of 36 + 20, so the selection is active in the prefill (queries 16
on) and in every step.

Tolerances are tests/test_deepseek_v3.py's, for its reasons.  One more
source of difference is this stack's own: the program's indexer runs in
bfloat16, so a position whose score lies within rounding of the threshold
may be chosen on one side and not on the other; at these widths that moves
a logit by less than the tolerances, and the agreement of the selections
themselves is held separately.
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from reference import glm52_captioner as ref  # noqa: E402
from reference import params_glm52  # noqa: E402
from reference.params import nest  # noqa: E402

from sat_tpu.config import Config  # noqa: E402
from sat_tpu.models import decoders, lm_common  # noqa: E402
from sat_tpu.models import glm_moe_dsa as dsa  # noqa: E402
from sat_tpu.models.captioner import compute_loss  # noqa: E402

from fixtures import plain_moe_ffn  # noqa: E402

bs = importlib.import_module("sat_tpu.ops.beam_search")

TOY = dict(
    decoder="glm_moe_dsa", cnn="vgg16", image_size=96, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=24, num_hidden_layers=3, num_dense_layers=1, num_attention_heads=4,
    num_experts=16, num_experts_per_tok=3, experts_held=4, first_expert=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24, n_shared_experts=1,
    q_lora_rank=48, index_n_heads=8, index_head_dim=16, index_topk=16,
    indexer_types=("full", "shared", "full"),
    tie_word_embeddings=False, layer_types=("latent_attention",) * 3,
    vocabulary_size=100, max_caption_length=20, beam_size=3, norm_eps=1e-5, rope_theta=8e6,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=2.5,
)
CONFIG = Config(**TOY)


def _model(toy):
    return {**toy, "layer_types": list(toy["layer_types"]), "indexer_types": list(toy["indexer_types"])}


MODEL = _model(TOY)
N = CONFIG.num_ctx                                         # 36: a 96-px image's 6 x 6 grid
WIDTH = TOY["kv_lora_rank"] + TOY["qk_rope_head_dim"]      # 40: what a token leaves in the latent cache
LAYER_TOL = 3e-2     # x the output's scale: see tests/test_deepseek_v3.py
FORWARD_TOL = 6e-2
PATH_TOL = 1e-2
FLIP_TOL = 0.12     # one of 16 attended positions exchanged for another


def _weights(model, seed=7):
    return params_glm52.make_weights(model, seed, only=lambda n: n.startswith("params/decoder/"))


@pytest.fixture(scope="module")
def weights():
    """Seeded decoder leaves, {path: numpy}, as the benchmark makes them."""
    return _weights(MODEL)


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))


@pytest.fixture
def small_blocks(monkeypatch):
    """Whole sequences in blocks of 8 queries: seven blocks over 56."""
    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 8)


def _inputs(seed=0, B=2, T=20):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    ctx = jax.random.normal(k1, (B, N, CONFIG.dim_ctx)).astype(jnp.bfloat16).astype(jnp.float32)
    tokens = jax.random.randint(k2, (B, T), 2, CONFIG.vocabulary_size)
    return ctx, tokens


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * float(np.abs(want).max()))


def _close_but_for_flips(got, want, tol, share=0.85):
    """Logits [B, T, V] of two computations whose SELECTIONS may differ at
    the threshold (bfloat16 against float32 scores, or two orders of one
    sum): one position of 16 flipped moves a caption position's logits by
    some percent of the scale (at the published 2,048 it is one of 2,048),
    so ``share`` of the caption positions are held to ``tol`` and every
    one to ``FLIP_TOL``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.abs(want).max())
    worst = np.abs(got - want).max(axis=-1)
    assert (worst < FLIP_TOL * scale).all(), worst.max() / scale
    assert (worst < tol * scale).mean() >= share, (worst / scale).round(3)


def _subtree(weights, prefix):
    path = "params/decoder/" + prefix
    return weights[path] if path in weights else nest(weights, path)


def _cached_logits(params, config, ctx, tokens):
    """Prefill, then one step a token through the caches: (logits [B, T, V],
    the final cache, the final counters)."""
    B, T = tokens.shape
    prefix, counts, _ = dsa.prefill(params, config, ctx)
    cache = dsa.start_beams(config, prefix, 1, T, decoders.tile_beams)
    counters = dsa.init_counters(counts, T)
    words_in = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), tokens[:, :-1]], axis=1)
    step = jax.jit(lambda c, n, w: dsa.step(params, config, prefix, c, n, w))
    cached = []
    for t in range(T):
        cache, counters, logits = step(cache, counters, words_in[:, t])
        cached.append(logits)
    return jnp.stack(cached, axis=1), prefix, cache, counters


# ---------------------------------------------------------------------------
# the tree, the configuration's refusals
# ---------------------------------------------------------------------------


def test_the_program_s_tree_is_the_benchmark_s_spec():
    """Names, shapes and dtypes: an indexer in the full layers alone, the
    router over all 16 experts, the maps of the 4 held."""
    shapes = jax.eval_shape(lambda: dsa.init_params(jax.random.PRNGKey(0), CONFIG))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    program = {"params/decoder/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
               for path, leaf in flat}
    spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype)
            in params_glm52.decoder_spec(MODEL).items()}
    assert program == spec
    layer = "params/decoder/lm/layers/"
    assert spec[layer + "01/feed_forward/gate"] == ((64, 16), "bfloat16")
    assert spec[layer + "01/feed_forward/expert_bias"] == ((16,), "float32")
    assert spec[layer + "01/feed_forward/w1"] == ((4, 64, 24), "bfloat16")
    assert spec[layer + "02/self_attn/indexer/wq_b"] == ((48, 128), "bfloat16")
    assert layer + "00/self_attn/indexer/wk" in spec and layer + "01/self_attn/indexer/wk" not in spec


@pytest.mark.parametrize("change,match", [
    (dict(indexer_types=("full",) * 4), "indexer_types"),
    (dict(indexer_types=("shared", "full", "full")), "indexer_types"),
    (dict(indexer_types=("full", "windowed", "full")), "indexer_types"),
    (dict(index_head_dim=4), "index_head_dim"),
    (dict(experts_held=4, first_expert=13), "experts_held"),
    (dict(experts_held=-1), "experts_held"),
    (dict(phase="serve"), "does not run with"),
], ids=["length", "first_shared", "kind", "rope_wider_than_index_head", "share_past_the_end", "negative", "serve"])
def test_the_configuration_refuses_what_it_cannot_run(change, match):
    with pytest.raises(ValueError, match=match):
        Config(**{**TOY, **change})


def test_every_expert_held_is_the_default():
    config = Config(**{**TOY, "experts_held": 0, "first_expert": 0})
    assert lm_common.held_experts(config) == 16 and lm_common.held_experts(CONFIG) == 4
    shapes = jax.eval_shape(lambda: dsa.init_params(jax.random.PRNGKey(0), config))
    assert shapes["lm"]["layers"]["01"]["feed_forward"]["w1"].shape == (16, 64, 24)


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["random", "ties", "fewer_than_k", "signed_zeros"])
def test_the_kth_largest_is_exact_without_a_sort(case):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((6, 40)).astype(np.float32)
    k = 7
    if case == "ties":
        x = np.round(x * 2) / 2
    elif case == "fewer_than_k":
        x[:, 4:] = -np.inf
    elif case == "signed_zeros":
        x[:, ::2] = 0.0
        x[:, 1::4] = -0.0
    u = dsa._ordered_bits(jnp.asarray(x))
    got = np.asarray(dsa._kth_largest(u, k))
    want = np.sort(np.asarray(u), axis=-1)[:, -k]
    assert np.array_equal(got, want)
    if case in ("ties", "signed_zeros"):     # -0.0 orders below 0.0 in the bits: the one place they part
        return
    order = np.argsort(x, axis=-1, kind="stable")
    assert (np.diff(np.take_along_axis(np.asarray(u), order, -1).astype(np.int64), axis=-1) >= 0).all()


def test_a_query_attends_the_k_best_visible_positions_and_all_of_them_when_fewer():
    rng = np.random.default_rng(5)
    S, k = 24, 6
    scores = jnp.asarray(rng.standard_normal((S, S)).astype(np.float32))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    mask = np.asarray(dsa._select_mask(scores, causal, k))
    assert mask.sum(axis=1).tolist() == [min(k, t + 1) for t in range(S)]
    assert not (mask & ~np.asarray(causal)).any()
    want = np.asarray(ref.select(jnp.where(causal, scores, -jnp.inf), k))
    assert np.array_equal(mask, want)


def test_the_indexer_against_the_reference_s(params, weights):
    """I[t, s] of layer 0 over one sequence: bfloat16 maps against float32."""
    ctx, tokens = _inputs()
    x = lm_common.sequence_inputs(params, ctx, tokens)[0]
    m = params["lm"]["layers"]["00"]
    h = lm_common.rms_norm(x, m["operator_norm"], CONFIG.norm_eps)
    positions = jnp.arange(x.shape[0])

    @jax.jit
    def scores(h):
        qr, _ = dsa._queries(m["self_attn"], dsa.widths(CONFIG), h, positions)
        return dsa._index_scores(*dsa._index_maps(m["self_attn"]["indexer"], dsa.widths(CONFIG), h, qr, positions))

    got = scores(h)
    p = ref._f32(_subtree(weights, "lm/layers/00"))
    with jax.default_matmul_precision("highest"):
        hf = ref._rms(x.astype(jnp.float32), p["operator_norm"], 1e-5)
        qrf = ref._rms(hf @ p["self_attn"]["q_a_proj"], p["self_attn"]["q_a_layernorm"], 1e-5)
        want = ref.index_scores(p["self_attn"]["indexer"], hf, qrf, ref._Static(MODEL), "f32")
    causal = np.tril(np.ones(got.shape, bool))
    _close(np.where(causal, got, 0.0), np.where(causal, want, 0.0), LAYER_TOL)


def test_without_its_rope_the_indexer_scores_otherwise(params, monkeypatch):
    """What the benchmark's sabotage "no_index_rope" takes away is there."""
    ctx, tokens = _inputs()
    x = lm_common.sequence_inputs(params, ctx, tokens)[0]
    m = params["lm"]["layers"]["00"]
    h = lm_common.rms_norm(x, m["operator_norm"], CONFIG.norm_eps)

    def scores():
        positions = jnp.arange(x.shape[0])
        qr, _ = dsa._queries(m["self_attn"], dsa.widths(CONFIG), h, positions)
        return np.asarray(dsa._index_scores(*dsa._index_maps(m["self_attn"]["indexer"], dsa.widths(CONFIG), h, qr, positions)))

    turned = scores()
    monkeypatch.setattr(dsa, "_index_rope", lambda x, positions, config: x)
    plain = scores()
    assert np.array_equal(turned[0, :1], plain[0, :1])          # position 0 turns by nothing
    assert np.abs(turned - plain)[20:].max() > 0.05 * np.abs(turned).max()


# ---------------------------------------------------------------------------
# whole sequences and the cache against the reference's full forward
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# the query: whole sequences take the rope's partner out of a product
# ---------------------------------------------------------------------------


def test_the_lanes_a_whole_sequence_s_rope_rewrites():
    """The rotary part widened to whole tiles of 128 lanes at the head's
    end: the published head's second tile (its 64 rotary lanes behind 64
    of nope), the whole head at the tests' widths."""
    from test_aot_tpu import _glm52_config

    assert dsa._turned_lanes(dsa.widths(_glm52_config())) == 128
    assert dsa._turned_lanes(dsa.widths(CONFIG)) == 24
    swapped = dsa._swapped_query_map({"q_b_proj": jnp.ones((48, 4 * 24), jnp.bfloat16)}, dsa.widths(CONFIG))
    assert swapped.shape == (48, 4, 24) and not np.asarray(swapped[..., :16], np.float32).any()


@pytest.mark.parametrize("lifted", [False, True], ids=["made_here", "made_by_the_caller"])
@pytest.mark.parametrize("theta", [8e6, 1e6], ids=["glm52", "kanana2"])
def test_the_folded_query_is_the_rolled_one_to_the_bit(params, theta, lifted):
    """``_queries(by_head=True)``, head-major with the partner out of
    ``qr W_r P``, against the step's form over the same rows (one product,
    the rotary part split off, rolled and concatenated back): the same dot
    products and the same roundings, so the same bfloat16 numbers."""
    config = dataclasses.replace(CONFIG, rope_theta=theta)
    m = params["lm"]["layers"]["02"]["self_attn"]
    h = jax.random.normal(jax.random.PRNGKey(3), (56, 64)).astype(jnp.bfloat16)
    positions = jnp.arange(56)
    swapped = dsa._swapped_query_map(m, dsa.widths(config)) if lifted else None
    qr, q = jax.jit(lambda h: dsa._queries(m, dsa.widths(config), h, positions, by_head=True, swapped=swapped))(h)
    want_qr, want = jax.jit(lambda h: dsa._queries(m, dsa.widths(config), h, positions))(h)
    assert q.shape == (4, 56, 24) and q.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(qr, np.float32), np.asarray(want_qr, np.float32))
    np.testing.assert_array_equal(np.asarray(q, np.float32), np.asarray(want, np.float32).transpose(1, 0, 2))


def test_the_step_s_query_is_the_one_product_rolled_as_before(params):
    """A step's rows do not take the partner's product (reading ``W_qb``
    bounds them): ``_queries`` without ``by_head`` is one product, split,
    turned by ``deepseek_v3._rope``'s rolls and concatenated, to the bit."""
    from test_deepseek_v3 import _rope_rolled

    m = params["lm"]["layers"]["00"]["self_attn"]
    h = jax.random.normal(jax.random.PRNGKey(4), (6, 1, 64)).astype(jnp.bfloat16)
    position = jnp.array([N + 3])
    qr, q = jax.jit(lambda h: dsa._queries(m, dsa.widths(CONFIG), h, position))(h)

    def before(h):
        qr = lm_common.rms_norm(lm_common.mm(h, m["q_a_proj"]), m["q_a_layernorm"], CONFIG.norm_eps)
        qr = qr.astype(jnp.bfloat16)
        q = lm_common.mm(qr, m["q_b_proj"]).reshape(6, 1, 4, 24)
        turned = _rope_rolled(q[..., 16:].astype(jnp.float32), position, CONFIG.rope_theta)
        return qr, jnp.concatenate([q[..., :16], turned.astype(jnp.bfloat16)], axis=-1)

    want_qr, want = jax.jit(before)(h)
    np.testing.assert_array_equal(np.asarray(qr, np.float32), np.asarray(want_qr, np.float32))
    np.testing.assert_array_equal(np.asarray(q, np.float32), np.asarray(want, np.float32))


def _lane_faults(jaxpr, lanes=128):
    """What the chip pays for in a query's jaxpr, stated where the CPU can
    see it: a roll; a slice, concatenation or pad along the last axis of a
    rank-3 array at an offset or width that is no multiple of ``lanes``; a
    float32 rank-3 array narrower than ``lanes``."""
    from test_deepseek_v3 import _all_eqns

    faults = []
    for e in _all_eqns(jaxpr):
        name = e.primitive.name
        if "roll" in name or "roll" in str(e.params.get("name", "")):
            faults.append(f"roll: {e.params.get('name', name)}")
        shapes = [v.aval.shape for v in list(e.invars) + list(e.outvars) if hasattr(v.aval, "shape")]
        if name == "slice" and len(shapes[0]) == 3:
            start, limit = e.params["start_indices"][-1], e.params["limit_indices"][-1]
            if start % lanes or (limit - start) % lanes:
                faults.append(f"slice [{start}:{limit}] of {shapes[0]}")
        if name == "concatenate" and len(shapes[0]) == 3 and e.params["dimension"] == 2:
            if any(shape[-1] % lanes for shape in shapes[:-1]):
                faults.append(f"concatenate of {shapes[:-1]}")
        if name == "dynamic_update_slice" and len(shapes[0]) == 3:
            start = getattr(e.invars[-1], "val", None)      # a literal, or the fault is not knowing it
            if start is None or int(start) % lanes or shapes[1][-1] % lanes:
                faults.append(f"update of {shapes[0]} at {start} by {shapes[1]}")
        if name == "pad" and len(shapes[0]) == 3:
            low, high, _ = e.params["padding_config"][-1]
            if low % lanes or high % lanes:
                faults.append(f"pad {low, high} of {shapes[0]}")
        for v in e.outvars:
            aval = v.aval
            if getattr(aval, "ndim", 0) == 3 and aval.dtype == jnp.float32 and aval.shape[-1] < lanes:
                faults.append(f"float32{aval.shape} from {name}")
    return faults


@pytest.mark.parametrize("by_head", [True, False], ids=["folded", "rolled"])
def test_the_prefill_s_query_cuts_no_tile_of_lanes_and_rolls_nothing(by_head):
    """``_queries(by_head=True)`` at the published widths (``qr``
    [4096, 2048], 64 heads of 192 + 64), traced and not run, its swapped
    map handed in as ``sequence_forward`` hands it: no roll, nothing cut or
    joined inside a tile of 128 lanes, no float32 array under 128 lanes.
    The step's form over the same rows is the control: it has all three."""
    from test_aot_tpu import _glm52_config

    config = _glm52_config()
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    m = {"q_a_proj": sd(6144, 2048), "q_a_layernorm": sd(2048), "q_b_proj": sd(2048, 64 * 256)}
    positions = jnp.arange(4096)
    if by_head:
        jaxpr = jax.make_jaxpr(
            lambda m, h, swapped: dsa._queries(m, dsa.widths(config), h, positions, by_head=True, swapped=swapped)
        )(m, sd(4096, 6144), sd(2048, 64, 128))
        assert jaxpr.out_avals[1].shape == (64, 4096, 256)
        assert not _lane_faults(jaxpr.jaxpr)
    else:
        jaxpr = jax.make_jaxpr(lambda m, h: dsa._queries(m, dsa.widths(config), h, positions))(m, sd(4096, 6144))
        faults = " ".join(_lane_faults(jaxpr.jaxpr))
        assert "roll" in faults and "slice [192:256]" in faults and "float32(4096, 64, 64)" in faults, faults


def test_whole_sequences_swap_once_a_layer_and_the_steps_never(params, monkeypatch):
    """``sequence_forward`` makes every layer's swapped map ONCE, outside
    its loop over the images, and hands it down (teacher forcing, whose
    loss and gradient are held against the reference below, and the
    prefill alike); a step makes none."""
    made, handed = [], []
    make, queries = dsa._swapped_query_map, dsa._queries

    def swapped_query_map(m, config):
        made.append(m)
        return make(m, config)

    def spy(m, config, h, positions, by_head=False, swapped=None):
        handed.append((by_head, swapped))
        return queries(m, config, h, positions, by_head, swapped)

    monkeypatch.setattr(dsa, "_swapped_query_map", swapped_query_map)
    monkeypatch.setattr(dsa, "_queries", spy)
    ctx, tokens = _inputs()
    jax.eval_shape(lambda: dsa.teacher_forced(params, CONFIG, ctx, tokens))
    assert len(made) == 3 and len(handed) == 3 and all(b and s is not None for b, s in handed)
    del made[:], handed[:]
    prefix, counts, _ = jax.eval_shape(lambda: dsa.prefill(params, CONFIG, ctx))
    assert len(made) == 3 and len(handed) == 3 and all(b and s is not None for b, s in handed)
    del made[:], handed[:]
    zeros = lambda tree: jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), tree)  # noqa: E731
    prefix = zeros(prefix)
    cache = dsa.start_beams(CONFIG, prefix, 1, 20, decoders.tile_beams)
    jax.eval_shape(lambda: dsa.step(params, CONFIG, prefix, cache, dsa.init_counters(zeros(counts), 20),
                                    jnp.zeros((2,), jnp.int32)))
    assert not made and handed == [(False, None)] * 3


@pytest.mark.parametrize("blocks", ["one_block", "blocks_of_8"])
def test_teacher_forced_logits_against_the_plain_full_forward(params, weights, blocks, monkeypatch):
    if blocks == "blocks_of_8":
        monkeypatch.setattr(lm_common, "QUERY_BLOCK", 8)
    ctx, tokens = _inputs()
    got = dsa.teacher_forced(params, CONFIG, ctx, tokens)
    want, _, selections = ref.forward(lambda pre: _subtree(weights, pre), MODEL, np.asarray(ctx), np.asarray(tokens))
    assert got.shape == (2, 20, 100)
    _close_but_for_flips(got, want, FORWARD_TOL)
    # the selection was active: every caption position attends 16 of 37..56
    # (a tie at the threshold adds one: every relu at zero is a score of exactly zero)
    assert selections.shape == (2, 2, 20, N + 20)
    assert selections.sum(-1).min() == 16 and selections.sum(-1).mean() < 16.2


def test_the_blocks_of_a_sequence_change_nothing(params, monkeypatch):
    ctx, tokens = _inputs(seed=4)
    whole = dsa.teacher_forced(params, CONFIG, ctx, tokens)
    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 8)
    blocked = dsa.teacher_forced(params, CONFIG, ctx, tokens)
    _close_but_for_flips(blocked, whole, PATH_TOL, share=0.9)


def test_prefill_then_20_cached_steps_equal_the_full_forward(params, weights, small_blocks):
    """Logits, not tokens: the N prefix positions once (expanded, masked,
    in blocks), then 20 one-token steps (top-k, mask, absorbed) through
    the caches, against (a) the program's own full forward and (b) the
    reference's full forward with no cache.  index_topk = 16 < 37: every
    step selects."""
    ctx, tokens = _inputs()
    B, T = tokens.shape
    full = dsa.teacher_forced(params, CONFIG, ctx, tokens)
    cached, prefix, cache, counters = _cached_logits(params, CONFIG, ctx, tokens)
    assert all(x.shape == (B, N, WIDTH) for x in prefix.latents) and len(prefix.latents) == 3
    assert [x.shape for x in prefix.index_keys] == [(B, N, 16)] * 2
    assert [x.shape for x in cache.index_keys] == [(B, T, 16)] * 2
    # the two forms sum the indexer's products in another order: where that
    # flips a position at the threshold a logit moves by some percent of the
    # scale; where it does not the forms agree as two paths do
    _close_but_for_flips(cached, full, PATH_TOL, share=0.8)
    assert int(counters.t) == T and cached.shape == (B, T, 100)
    assert np.asarray(counters.moe_counts).sum(axis=1).tolist() == [B * (N + T) * 3] * 2
    want, _, _ = ref.forward(lambda pre: _subtree(weights, pre), MODEL, np.asarray(ctx), np.asarray(tokens))
    _close_but_for_flips(cached, want, FORWARD_TOL)


@pytest.mark.parametrize("hook,blocks", [(True, [3, 3]), (False, [0, 3])], ids=["fused", "lax"])
def test_prefill_through_the_fused_kernel_then_20_cached_steps_equal_the_full_forward(
        params, weights, monkeypatch, hook, blocks):
    """The prefill's attention through ops/flash_prefill.py (interpret
    mode, under its test hook; 36 positions = 3 query blocks of 12, the
    last two under the selection's mask), then 20 cached steps, against the
    reference's full forward at the ``lax`` form's own tolerance; the
    counter says which form ran, and without the hook it is the ``lax``
    blocks."""
    from sat_tpu.ops import flash_prefill

    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 12)
    monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", hook)
    ctx, tokens = _inputs()
    cached, prefix, _, counters = _cached_logits(params, CONFIG, ctx, tokens)
    assert np.asarray(counters.fused).tolist() == blocks
    want, _, _ = ref.forward(lambda pre: _subtree(weights, pre), MODEL, np.asarray(ctx), np.asarray(tokens))
    _close_but_for_flips(cached, want, FORWARD_TOL)
    if hook:    # the two forms of the prefill leave the same latents (layer 0's are made before any attention)
        monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", False)
        plain, _, _ = dsa.prefill(params, CONFIG, ctx)
        assert np.array_equal(np.asarray(prefix.latents[0], np.float32), np.asarray(plain.latents[0], np.float32))
        for got, lax_form in zip(prefix.latents[1:], plain.latents[1:]):
            _close(got, lax_form, PATH_TOL)


def test_the_steps_choose_what_the_reference_chooses(params, weights):
    """The record of chosen positions against the reference's S_t at the
    caption's positions, full layer by full layer and step by step."""
    ctx, tokens = _inputs(seed=1)
    B, T = tokens.shape
    _, _, cache, counters = _cached_logits(params, CONFIG, ctx, tokens)
    chosen = np.asarray(cache.selected).reshape(B, T, 2, 16)
    assert (chosen >= 0).all() and (chosen <= N + np.arange(T)[None, :, None, None]).all()
    assert np.asarray(counters.attended).tolist() == [B * T * 2 * 16, 2 * B * sum(N + t + 1 for t in range(T))]
    _, _, selections = ref.forward(lambda pre: _subtree(weights, pre), MODEL, np.asarray(ctx), np.asarray(tokens))
    agree = np.take_along_axis(selections.transpose(1, 2, 0, 3), chosen, axis=-1)   # [B, T, full, k]
    assert agree.mean() > 0.93, agree.mean()
    assert (np.sort(chosen, -1)[..., 1:] != np.sort(chosen, -1)[..., :-1]).all()     # 16 distinct positions


def test_a_shared_layer_attends_the_preceding_full_layer_s_choice(params, monkeypatch):
    """Layer 1 is handed layer 0's (positions, visible), layer 2 makes its
    own; in a whole sequence the same with the blocks' masks.  Traced, not
    run: the values are the tracers themselves."""
    ctx, _ = _inputs()
    seen, handed, step_, seq_ = [], [], dsa.attend_step, dsa.attend_sequence

    def attend_step(m, config, h, prefix, suffix, t, chosen):
        out = step_(m, config, h, prefix, suffix, t, chosen)
        seen.append((chosen, out[2], "indexer" in m))
        return out

    def attend_sequence(m, config, h, masks, fused=False, swapped=None):
        out = seq_(m, config, h, masks, fused, swapped)
        handed.append((masks, out[3]))
        return out

    monkeypatch.setattr(dsa, "attend_step", attend_step)
    monkeypatch.setattr(dsa, "attend_sequence", attend_sequence)

    def both():
        prefix, counts, _ = dsa.prefill(params, CONFIG, ctx)
        cache = dsa.start_beams(CONFIG, prefix, 1, 20, decoders.tile_beams)
        return dsa.step(params, CONFIG, prefix, cache, dsa.init_counters(counts, 20), jnp.zeros((2,), jnp.int32))

    jax.eval_shape(both)
    assert [s[2] for s in seen] == [True, False, True]
    assert seen[0][0] is None and seen[2][0] is None and seen[1][0] is seen[0][1]
    assert seen[2][1] is not seen[0][1]
    assert handed[0][0] is None and handed[2][0] is None and handed[1][0] is handed[0][1]


def test_a_shared_layer_s_output_depends_on_the_choice_it_is_handed(params):
    ctx, _ = _inputs()
    prefix, _, _ = dsa.prefill(params, CONFIG, ctx)
    cache = dsa.start_beams(CONFIG, prefix, 1, 20, decoders.tile_beams)
    m = params["lm"]["layers"]["01"]["self_attn"]
    assert "indexer" not in m
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 64))
    def run(lo):
        positions = jnp.tile(jnp.arange(lo, lo + 16, dtype=jnp.int32), (2, 1))
        attend = jnp.tile((jnp.arange(N + 20) >= lo) & (jnp.arange(N + 20) < lo + 16), (2, 1))
        return jax.jit(lambda: dsa.attend_step(
            m, dsa.widths(CONFIG), h, (prefix.latents[1], None), (cache.latents[1], None), jnp.int32(0),
            (positions, jnp.ones_like(positions, bool), attend))[0])()

    a, b = run(0), run(16)
    assert float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) > 1e-3


@pytest.mark.parametrize("form", ["teacher_forced", "prefill_and_steps"])
def test_index_topk_over_the_sequence_is_plain_causal_latent_attention(form):
    """With the selection off (index_topk 64 >= 56) both forms are the
    reference's plain causal latent attention: its own selection chooses
    everything visible."""
    toy = {**TOY, "index_topk": 64}
    config, model = Config(**toy), _model(toy)
    weights = _weights(model)
    params = jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))
    ctx, tokens = _inputs(seed=6)
    want, _, selections = ref.forward(lambda pre: _subtree(weights, pre), model, np.asarray(ctx), np.asarray(tokens))
    assert np.array_equal(selections[0, 0], np.tril(np.ones((56, 56), bool))[N:])
    if form == "teacher_forced":
        got = dsa.teacher_forced(params, config, ctx, tokens)
    else:
        got, _, cache, counters = _cached_logits(params, config, ctx, tokens)
        chosen = np.asarray(cache.selected).reshape(2, 20, 2, 56)
        for t in (0, 7, 19):      # every visible position, and -1 for the rest
            assert sorted(chosen[0, t, 0].tolist()) == [-1] * (19 - t) + list(range(N + t + 1))
        assert np.asarray(counters.attended)[0] == np.asarray(counters.attended)[1]
    _close(got, want, FORWARD_TOL)


def test_the_reorder_moves_the_indexer_s_keys_with_the_latents():
    """One tree-wide gather: per-beam latents, indexer keys, the record of
    routes and the record of chosen positions follow their beam; the
    counters are untouched; the prefix is no part of the state at all."""
    B, K = 2, 3
    rows = jnp.arange(B * K, dtype=jnp.float32)
    leaf = lambda *shape: rows.reshape((B * K,) + (1,) * len(shape)) + jnp.zeros((B * K,) + shape)  # noqa: E731
    cache = dsa.DsaCache(latents=(leaf(5, WIDTH), leaf(5, WIDTH)), index_keys=(leaf(5, 16),),
                         routes=leaf(30), selected=leaf(80))
    shared = dsa.DsaCounters(t=jnp.int32(7), moe_counts=jnp.arange(8).reshape(2, 4),
                             step_visits=jnp.arange(10).reshape(2, 5), pairs=jnp.arange(6).reshape(2, 3),
                             attended=jnp.arange(2), fused=jnp.arange(2))
    parent = jnp.array([[2, 0, 1], [1, 1, 0]])
    moved = bs._reorder_beams(bs.StepState(cache, shared), B, K, jnp.arange(B)[:, None], parent)
    want = (jnp.arange(B)[:, None] * K + parent).reshape(-1).astype(jnp.float32)
    leaves = jax.tree_util.tree_leaves(moved.beam)
    assert len(leaves) == 5
    for x in leaves:
        assert np.array_equal(np.asarray(x).reshape(B * K, -1)[:, 0], np.asarray(want))
    assert int(moved.shared.t) == 7 and np.array_equal(moved.shared.pairs, shared.pairs)


# ---------------------------------------------------------------------------
# the expert layer that holds a share
# ---------------------------------------------------------------------------


def _layer_and_tokens(params, T, seed=11):
    p = params["lm"]["layers"]["01"]
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(seed), (T, 64))).astype(jnp.bfloat16)
    return p, x


@pytest.mark.parametrize("T", [24, 300])
def test_the_shares_add_up_to_the_uncut_layer(T):
    """experts_held 4 of 16: the routed parts of the four shares and the
    shared expert counted ONCE are the uncut layer's output, for a step's
    24 rows and for 300 tokens."""
    toy = {**TOY, "experts_held": 0, "first_expert": 0}
    whole = Config(**toy)
    weights = _weights(_model(toy))
    params = jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))
    p, x = _layer_and_tokens(params, T)
    want, counts, experts = jax.jit(lambda p, x: plain_moe_ffn(p, whole, x, 1e-20))(p, x)
    routed, shared_part = jnp.zeros((T, 64), jnp.float32), None
    seen = 0
    for first in (0, 4, 8, 12):
        f = p["feed_forward"]
        held = {**p, "feed_forward": {**f, **{w: f[w][first:first + 4] for w in ("w1", "w3", "w2")}}}
        config = Config(**{**toy, "experts_held": 4, "first_expert": first})
        share = jax.jit(lambda p, x, config=config: lm_common.moe_ffn(p, config, x, 1e-20))
        y, counts_, experts_, pairs = share(held, x)
        assert np.array_equal(counts_, counts) and np.array_equal(experts_, experts)
        alone = {**held, "feed_forward": {k: v for k, v in held["feed_forward"].items() if k != "shared"}}
        y_routed = share(alone, x)[0]
        routed = routed + (y_routed.astype(jnp.float32) - x.astype(jnp.float32))
        shared_part = y.astype(jnp.float32) - y_routed.astype(jnp.float32)
        assert int(pairs.over) == 0 and int(pairs.routed) == T * 3
        assert int(pairs.held) == int(counts[first:first + 4].sum())
        seen += int(pairs.held)
    assert seen == T * 3
    total = x.astype(jnp.float32) + routed + shared_part
    _close(total, want, PATH_TOL)


def test_a_share_against_the_reference_s_share(params, weights):
    p, x = _layer_and_tokens(params, 40)
    got, _, experts, _ = lm_common.moe_ffn(p, CONFIG, x, 1e-20)
    pf = ref._f32(_subtree(weights, "lm/layers/01"))
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.ffn(pf, x.astype(jnp.float32), True, ref._Static(MODEL))
    assert (np.sort(experts, -1) == np.sort(chosen, -1)).all(-1).mean() > 0.9
    _close(got, want, LAYER_TOL)


def test_every_expert_held_is_today_s_layer_to_the_bit():
    """``experts_held`` 0 is the one expert layer's plain case: to the bit
    the layer with no share written out (``fixtures.plain_moe_ffn``), every
    routed pair held, fetched and none over."""
    toy = {**TOY, "experts_held": 0, "first_expert": 0}
    config = Config(**toy)
    params = jax.tree_util.tree_map(jnp.asarray, nest(_weights(_model(toy)), "params/decoder"))
    for T in (24, 300):
        p, x = _layer_and_tokens(params, T)
        want, counts, experts = plain_moe_ffn(p, config, x, 1e-20)
        got, counts_, experts_, pairs = lm_common.moe_ffn(p, config, x, 1e-20)
        assert np.array_equal(np.asarray(got).view(np.uint16), np.asarray(want).view(np.uint16))
        assert np.array_equal(counts_, counts) and np.array_equal(experts_, experts)
        assert (int(pairs.held), int(pairs.routed), int(pairs.over)) == (T * 3, T * 3, 0)
        assert (int(pairs.fetched), int(pairs.fused)) == (T * 3, 0)
        assert int(pairs.visited) == int((counts > 0).sum())


def test_the_rows_of_a_share_and_what_bounds_them():
    published = Config(**{**TOY, "num_experts": 256, "num_experts_per_tok": 8, "experts_held": 16, "first_expert": 0})
    assert lm_common.held_pair_rows(published, 24) == 24 * 8          # a step: the hard bound
    assert lm_common.held_pair_rows(published, 4096) == 4 * 2048      # an image: 4 x the balanced share
    assert lm_common.held_pair_rows(CONFIG, 24) == 24 * 3
    assert lm_common.held_pair_rows(CONFIG, 2000) == 4 * 1500


def test_pairs_over_the_rows_are_counted_and_left_out(params, monkeypatch):
    """Rows for 5 pairs where more land: the counter says how many were
    left out, and the output is the layer's with exactly those left out."""
    p, x = _layer_and_tokens(params, 40)
    _, counts, experts, sound = lm_common.moe_ffn(p, CONFIG, x, 1e-20)
    landed = int(counts[4:8].sum())
    assert int(sound.held) == landed > 5 and int(sound.over) == 0
    assert int(sound.visited) == int((counts[4:8] > 0).sum())
    monkeypatch.setattr(lm_common, "held_pair_rows", lambda config, tokens: 5)
    y, _, _, cut = lm_common.moe_ffn(p, CONFIG, x, 1e-20)
    assert (int(cut.held), int(cut.over), int(cut.routed)) == (5, landed - 5, 120)
    assert np.isfinite(np.asarray(y, np.float32)).all()


# ---------------------------------------------------------------------------
# through the search
# ---------------------------------------------------------------------------


def test_the_search_reports_what_it_chose_and_what_it_held(params):
    """``BeamResult.decoder_stats``: the chosen positions of each LIVE
    beam's own tokens (equal to what a whole-sequence pass over its caption
    chooses, up to near-ties), the pairs held / routed / over, the
    positions attended / visible, and the state's bytes with the indexer's
    keys in."""
    ctx, _ = _inputs(seed=2, B=4)
    T, K = 8, 3
    out = bs.beam_search_jit(params, CONFIG, ctx, 1, beam_size=K, valid_size=100, max_len=T, early_exit=False)
    stats = out.decoder_stats
    assert stats["step_selected"].shape == (4, K, T, 2, 16)
    assert stats["prefix_routes"].shape == (4, N, 6) and stats["step_routes"].shape == (4, K, T, 6)
    pairs = np.asarray(stats["moe_pairs"])                   # [prefill | steps, held | routed | over]
    assert pairs[:, 1].tolist() == [2 * 4 * N * 3, 2 * 4 * K * T * 3] and (pairs[:, 2] == 0).all()
    assert (0 < pairs[:, 0]).all() and (pairs[:, 0] < pairs[:, 1]).all()
    attended, visible = np.asarray(stats["dsa_attended"]).tolist()
    assert attended == 2 * 4 * K * T * 16 and visible == 2 * 4 * K * sum(N + t + 1 for t in range(T))
    per_image = 3 * N * WIDTH * 2 + 2 * N * 16 * 2
    per_beam = 3 * T * WIDTH * 2 + 2 * T * 16 * 2 + T * 6 * 4 + T * 2 * 16 * 4
    assert int(stats["state_bytes"]) == 4 * per_image + 4 * K * per_beam
    agree = []
    for b in range(4):
        for beam in range(K):
            words = out.words[b, beam]
            if int(out.lengths[b, beam]) < T or bool((words == 1).any()):
                continue
            x = lm_common.sequence_inputs(params, ctx[b:b + 1], words[None])[0]
            masks = _sequence_masks(params, x)
            chosen = np.asarray(stats["step_selected"][b, beam])             # [T, full, k]
            for f in range(2):
                agree.append(np.take_along_axis(masks[f][N:], chosen[:, f], axis=-1))
    assert len(agree) >= 4 and np.mean(agree) > 0.93, np.mean(agree)


def _sequence_masks(params, x):
    """The whole-sequence form's selections [S, S] of each full layer."""
    return [np.asarray(m) for m in _masks_jit(params, x)]


@jax.jit
def _masks_jit(params, x):
    seen, seq_ = [], dsa.attend_sequence

    def attend_sequence(m, config, h, masks, fused=False, swapped=None):
        out = seq_(m, config, h, masks, fused, swapped)
        if masks is None:
            S = h.shape[0]
            seen.append(jnp.concatenate([jnp.pad(b, ((0, 0), (0, S - b.shape[1]))) for b in out[3]]))
        return out

    dsa.attend_sequence = attend_sequence
    try:
        dsa._one_sequence(params["lm"], CONFIG, x, 0)
    finally:
        dsa.attend_sequence = seq_
    return seen


def test_the_prefix_stays_per_image_in_the_search(params):
    ctx, _ = _inputs(B=2)
    search = decoders.search(params, CONFIG, ctx, 3, 20)
    beam = jax.tree_util.tree_leaves(search.state0.beam)
    assert all(x.shape[0] == 6 and N not in x.shape[1:] for x in beam)
    assert search.alpha_width == 0
    text = jax.jit(lambda: search.step_fn(search.state0, jnp.zeros((6,), jnp.int32))[1]).lower().as_text()
    assert f"tensor<6x{N}x{WIDTH}x" not in text and f"tensor<2x{N}x{WIDTH}xbf16>" in text


def test_no_module_but_decoders_tests_the_decoder_field():
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "sat_tpu")
    hits = []
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    if "glm_moe_dsa" in f.read():
                        hits.append(os.path.relpath(os.path.join(folder, name), root))
    # models/dots3_note.py imports the module (its full layers are this block at other widths)
    # and, like it, never looks at Config.decoder
    assert sorted(hits) == ["config.py", "models/decoders.py", "models/dots3_note.py", "models/glm_moe_dsa.py"]
    for name in ("models/dots3_note.py", "models/glm_moe_dsa.py"):
        with open(os.path.join(root, name)) as f:
            assert "config.decoder" not in f.read(), name


# ---------------------------------------------------------------------------
# training the connector, the frozen stack, the checkpoint
# ---------------------------------------------------------------------------


def test_train_loss_and_connector_gradient_against_the_reference(params, weights):
    ctx, tokens = _inputs(seed=2, T=8)
    masks = (jnp.arange(8)[None, :] < jnp.array([[8], [5]])).astype(jnp.float32)
    batch = {"contexts": ctx, "word_idxs": tokens, "masks": masks}

    def loss_of(connector):
        variables = {"params": {"cnn": {}, "decoder": {**params, "connector": connector}}}
        return compute_loss(variables, CONFIG, batch, rng=jax.random.PRNGKey(0), train=True)

    (loss, aux), grad = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(params["connector"])
    assert aux["attentions"] is None
    want_loss, want_grad = ref.train_loss(weights, MODEL, np.asarray(ctx), np.asarray(tokens), masks)
    assert abs(float(loss) - float(want_loss)) < 2e-2 * float(want_loss)
    for leaf in ("kernel", "bias"):
        g, w = np.asarray(grad[leaf], np.float64).ravel(), np.asarray(want_grad[leaf], np.float64).ravel()
        assert g @ w / (np.linalg.norm(g) * np.linalg.norm(w)) > 0.99, leaf
        assert abs(np.linalg.norm(g) / np.linalg.norm(w) - 1) < 0.06, leaf


def test_the_stack_is_frozen_as_the_cnn_is(params):
    from sat_tpu.train.step import merge_params, split_trainable

    tree = {"cnn": {"conv": jnp.ones(2)}, "decoder": params}
    trainable, frozen = split_trainable(tree, CONFIG)
    assert set(trainable["decoder"]) == {"connector"} and set(frozen["decoder"]) == {"lm"}
    merged = merge_params(frozen, trainable)
    assert jax.tree_util.tree_structure(merged) == jax.tree_util.tree_structure(tree)
    thawed, _ = split_trainable(tree, dataclasses.replace(CONFIG, train_lm=True))
    assert set(thawed["decoder"]) == {"connector", "lm"}


def test_the_tree_round_trips_the_checkpoint_bit_exactly(tmp_path, params):
    from sat_tpu.train.checkpoint import restore_checkpoint, save_checkpoint
    from sat_tpu.train.step import TrainState

    config = Config(**{**TOY, "save_dir": str(tmp_path)})
    state = TrainState(params={"decoder": params}, batch_stats={}, opt_state=(), step=jnp.int32(0))
    save_checkpoint(state, config)
    restored, count = restore_checkpoint(jax.eval_shape(lambda: state), save_dir=str(tmp_path))
    assert count == len(jax.tree_util.tree_leaves(params))
    for got, want in zip(jax.tree_util.tree_leaves(restored.params["decoder"]), jax.tree_util.tree_leaves(params)):
        assert got.dtype == want.dtype and np.array_equal(
            np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8))
