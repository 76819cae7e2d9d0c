"""The Qwen3-Next caption decoder (models/qwen3_next.py: three Gated
DeltaNet layers to one gated grouped-query layer, ``(1 + w)`` norms, a
softmax router over a held share of the experts beside ONE gated shared
expert, an untied head; a float32 matrix state a beam that every token
rewrites, conv taps, keys and values in one search state) at toy widths on
the CPU, held against the plain float32 reference under benchmark/reference
(which imports nothing of the program and runs the recurrence a token at a
time), on seeded weights whose values are bfloat16-representable.  The toy
has 2 key heads serving 6 value heads (nk != nv, so the per-key-head layout
of ``in_proj_qkvz`` and ``in_proj_ba`` is exercised), a rotary part of 8 of
a head's 16 lanes, and a 160-px image: 100 prefix positions + 20, so whole
sequences span two chunks of 64, the second ragged.

Tolerances, each x the compared output's scale (tests/test_deepseek_v3.py
has the reasons: bfloat16 products and a bfloat16 residual stream against
float32 ``highest``): a layer 3e-2, the whole forward 6e-2, two paths of
the program against each other 1e-2; two float32 forms of ONE recurrence
1e-5.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from reference import params_qwen3next  # noqa: E402
from reference import qwen3next_captioner as ref  # noqa: E402
from reference.params import nest  # noqa: E402

from sat_tpu.config import Config  # noqa: E402
from sat_tpu.models import decoders, lm_common  # noqa: E402
from sat_tpu.models import qwen3_next as qn  # noqa: E402
from sat_tpu.ops import gdn_chunk  # noqa: E402

from test_glm_moe_dsa import FORWARD_TOL, LAYER_TOL, PATH_TOL, _close  # noqa: E402

bs = importlib.import_module("sat_tpu.ops.beam_search")

KINDS = ("linear_attention", "linear_attention", "linear_attention", "full_attention")
TOY = dict(
    decoder="qwen3_next", cnn="vgg16", image_size=160, hidden_size=64, moe_intermediate_size=24,
    num_hidden_layers=4, num_dense_layers=0, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.5, linear_num_key_heads=2, linear_num_value_heads=6, linear_key_head_dim=16,
    linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=3, experts_held=8,
    first_expert=0, n_shared_experts=1, shared_expert_intermediate_size=20, shared_expert_gate=True,
    scoring_func="softmax", layer_types=KINDS, tie_word_embeddings=False, vocabulary_size=100,
    max_caption_length=20, beam_size=3, norm_eps=1e-6, rope_theta=100.0, norm_topk_prob=True,
    use_expert_bias=False, routed_scaling_factor=1.0,
)
CONFIG = Config(**TOY)
EXACT_TOL = 1e-5


def _model(toy):
    return {**toy, "layer_types": list(toy["layer_types"])}


MODEL = _model(TOY)
N = CONFIG.num_ctx                  # 100: a 160-px image's 10 x 10 grid
NV, DK, DV, WIDTH = 6, 16, 8, 2 * 2 * 16 + 6 * 8


def _weights(model, seed=7):
    return params_qwen3next.make_weights(model, seed, only=lambda n: n.startswith("params/decoder/"))


@pytest.fixture(scope="module")
def weights():
    return _weights(MODEL)


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))


def _inputs(seed=0, B=2, T=20, n=N):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    ctx = jax.random.normal(k1, (B, n, CONFIG.dim_ctx)).astype(jnp.bfloat16).astype(jnp.float32)
    tokens = jax.random.randint(k2, (B, T), 2, CONFIG.vocabulary_size)
    return ctx, tokens


def _subtree(weights, prefix):
    path = "params/decoder/" + prefix
    return weights[path] if path in weights else nest(weights, path)


def _reference(weights, ctx, tokens, model=MODEL, mode="f32"):
    return ref.forward(lambda pre: _subtree(weights, pre), model, np.asarray(ctx), np.asarray(tokens), mode=mode)


def _cached_logits(params, config, ctx, tokens):
    """Prefill, then one step a token through the caches: (logits
    [B, T, V], the final cache, the final counters)."""
    B, T = tokens.shape
    prefix, counts, _ = jax.jit(lambda p, c: qn.prefill(p, config, c))(params, ctx)
    cache = qn.start_beams(config, prefix, 1, T, decoders.tile_beams)
    counters = qn.init_counters(counts, T)
    words_in = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), tokens[:, :-1]], axis=1)
    step = jax.jit(lambda c, n, w: qn.step(params, config, prefix, c, n, w))
    cached = []
    for t in range(T):
        cache, counters, logits = step(cache, counters, words_in[:, t])
        cached.append(logits)
    return jnp.stack(cached, axis=1), cache, counters


# ---------------------------------------------------------------------------
# the configuration and the tree
# ---------------------------------------------------------------------------


def test_the_program_s_tree_is_the_benchmark_s_spec():
    shapes = jax.eval_shape(lambda: qn.init_params(jax.random.PRNGKey(0), CONFIG))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    got = {"params/decoder/" + "/".join(str(k.key) for k in path): (tuple(leaf.shape), str(leaf.dtype))
           for path, leaf in flat}
    want = {name: (tuple(shape), dtype) for name, (shape, _, dtype) in params_qwen3next.decoder_spec(MODEL).items()}
    assert got == want
    lin, full = "params/decoder/lm/layers/00/", "params/decoder/lm/layers/03/"
    # per key head [q 16 | k 16 | v 3 x 8 | z 3 x 8] and [b 3 | a 3]; the conv over q, k, v; the decay in float32
    assert got[lin + "linear_attn/in_proj_qkvz"] == ((64, 2 * (16 + 16 + 24 + 24)), "bfloat16")
    assert got[lin + "linear_attn/in_proj_ba"] == ((64, 12), "bfloat16")
    assert got[lin + "linear_attn/conv1d"] == ((4, WIDTH), "bfloat16")
    assert got[lin + "linear_attn/A_log"] == got[lin + "linear_attn/dt_bias"] == ((6,), "float32")
    # a query AND a gate out of q_proj; the held share; ONE shared expert of its own width behind a gate
    assert got[full + "self_attn/q_proj"] == ((64, 4 * 2 * 16), "bfloat16")
    assert got[full + "feed_forward/w1"] == ((8, 64, 24), "bfloat16")
    assert got[full + "feed_forward/shared/w1"] == ((64, 20), "bfloat16")
    assert got[full + "feed_forward/shared/gate"] == ((64, 1), "bfloat16")
    assert got["params/decoder/lm/lm_head"] == ((64, 100), "bfloat16")
    assert not [k for k in got if k.endswith(("expert_bias", "ffn_norm", "operator_norm"))]
    # a fresh tree's (1 + w) norms are the identity's
    tree = qn.init_params(jax.random.PRNGKey(0), CONFIG)
    assert not np.asarray(tree["lm"]["norm"], np.float32).any()
    assert not np.asarray(tree["lm"]["layers"]["03"]["self_attn"]["q_norm"], np.float32).any()


LFM2 = dict(decoder="lfm2_moe", layer_types=("conv", "full_attention") * 2, num_dense_layers=1, head_dim=0,
            tie_word_embeddings=True, use_expert_bias=True, n_shared_experts=2, linear_num_key_heads=0,
            linear_num_value_heads=0, linear_key_head_dim=0, linear_value_head_dim=0, linear_conv_kernel_dim=0,
            partial_rotary_factor=1.0, shared_expert_intermediate_size=0, shared_expert_gate=False,
            scoring_func="sigmoid")


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=("latent_attention",) * 4), "layer_types"),
    (dict(layer_types=KINDS[:3]), "layer_types"),
    (dict(linear_num_value_heads=5), "linear_num_value_heads a multiple"),
    (dict(linear_conv_kernel_dim=1), "linear_conv_kernel_dim at least 2"),
    (dict(num_key_value_heads=3), "num_key_value_heads groups"),
    (dict(partial_rotary_factor=0.3), "an even number of lanes"),
    (dict(partial_rotary_factor=1.5), "within the head"),
    (dict(head_dim=0), "head_dim"),
    (dict(shared_expert_intermediate_size=0), "shared_expert_intermediate_size"),
    (dict(num_dense_layers=1), "num_dense_layers=0"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings=False"),
    (dict(use_expert_bias=True), "use_expert_bias=False"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(phase="serve"), "does not run with phase='serve'"),
    (dict(phase="bulk"), "does not run with phase='bulk'"),
    (dict(phase="route"), "does not run with phase='route'"),
    (dict(mesh_shape=(2, 1)), "one device only"),
    (dict(save_attention_maps=True), "save_attention_maps"),
    # the DeltaNet layers' widths, the partial rope, the shared expert's width, the router's score and the shared
    # gate are this stack's: no other decoder leaves them out in silence
    ({**LFM2, "linear_num_key_heads": 2}, 'only decoder="qwen3_next"'),
    ({**LFM2, "partial_rotary_factor": 0.5}, 'only decoder="qwen3_next"'),
    ({**LFM2, "shared_expert_intermediate_size": 8}, 'only decoder="qwen3_next"'),
    ({**LFM2, "scoring_func": "softmax"}, 'only decoder="qwen3_next"'),
    ({**LFM2, "shared_expert_gate": True}, 'only decoder="qwen3_next"'),
])
def test_the_configuration_refuses_what_it_cannot_run(change, match):
    with pytest.raises(ValueError, match=match):
        Config(**{**TOY, **change})
    Config(**{**TOY, **LFM2})       # the other stack's own settings stand


# ---------------------------------------------------------------------------
# the recurrence in its two forms
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("C", [2, 8, 64])
def test_the_blocked_forward_substitution_inverts_a_unit_lower_triangle(C):
    rng = np.random.default_rng(C)
    a = np.tril(rng.normal(size=(3, 2, C, C)), -1).astype(np.float32) * 0.4
    want = np.linalg.inv(np.eye(C) + a.astype(np.float64))
    _close(gdn_chunk.unit_lower_inverse(jnp.asarray(a)), want, EXACT_TOL)


def _recurrence(q, k, v, g, beta):
    """The module docstring's four lines, a position at a time, float64."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    B, S, nk, dk = q.shape
    nv, dv = v.shape[2:]
    q, k = np.repeat(q, nv // nk, axis=2), np.repeat(k, nv // nk, axis=2)
    state, out = np.zeros((B, nv, dk, dv)), np.zeros((B, S, nv, dv))
    for t in range(S):
        state = state * np.exp(g[:, t])[..., None, None]
        d = beta[:, t][..., None] * (v[:, t] - np.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., None] * d[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    return out, state


def _rule_inputs(S, nk, nv, seed=0, B=2, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.normal(size=(B, S, nk, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(B, S, nk, dk)))
    v = rng.normal(size=(B, S, nv, dv))
    g, beta = -rng.uniform(0, 1.5, size=(B, S, nv)), rng.uniform(0, 1, size=(B, S, nv))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("S,nk,nv", [(50, 2, 6), (64, 2, 6), (150, 2, 6), (150, 3, 3), (129, 1, 4)],
                         ids=["under-a-chunk", "one-chunk", "ragged-third-chunk", "nk-is-nv", "one-key-head"])
def test_the_chunked_rule_is_the_recurrence(S, nk, nv):
    """Lengths that are no multiple of 64 pad with beta = 0, g = 0, k = 0,
    which leave S as it was; a key head's products serve its nv / nk value
    heads."""
    inputs = _rule_inputs(S, nk, nv, seed=S)
    o, state = jax.jit(gdn_chunk.gdn_chunk_lax)(*inputs)
    want_o, want_state = _recurrence(*inputs)
    assert o.shape == (2, S, nv, 8) and state.shape == (2, nv, 16, 8)
    _close(o, want_o, EXACT_TOL)
    _close(state, want_state, EXACT_TOL)


def test_the_chunked_rule_goes_on_from_a_state():
    inputs = _rule_inputs(100, 2, 6, seed=3)
    rule = jax.jit(gdn_chunk.gdn_chunk_lax)
    whole_o, whole_state = rule(*inputs)
    _, first = rule(*(x[:, :37] for x in inputs))
    rest_o, rest = rule(*(x[:, 37:] for x in inputs), state=first)
    _close(rest_o, whole_o[:, 37:], EXACT_TOL)
    _close(rest, whole_state, EXACT_TOL)


@pytest.mark.parametrize("S", [2, 36, 70])
def test_a_step_through_state_and_taps_is_the_whole_sequence_s_last_position(params, S):
    """One DeltaNet layer: the chunked form over S + 1 positions against
    the step at position S from the state and the conv's taps the first S
    left (at S = 2 the taps still hold a zero of the padding)."""
    m = params["lm"]["layers"]["01"]["linear_attn"]
    u = jax.random.normal(jax.random.PRNGKey(S), (2, S + 1, 64)).astype(jnp.bfloat16)
    sequence = jax.jit(lambda m, u: qn.gdn_sequence(m, CONFIG, u))
    want, want_state, want_taps = sequence(m, u)
    _, state, taps = sequence(m, u[:, :S])
    assert state.shape == (2, NV, DK, DV) and state.dtype == jnp.float32 and taps.shape == (2, 3, WIDTH)
    got, state, taps, fused = jax.jit(
        lambda m, u, s, t: qn.gdn_step(m, CONFIG, u, s, t, jnp.arange(2), 1)
    )(m, u[:, S], state, taps)
    assert not fused            # off the TPU the ``lax`` form
    _close(got, want[:, -1], PATH_TOL)
    _close(state, want_state, 1e-4)
    assert np.array_equal(np.asarray(taps, np.float32), np.asarray(want_taps, np.float32))


def test_the_rope_turns_the_first_lanes_of_a_head_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16))
    got = qn._rope(x, jnp.arange(5), 100.0, 8)
    assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    assert np.array_equal(np.asarray(got[0]), np.asarray(x[0]))          # position 0 turns nothing
    assert float(jnp.abs(got[1:, :, :8] - x[1:, :, :8]).min()) > 0
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5)
    whole = qn._rope(x, jnp.arange(5), 100.0, 16)
    assert float(jnp.abs(whole[1:, :, 8:] - x[1:, :, 8:]).min()) > 0


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------


def test_teacher_forced_logits_against_the_plain_full_forward(params, weights):
    ctx, tokens = _inputs()
    want, routes, states = _reference(weights, ctx, tokens)
    got = jax.jit(lambda p, c, t: qn.teacher_forced(p, CONFIG, c, t))(params, ctx, tokens)
    _close(got, want, FORWARD_TOL)
    assert routes.shape == (4, 2, N + 20, 3) and states.shape == (3, 2, NV, DK, DV)


def test_prefill_then_20_cached_steps_equal_the_full_forward(params, weights):
    """Logits, not tokens: the prefix through the chunked rule once, then
    20 one-token steps through state, taps, keys and values, against the
    program's own full forward and the reference's; the final S against
    the reference's after the same words."""
    ctx, tokens = _inputs(seed=1)
    B, T = tokens.shape
    cached, cache, counters = _cached_logits(params, CONFIG, ctx, tokens)
    assert [x.shape for x in cache.state] == [(B, NV, DK, DV)] * 3
    assert [x.shape for x in cache.conv] == [(B, 3, WIDTH)] * 3
    assert [x.shape for x in cache.keys] == [(B, T, 32)] and all(x.dtype == jnp.float32 for x in cache.state)
    _close(cached, jax.jit(lambda p, c, t: qn.teacher_forced(p, CONFIG, c, t))(params, ctx, tokens), PATH_TOL)
    want, routes, states = _reference(weights, ctx, tokens)
    _close(cached, want, FORWARD_TOL)
    assert ref.state_gap(np.stack([np.asarray(s) for s in cache.state]), states) < 2e-2
    taken = np.asarray(cache.routes).reshape(B, T, 4, 3).transpose(2, 0, 1, 3)
    assert (np.sort(taken, -1) == np.sort(routes[:, :, N:], -1)).all(-1).mean() > 0.9
    pairs = np.asarray(counters.pairs)
    assert pairs[:, 1].tolist() == [4 * B * N * 3, 4 * B * T * 3] and pairs[:, 2].tolist() == [0, 0]
    assert int(counters.t) == T


# ---------------------------------------------------------------------------
# the expert layer: a softmax router, a gated shared expert, its two shares
# ---------------------------------------------------------------------------


def test_the_router_scores_by_a_softmax_over_all_experts():
    f = {"gate": (0.5 * jax.random.normal(jax.random.PRNGKey(1), (64, 16))).astype(jnp.bfloat16)}
    h = jax.random.normal(jax.random.PRNGKey(2), (10, 64)).astype(jnp.bfloat16)
    experts, weights = lm_common.route(f, CONFIG, h, 0.0)
    p = jax.nn.softmax(h.astype(jnp.float32) @ f["gate"].astype(jnp.float32), axis=-1)
    top = np.sort(np.asarray(p), axis=-1)[:, -3:]
    np.testing.assert_allclose(np.sort(np.asarray(weights), -1), top / top.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    assert np.array_equal(np.sort(experts, -1), np.sort(np.argsort(np.asarray(p), axis=-1)[:, -3:], -1))
    sigmoid = Config(**{**TOY, **LFM2})
    _, other = lm_common.route({**f, "expert_bias": jnp.zeros((16,))}, sigmoid, h, 0.0)
    assert float(jnp.abs(jnp.sort(other, -1) - jnp.sort(weights, -1)).max()) > 1e-3


def test_the_two_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer():
    """experts_held 8 of 16 = one of TWO chips: the routed parts of the two
    shares and the shared expert times its gate, counted ONCE, are the
    uncut reference's expert branch."""
    toy = {**TOY, "experts_held": 0, "first_expert": 0}
    weights = _weights(_model(toy))
    params = jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))
    f = params["lm"]["layers"]["02"]["feed_forward"]
    T = 48
    u = (0.5 * jax.random.normal(jax.random.PRNGKey(11), (T, 64))).astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.expert_ffn(ref._f32(_subtree(weights, "lm/layers/02/feed_forward")),
                                      u.astype(jnp.float32), ref._Static(_model(toy)), "f32")
    routed, shared_part, seen = jnp.zeros((T, 64), jnp.float32), None, 0
    for first in (0, 8):
        held = {**f, **{w: f[w][first:first + 8] for w in ("w1", "w3", "w2")}}
        config = Config(**{**toy, "experts_held": 8, "first_expert": first})
        share = jax.jit(lambda f, u, config=config: lm_common.moe_experts(f, config, u, 0.0))
        y, _, experts, pairs = share(held, u)
        y_routed = share({k: v for k, v in held.items() if k != "shared"}, u)[0]
        routed, shared_part = routed + y_routed, y - y_routed
        assert int(pairs.over) == 0
        seen += int(pairs.held)
    assert seen == T * 3 and (np.sort(experts, -1) == np.sort(chosen, -1)).all(-1).mean() > 0.9
    _close(routed + shared_part, want, LAYER_TOL)
    # the gate is there: ungated, the shared part is another
    plain = lm_common.shared_experts(f, u)
    gate = jax.nn.sigmoid(u.astype(jnp.float32) @ f["shared"]["gate"].astype(jnp.float32))
    _close(lm_common.shared_gate(f, u), gate, 1e-5)
    _close(shared_part, plain * gate, 1e-2)
    assert float(jnp.abs(plain - shared_part).max()) > 0.2 * float(jnp.abs(plain).max())


# ---------------------------------------------------------------------------
# through the search
# ---------------------------------------------------------------------------


def test_the_reorder_swaps_a_beam_s_taps_keys_and_values_and_names_its_state_s_source():
    """What follows its beam is gathered by parent; the leaves a decoder
    keeps ``at_source`` are not touched, and ``source`` says which row of
    them each slot descends from."""
    B, K = 2, 3
    rows = jnp.arange(B * K, dtype=jnp.float32)
    leaf = lambda *shape: rows.reshape((B * K,) + (1,) * len(shape)) + jnp.zeros((B * K,) + shape)  # noqa: E731
    cache = qn.HybridCache(state=None, conv=(leaf(3, WIDTH),) * 3, keys=(leaf(5, 32),),
                           values=(leaf(5, 32),), routes=leaf(30))
    shared = qn.Counters(t=jnp.int32(7), moe_counts=jnp.arange(8).reshape(2, 4),
                         step_visits=jnp.arange(10).reshape(2, 5), pairs=jnp.arange(12).reshape(2, 6),
                         fold=jnp.zeros((3,)), chunk=jnp.zeros((2,)))
    held = {"state": (leaf(NV, DK, DV),) * 3}
    parent = jnp.array([[2, 0, 1], [1, 1, 0]])
    moved = bs._reorder_beams(
        bs.StepState(cache, shared, held, jnp.arange(B * K, dtype=jnp.int32)), B, K, jnp.arange(B)[:, None], parent
    )
    want = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
    for x in jax.tree_util.tree_leaves(moved.beam):
        assert np.array_equal(np.asarray(x).reshape(B * K, -1)[:, 0], np.asarray(want, np.float32))
    assert np.array_equal(moved.shared.pairs, shared.pairs) and int(moved.shared.t) == 7
    assert moved.source.dtype == jnp.int32 and np.array_equal(moved.source, want)
    for x in moved.at_source["state"]:
        assert np.array_equal(np.asarray(x), np.asarray(held["state"][0]))


def test_the_search_serves_what_the_reference_scores_and_hands_back_its_state(params, weights):
    """Through the cache AND the reorder: each served caption's score is
    the sum of the reference's log-probabilities of its tokens
    (teacher-forced on them, no cache); the final S of live beam 0, which
    followed that beam through every swap, is the reference's after the
    same words; ``decoder_stats`` holds the held share's pairs and the
    state's bytes by kind of leaf."""
    ctx, _ = _inputs(seed=2, B=4)
    T, K = 8, 3
    out, state = _searched(params, ctx, K, T, early_exit=False)
    stats = out.decoder_stats
    assert stats["step_routes"].shape == (4, K, T, 12) and stats["prefix_routes"].shape == (4, N, 12)
    pairs = np.asarray(stats["moe_pairs"])
    assert pairs[:, 1].tolist() == [4 * 4 * N * 3, 4 * 4 * K * T * 3] and pairs[:, 2].tolist() == [0, 0]
    recurrent = 3 * 4 * K * (NV * DK * DV * 4 + 3 * WIDTH * 2)
    full = 2 * 32 * 2 * (4 * N + 4 * K * T)
    assert int(stats["state_bytes_recurrent"]) == recurrent
    assert int(stats["state_bytes"]) == recurrent + full + 4 * K * T * 12 * 4
    assert stats["final_state"].shape == (4, 3, NV, DK, DV)
    # the beams did swap: some step's parents are no identity
    live = np.asarray(state.at_source["state"][0]).reshape(4, K, -1)
    assert not np.allclose(live[:, 0], live[:, 1])
    assert state.beam.state is None and np.asarray(stats["gdn_fold"]).tolist()[:2] == [0.0, 3.0 * T]
    assert np.asarray(stats["gdn_chunk"]).tolist() == [0.0, 3.0]       # off the TPU the prefill's rule is ``lax``
    words, lengths = np.asarray(out.words[:, 0]), np.asarray(out.lengths[:, 0])
    logits, _, states = _reference(weights, ctx, words)
    logp = jax.nn.log_softmax(logits, axis=-1)
    for b in range(4):
        n = int(lengths[b])
        want = float(np.take_along_axis(np.asarray(logp[b, :n]), words[b, :n, None], axis=-1).sum())
        assert abs(float(out.log_scores[b, 0]) - want) < 0.25, (b, float(out.log_scores[b, 0]), want)
    # live beam 0 is the served caption where none ended (the terminator is one word in 100)
    never_ended = [b for b in range(4) if lengths[b] == T and 1 not in words[b]]
    assert never_ended
    got = np.moveaxis(np.asarray(stats["final_state"]), 1, 0)[:, never_ended]
    assert ref.state_gap(got, states[:, never_ended]) < 2e-2
    # another beam's state is another: the gap is the state's own size
    second = np.asarray(state.source).reshape(4, K)[never_ended, 1]
    other = np.stack([np.asarray(s)[second] for s in state.at_source["state"]])
    assert ref.state_gap(other, states[:, never_ended]) > 0.1


def _searched(params, ctx, K, T, early_exit):
    def run(params, ctx):
        search = decoders.search(params, CONFIG, ctx, K, T)
        result, state = bs.run_search(CONFIG, search.step_fn, search.state0, ctx.shape[0], 1, beam_size=K, max_len=T,
                                      valid_size=100, early_exit=early_exit, return_steps=True, return_state=True)
        return search.finish(result, state), state

    return jax.jit(run)(params, ctx)


@pytest.mark.parametrize("early_exit", [False, True], ids=["all_steps", "early_exit"])
def test_the_search_through_the_kernel_is_the_search_through_the_lax_form(params, monkeypatch, early_exit):
    """The whole search with the state read at its source row by
    ``ops/gdn_step.py``'s kernel (interpreted) against the same search in
    ``lax`` (the state gathered by source, then the recurrence): the same
    words, lengths and routes, scores and final state to rounding; the
    counter says which form made the updates and how many rows came from
    another slot."""
    from sat_tpu.ops import gdn_step as gs

    ctx, _ = _inputs(seed=2, B=4)
    T, K = 8, 3
    want, want_state = _searched(params, ctx, K, T, early_exit)
    monkeypatch.setattr(gs, "FORCE_INTERPRET", True)
    got, got_state = _searched(params, ctx, K, T, early_exit)
    steps = int(got.steps_run)
    assert steps == int(want.steps_run) and (early_exit or steps == T)
    assert np.array_equal(got.words, want.words) and np.array_equal(got.lengths, want.lengths)
    assert np.array_equal(got.decoder_stats["step_routes"], want.decoder_stats["step_routes"])
    assert np.array_equal(got_state.source, want_state.source)
    np.testing.assert_allclose(got.log_scores, want.log_scores, atol=1e-4)
    _close(got.decoder_stats["final_state"], want.decoder_stats["final_state"], EXACT_TOL)
    fold, fold_lax = np.asarray(got.decoder_stats["gdn_fold"]), np.asarray(want.decoder_stats["gdn_fold"])
    assert fold[:2].tolist() == [3.0 * steps, 3.0 * steps] and fold_lax[:2].tolist() == [0.0, 3.0 * steps]
    # step 0 names every row its own; from step 1 on every image's beams descend from beam 0 at least
    assert fold[2] == fold_lax[2] and 4 * (K - 1) <= fold[2] <= 4 * K * (steps - 1)
    import inspect

    from sat_tpu import runtime

    assert '"decode/lm_gdn_fold_share"' in inspect.getsource(runtime)      # the drain's gauge of the first two


def test_the_prefill_takes_the_chunk_s_kernel_and_teacher_forcing_keeps_the_lax_form(params, monkeypatch):
    """Under the tests' hook ``prefill`` runs every DeltaNet layer's chunked
    rule in ``ops/gdn_chunk.py``'s kernel (interpreted) and says so; its
    cache is the ``lax`` form's: the first layer's state to float32
    rounding (its inputs are the same numbers), the layers above it to two
    paths of the program (a bfloat16 stream carries the rounding on).
    ``teacher_forced`` is differentiated and keeps ``lax`` whatever the
    hook says; without the hook the counter reads 0."""
    ctx, tokens = _inputs(seed=4, B=2, T=5)
    prefill = lambda: jax.jit(lambda p, c: qn.prefill(p, CONFIG, c))(params, ctx)  # noqa: E731
    want, (_, _, chunk), _ = prefill()
    assert np.asarray(chunk).tolist() == [0.0, 3.0]
    forced = lambda p: qn.teacher_forced(p, CONFIG, ctx, tokens)  # noqa: E731
    monkeypatch.setattr(gdn_chunk, "FORCE_INTERPRET", True)
    got, (_, _, chunk), _ = prefill()
    assert np.asarray(chunk).tolist() == [3.0, 3.0]
    assert got.state[0].dtype == qn.STATE_DTYPE and got.state[0].shape == (2, NV, DK, DV)
    _close(got.state[0], want.state[0], EXACT_TOL)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        _close(a, b, PATH_TOL)
    assert "pallas_call" in str(jax.make_jaxpr(lambda p: qn.prefill(p, CONFIG, ctx))(params))
    assert "pallas_call" not in str(jax.make_jaxpr(forced)(params))
    grads = jax.jit(jax.grad(lambda p: jnp.sum(forced(p) ** 2)))(params)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in jax.tree_util.tree_leaves(grads))
    assert float(jnp.abs(grads["lm"]["layers"]["00"]["linear_attn"]["in_proj_qkvz"].astype(jnp.float32)).max()) > 0
    # the search hands the counter on, and the drain turns it into a gauge
    out, _ = _searched(params, ctx, 3, 2, early_exit=False)
    assert np.asarray(out.decoder_stats["gdn_chunk"]).tolist() == [3.0, 3.0]
    import inspect

    from sat_tpu import runtime

    assert '"decode/lm_gdn_chunk_share"' in inspect.getsource(runtime)


def test_the_final_state_is_read_at_the_last_step_s_sources(params):
    """``final_state`` is live beam 0's AFTER the search's last choice: the
    state lies where the last step wrote it, and beam 0 of an image
    descends from the row ``source`` names, which need not be row 0 of the
    image (read unresolved, another beam's state would come back)."""
    ctx, _ = _inputs(seed=2, B=4)
    T, K = 8, 3
    out, state = _searched(params, ctx, K, T, False)
    first = np.asarray(state.source).reshape(4, K)[:, 0]
    assert (first != np.arange(4) * K).any() and (first // K == np.arange(4)).all()
    final = np.asarray(out.decoder_stats["final_state"])
    for layer, s in enumerate(state.at_source["state"]):
        assert np.array_equal(final[:, layer], np.asarray(s)[first])
        swapped = first != np.arange(4) * K
        assert not np.allclose(final[swapped, layer], np.asarray(s)[np.arange(4) * K][swapped])


def test_the_prefix_s_keys_stay_per_image_and_the_state_is_per_beam(params):
    ctx, _ = _inputs(B=2)

    def first_logits(params, ctx):
        search = decoders.search(params, CONFIG, ctx, 3, 20)
        state0 = search.state0
        return search.step_fn(state0, jnp.zeros((6,), jnp.int32))[1], state0.beam._replace(**state0.at_source)

    _, beam = jax.jit(first_logits)(params, ctx)
    assert [x.shape for x in beam.state] == [(6, NV, DK, DV)] * 3 and [x.shape for x in beam.keys] == [(6, 20, 32)]
    # an image's beams start from ITS prefix's state
    assert np.array_equal(np.asarray(beam.state[1][0]), np.asarray(beam.state[1][2]))
    assert not np.array_equal(np.asarray(beam.state[1][0]), np.asarray(beam.state[1][3]))
    text = jax.jit(first_logits).lower(params, ctx).as_text()
    assert f"tensor<2x{N}x32xbf16>" in text and f"tensor<6x{N}x32x" not in text


def test_the_tile_sweep_rehearses_this_stack_s_consumers(tmp_path):
    """``scripts/gmm_tile_sweep.py --stack qwen3_next`` (the tool that filled
    the 512-wide rows of ``_GMM_TILES``) at toy widths: the layers' expert
    halves over a step's rows and over one prefill pass compile and run
    under two pairs of tiles each; off the chip a run has NO ms a call."""
    import json
    import subprocess

    from test_lfm2 import _SWEEP

    out = tmp_path / "sweep.json"
    proc = subprocess.run([sys.executable, _SWEEP, "--stack", "qwen3_next", "--rehearse", "--out", str(out)],
                          capture_output=True, text=True, timeout=420, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(out.read_text())
    assert [r["regime"] for r in got["results"]] == ["prefill", "prefill", "step", "step"]
    assert np.asarray(got["step_sizes"]).shape == (4, 16) and np.asarray(got["step_sizes"]).sum() == 4 * 12 * 3
    assert all(r["wall_ms"] > 0 and "ms_a_call" not in r for r in got["results"])
