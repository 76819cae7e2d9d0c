"""The dots3-note-prev caption decoder (models/dots3_note.py: full layers
under an indexer beside sliding-window layers with latent attention at
widths of their own, a headwise output gate in both, the latents rescaled,
a cache that keeps a window) at toy widths on the CPU, held against the
plain float32 reference under benchmark/reference (which imports nothing
of the program, has the expanded form only, selects by ``lax.top_k`` and
bounds the window by a comparison of positions), on seeded weights whose
values are bfloat16-representable.  ``sliding_window_size`` is 9 against
36 + 20 positions, so the band is active in the prefill, the kept tail is 8
of 36 latents, and the steps slide past tail and suffix both;
``index_topk`` is 16, as in tests/test_glm_moe_dsa.py, whose tolerances
and whose reasons for them these are (``_close_but_for_flips``: the
program's indexer runs in bfloat16, so a position at the threshold may be
chosen on one side and not on the other).
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from reference import dots3_captioner as ref  # noqa: E402
from reference import params_dots3  # noqa: E402
from reference.params import nest  # noqa: E402

from sat_tpu.config import Config  # noqa: E402
from sat_tpu.models import decoders, lm_common  # noqa: E402
from sat_tpu.models import dots3_note as d3  # noqa: E402
from sat_tpu.models import glm_moe_dsa as dsa  # noqa: E402
from sat_tpu.ops import flash_prefill  # noqa: E402

from test_glm_moe_dsa import FORWARD_TOL, LAYER_TOL, PATH_TOL, _close, _close_but_for_flips  # noqa: E402

bs = importlib.import_module("sat_tpu.ops.beam_search")

KINDS = ("full_attention", "full_attention", "sliding_attention", "sliding_attention", "sliding_attention")
TOY = dict(
    decoder="dots3_note", cnn="vgg16", image_size=96, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=24, num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4,
    num_experts=16, num_experts_per_tok=3, experts_held=2, first_expert=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24, n_shared_experts=1,
    q_lora_rank=48, index_n_heads=8, index_head_dim=16, index_topk=16,
    swa_num_attention_heads=2, swa_q_lora_rank=40, swa_kv_lora_rank=28, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=16, swa_v_head_dim=16, swa_rope_theta=100.0, sliding_window_size=9,
    attention_gate="headwise", mla_lora_rescale=True, layer_types=KINDS,
    tie_word_embeddings=False, vocabulary_size=100, max_caption_length=20, beam_size=3,
    norm_eps=1e-5, rope_theta=8e7, norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1.0,
)
CONFIG = Config(**TOY)


def _model(toy):
    return {**toy, "layer_types": list(toy["layer_types"])}


MODEL = _model(TOY)
N = CONFIG.num_ctx                  # 36: a 96-px image's 6 x 6 grid
FULL_W, SWA_W = 32 + 8, 28 + 16     # what a token leaves in a full layer's cache, in a sliding layer's
KEPT = 8                            # window - 1 of the prefix's 36 latents


def _weights(model, seed=7):
    return params_dots3.make_weights(model, seed, only=lambda n: n.startswith("params/decoder/"))


@pytest.fixture(scope="module")
def weights():
    return _weights(MODEL)


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))


@pytest.fixture
def small_blocks(monkeypatch):
    """Whole sequences in blocks of 8 queries: seven blocks over 56, a
    sliding layer's band two blocks wide."""
    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 8)


def _inputs(seed=0, B=2, T=20):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    ctx = jax.random.normal(k1, (B, N, CONFIG.dim_ctx)).astype(jnp.bfloat16).astype(jnp.float32)
    tokens = jax.random.randint(k2, (B, T), 2, CONFIG.vocabulary_size)
    return ctx, tokens


def _subtree(weights, prefix):
    path = "params/decoder/" + prefix
    return weights[path] if path in weights else nest(weights, path)


def _reference(weights, ctx, tokens, model=MODEL):
    return ref.forward(lambda pre: _subtree(weights, pre), model, np.asarray(ctx), np.asarray(tokens))


def _cached_logits(params, config, ctx, tokens, prefix=None):
    """Prefill, then one step a token through the caches: (logits
    [B, T, V], the prefix, the final cache, the final counters)."""
    B, T = tokens.shape
    made, counts, _ = d3.prefill(params, config, ctx)
    prefix = prefix or made
    cache = d3.start_beams(config, prefix, 1, T, decoders.tile_beams)
    counters = d3.init_counters(counts, T)
    words_in = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), tokens[:, :-1]], axis=1)
    step = jax.jit(lambda c, n, w: d3.step(params, config, prefix, c, n, w))
    cached = []
    for t in range(T):
        cache, counters, logits = step(cache, counters, words_in[:, t])
        cached.append(logits)
    return jnp.stack(cached, axis=1), prefix, cache, counters


# ---------------------------------------------------------------------------
# the tree, the configuration's refusals, the two records of widths
# ---------------------------------------------------------------------------


def test_the_program_s_tree_is_the_benchmark_s_spec():
    shapes = jax.eval_shape(lambda: d3.init_params(jax.random.PRNGKey(0), CONFIG))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    got = {"params/decoder/" + "/".join(str(k.key) for k in path): (tuple(leaf.shape), str(leaf.dtype))
           for path, leaf in flat}
    want = {name: (tuple(shape), dtype) for name, (shape, _, dtype) in params_dots3.decoder_spec(MODEL).items()}
    assert got == want
    # a gate and two widths of latent side by side; an indexer in the full layers alone
    assert got["params/decoder/lm/layers/01/self_attn/gate_proj"] == ((64, 4), "bfloat16")
    assert got["params/decoder/lm/layers/02/self_attn/gate_proj"] == ((64, 2), "bfloat16")
    assert got["params/decoder/lm/layers/02/self_attn/kv_a_proj"] == ((64, SWA_W), "bfloat16")
    assert "params/decoder/lm/layers/01/self_attn/indexer/wk" in got
    assert "params/decoder/lm/layers/02/self_attn/indexer/wk" not in got


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=("latent_attention",) * 5), "layer_types"),
    (dict(layer_types=KINDS[:4]), "layer_types"),
    (dict(indexer_types=("full",) * 5), "indexer_types"),
    (dict(swa_qk_rope_head_dim=7), "swa_qk_rope_head_dim"),
    (dict(sliding_window_size=0), "sliding_window_size"),
    (dict(attention_gate="elementwise"), "attention_gate"),
    (dict(index_topk=0), "index_topk"),
    (dict(phase="serve"), "does not run with phase='serve'"),
    (dict(phase="bulk"), "does not run with phase='bulk'"),
    (dict(phase="route"), "does not run with phase='route'"),
    (dict(mesh_shape=(2, 1)), "one device only"),
    (dict(save_attention_maps=True), "save_attention_maps"),
    # the gate and the rescale are this stack's: no other decoder leaves them out in silence
    (dict(decoder="glm_moe_dsa", layer_types=("latent_attention",) * 5, indexer_types=("full",) * 5,
          mla_lora_rescale=False), 'only decoder="dots3_note"'),
    (dict(decoder="deepseek_v3", layer_types=("latent_attention",) * 5, attention_gate="none"),
     'only decoder="dots3_note"'),
])
def test_the_configuration_refuses_what_it_cannot_run(change, match):
    with pytest.raises(ValueError, match=match):
        Config(**{**TOY, **change})


def test_a_kind_of_layer_has_widths_of_its_own():
    full, sliding = d3.widths(CONFIG)
    assert (full.heads, full.q_rank, full.kv_rank, full.nope, full.rope, full.v, full.theta) == \
        (4, 48, 32, 16, 8, 24, 8e7)
    assert (sliding.heads, sliding.q_rank, sliding.kv_rank, sliding.nope, sliding.rope, sliding.v,
            sliding.theta) == (2, 40, 28, 24, 16, 16, 100.0)
    assert full.index_topk == 16 and sliding.index_topk == 0
    assert full.q_scale == pytest.approx((64 / 48) ** 0.5) and sliding.kv_scale == pytest.approx((64 / 28) ** 0.5)
    assert full.segment == "" and sliding.segment == "window"
    plain = d3.widths(CONFIG.replace(mla_lora_rescale=False))
    assert plain[0] == dsa.widths(CONFIG) and plain[1].q_scale == plain[1].kv_scale == 1.0
    published, window = d3.widths(Config(decoder="dots3_note", hidden_size=5120, num_attention_heads=128,
                                         q_lora_rank=1024, index_n_heads=64, rope_theta=8e7, mla_lora_rescale=True,
                                         num_hidden_layers=1, num_dense_layers=1, layer_types=("full_attention",)))
    assert (published.heads, published.qk, published.kv_rank + published.rope) == (128, 192, 576)
    assert (window.heads, window.qk, window.kv_rank + window.rope, window.theta) == (64, 256, 1088, 5e4)
    assert published.kv_scale == pytest.approx(10 ** 0.5) and window.kv_scale == pytest.approx(5 ** 0.5)


# ---------------------------------------------------------------------------
# the window: the kernel against the lax band, a step at the prefix's boundary
# ---------------------------------------------------------------------------


def _plain_band(q, k, v, window, scale):
    """q, k [nh, S, d], v [nh, S, dv] float32 -> [S, nh * dv]: every score,
    the band by a comparison of positions."""
    S = q.shape[1]
    ahead = np.arange(S)[:, None] - np.arange(S)[None, :]
    scores = np.where((ahead >= 0) & (ahead < window), np.einsum("hsd,htd->hst", q, k) * scale, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("hst,htd->shd", probs, v).reshape(S, -1)


@pytest.mark.parametrize("S,window,tiles", [
    (64, 17, (16, 8, 2)),       # window - 1 = a query tile: the published shape in small
    (64, 9, (16, 8, 2)),        # a band narrower than a query tile
    (64, 40, (16, 16, 4)),      # a band of several query tiles
    (48, 1, (8, 8, 2)),         # a query sees itself alone
    (32, 100, (8, 16, 1)),      # a window wider than the sequence: plain causal
], ids=["tile", "narrow", "wide", "self", "causal"])
def test_the_windowed_kernel_against_the_lax_band_and_every_score(S, window, tiles):
    keys = jax.random.split(jax.random.PRNGKey(S + window), 3)
    q, k, v = (jax.random.normal(key, (4, S, d)).astype(jnp.bfloat16) for key, d in zip(keys, (24, 24, 16)))
    scale = 24 ** -0.5
    got = flash_prefill.flash_prefill(q, k, v, None, scale=scale, tiles=tiles, interpret=True, window=window)
    want = _plain_band(*(np.asarray(x, np.float32) for x in (q, k, v)), window, scale)
    _close(got, want, 2e-2)     # bfloat16 weights in the second product
    lows, masks = lm_common.causal_blocks(S, window)
    lax_form = lm_common.attend_blocks(q, k, v, masks, scale, lows)
    _close(got, lax_form, 1e-2)


def test_the_windowed_kernel_visits_the_band_s_key_tiles_alone():
    """At the published shape (4,096 positions, a window of 513, tiles of
    512 x 256) the grid's key axis is 4 long, not 16; with no window it is
    what it was."""
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    args = (sd(64, 4096, 256), sd(64, 4096, 256), sd(64, 4096, 128), None)
    grids = {}
    for window in (513, None):
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, m, window=window: flash_prefill.flash_prefill(
                q, k, v, m, scale=1.0, interpret=True, window=window)
        )(*args)
        call = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns if e.primitive.name == "pallas_call"][0]
        grids[window] = tuple(call.params["grid_mapping"].grid)
    assert grids == {513: (4, 8, 4), None: (4, 8, 16)}


def test_a_band_of_the_lax_form_is_two_blocks_wide(small_blocks):
    lows, masks = lm_common.causal_blocks(56, 9)
    assert lows == [0, 0, 8, 16, 24, 32, 40] and [m.shape for m in masks] == [(8, 8)] + [(8, 16)] * 6
    assert all(int(m.sum(-1).max()) == 9 for m in masks[1:]) and int(masks[0].sum()) == 36


@pytest.mark.parametrize("t", [0, 3, 7, 8, 15])
def test_a_step_s_window_at_the_prefix_s_boundary(params, t):
    """One sliding layer: the whole-sequence form over N + t + 1 positions
    against the step at position N + t over the prefix's kept tail (8
    latents) and a suffix of t + 1: at t = 0 the band holds the whole tail
    and the token itself, at t = 8 the last of the tail has slid out."""
    m = params["lm"]["layers"]["03"]["self_attn"]
    _, w = d3.widths(CONFIG)
    h = jax.random.normal(jax.random.PRNGKey(5), (N + t + 1, 64))
    want, latents = d3.attend_window(m, w, 9, h)
    suffix = jnp.zeros((1, 20, SWA_W), jnp.bfloat16).at[0, :t].set(latents[N:N + t])
    got, suffix, seen = d3.attend_window_step(m, w, 9, h[-1:], latents[None, N - KEPT:N], suffix, N, jnp.int32(t))
    assert int(seen) == 9
    assert np.array_equal(np.asarray(suffix[0, t], np.float32), np.asarray(latents[N + t], np.float32))
    _close(got[0], want[-1], PATH_TOL)
    # the same over the prefix kept whole: what the tail leaves out is never seen
    whole, _, seen = d3.attend_window_step(m, w, 9, h[-1:], latents[None, :N], suffix, N, jnp.int32(t))
    assert int(seen) == 9 and np.array_equal(np.asarray(whole, np.float32), np.asarray(got, np.float32))


# ---------------------------------------------------------------------------
# against the reference: whole sequences, the cache, the fused prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("blocks", ["one_block", "blocks_of_8"])
def test_teacher_forced_logits_against_the_plain_full_forward(params, weights, blocks, monkeypatch):
    if blocks == "blocks_of_8":
        monkeypatch.setattr(lm_common, "QUERY_BLOCK", 8)
    ctx, tokens = _inputs()
    got = d3.teacher_forced(params, CONFIG, ctx, tokens)
    want, routes, selections = _reference(weights, ctx, tokens)
    assert got.shape == (2, 20, 100) and routes.shape == (4, 2, N + 20, 3)
    _close_but_for_flips(got, want, FORWARD_TOL)
    assert selections.shape == (2, 2, 20, N + 20) and selections.sum(-1).min() == 16


def test_prefill_then_20_cached_steps_equal_the_full_forward(params, weights, small_blocks):
    """Logits, not tokens: the N prefix positions once (expanded, in
    blocks, the sliding layers over their band), then 20 one-token steps
    (absorbed, the sliding layers over the kept tail and their suffix)
    through the caches, against the program's own full forward and the
    reference's with no cache."""
    ctx, tokens = _inputs()
    B, T = tokens.shape
    full = d3.teacher_forced(params, CONFIG, ctx, tokens)
    cached, prefix, cache, counters = _cached_logits(params, CONFIG, ctx, tokens)
    assert [x.shape for x in prefix.latents] == [(B, N, FULL_W)] * 2 + [(B, KEPT, SWA_W)] * 3
    assert [x.shape for x in cache.latents] == [(B, T, FULL_W)] * 2 + [(B, T, SWA_W)] * 3
    assert [x.shape for x in prefix.index_keys] == [(B, N, 16)] * 2 == [x.shape[:1] + (N, 16) for x in cache.index_keys]
    _close_but_for_flips(cached, full, PATH_TOL, share=0.8)
    assert int(counters.t) == T
    # 3 sliding layers x 20 steps x 2 rows attend 9 of 37..56 each
    assert np.asarray(counters.window).tolist() == [3 * T * B * 9, 3 * B * sum(N + t + 1 for t in range(T))]
    assert np.asarray(counters.attended).tolist() == [2 * T * B * 16, 2 * B * sum(N + t + 1 for t in range(T))]
    want, _, _ = _reference(weights, ctx, tokens)
    _close_but_for_flips(cached, want, FORWARD_TOL)


def test_the_tail_alone_gives_the_logits_of_a_cache_kept_whole(params, monkeypatch):
    """The sliding layers' prefix kept WHOLE ([B, 36, 44]) against its
    last 8 latents: the same logits to the bit, a fifth of the bytes."""
    ctx, tokens = _inputs(seed=3)
    cached, prefix, _, _ = _cached_logits(params, CONFIG, ctx, tokens)
    monkeypatch.setattr(d3, "_kept", lambda config, positions: positions)
    whole, _, _ = d3.prefill(params, CONFIG, ctx)
    assert [x.shape[1] for x in whole.latents] == [N] * 5
    for kept, tail in zip(whole.latents[2:], prefix.latents[2:]):
        assert np.array_equal(np.asarray(kept[:, N - KEPT:], np.float32), np.asarray(tail, np.float32))
    again, _, _, _ = _cached_logits(params, CONFIG, ctx, tokens, prefix=whole)
    assert np.array_equal(np.asarray(again), np.asarray(cached))


@pytest.mark.parametrize("hook,blocks", [(True, [[6, 6], [9, 9]]), (False, [[0, 6], [0, 9]])], ids=["fused", "lax"])
def test_prefill_through_the_fused_kernel_then_20_cached_steps_equal_the_full_forward(
        params, weights, monkeypatch, hook, blocks):
    """The prefill's attention through ops/flash_prefill.py (interpret
    mode, under its test hook; 36 positions = 3 query blocks of 12): the
    full layers under the selection's mask, the sliding layers under the
    window bound; then 20 cached steps, against the reference's full
    forward.  The counter says which form ran, by kind."""
    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 12)
    monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", hook)
    ctx, tokens = _inputs()
    cached, prefix, _, counters = _cached_logits(params, CONFIG, ctx, tokens)
    assert np.asarray(counters.fused).tolist() == blocks
    want, _, _ = _reference(weights, ctx, tokens)
    _close_but_for_flips(cached, want, FORWARD_TOL)
    if hook:
        monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", False)
        plain, _, _ = d3.prefill(params, CONFIG, ctx)
        for got, lax_form in zip(prefix.latents[1:], plain.latents[1:]):
            _close(got, lax_form, PATH_TOL)


def _without_gates(params):
    layers = {name: {**p, "self_attn": {k: v for k, v in p["self_attn"].items() if k != "gate_proj"}}
              for name, p in params["lm"]["layers"].items()}
    return {**params, "lm": {**params["lm"], "layers": layers}}


# at 64 wide the scores of seeded weights are near zero and every softmax
# near uniform: a rope base or a scale of the query barely shows.  The
# stream 1,024 wide (a score's spread grows as the hidden size), a sliding
# head mostly rotary and its base far from the full layers': it shows
SHARP = {**TOY, "hidden_size": 1024, "swa_qk_nope_head_dim": 8, "swa_qk_rope_head_dim": 32,
         "swa_rope_theta": 4.0}


@pytest.fixture(scope="module")
def sharp():
    weights = _weights(_model(SHARP))
    ctx, tokens = _inputs()
    want, _, _ = _reference(weights, ctx, tokens, _model(SHARP))
    return jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder")), ctx, tokens, want


@pytest.mark.parametrize("dropped", ["nothing", "no_gate", "no_rescale", "swa_theta", "no_window"])
def test_each_part_dropped_from_the_program_changes_the_logits(sharp, dropped):
    """What the benchmark's sabotaged programs take away is there: the
    gate, the rescale, a sliding layer's own rope base and the window each
    move the logits.  The measure is the MEAN gap to the reference over
    the logits, in units of their scale: the sound program reads 0.0056
    (its largest gap is a selection flipped at the threshold, which a mean
    hardly sees), a sliding layer under the full layers' rope base 0.030,
    no window 0.058, no rescale 0.10, no gate 0.12."""
    params, ctx, tokens, want = sharp
    sound = Config(**SHARP)
    config = {
        "no_rescale": sound.replace(mla_lora_rescale=False),
        "swa_theta": sound.replace(swa_rope_theta=sound.rope_theta),
        "no_window": sound.replace(sliding_window_size=N + 20),
    }.get(dropped, sound)
    got = d3.teacher_forced(_without_gates(params) if dropped == "no_gate" else params, config, ctx, tokens)
    gap = float(np.abs(np.asarray(got) - want).mean() / np.abs(want).max())
    assert gap < 0.008 if dropped == "nothing" else gap > 0.02, gap


# ---------------------------------------------------------------------------
# the expert layer's eight shares
# ---------------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_reference_s_uncut_layer():
    """experts_held 2 of 16 = one of EIGHT chips: the routed parts of the
    eight shares and the shared expert counted ONCE are the uncut
    reference's layer."""
    toy = {**TOY, "experts_held": 0, "first_expert": 0}
    weights = _weights(_model(toy))
    params = jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))
    p = params["lm"]["layers"]["02"]
    T = 48
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(11), (T, 64))).astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.ffn(ref._f32(_subtree(weights, "lm/layers/02")), x.astype(jnp.float32), True,
                               ref._Static(_model(toy)))
    routed, shared_part, seen = jnp.zeros((T, 64), jnp.float32), None, 0
    f = p["feed_forward"]
    for first in range(0, 16, 2):
        held = {**p, "feed_forward": {**f, **{w: f[w][first:first + 2] for w in ("w1", "w3", "w2")}}}
        config = Config(**{**toy, "experts_held": 2, "first_expert": first})
        share = jax.jit(lambda p, x, config=config: lm_common.moe_ffn(p, config, x, 1e-20))
        y, _, experts, pairs = share(held, x)
        alone = {**held, "feed_forward": {k: v for k, v in held["feed_forward"].items() if k != "shared"}}
        y_routed = share(alone, x)[0]
        routed = routed + (y_routed.astype(jnp.float32) - x.astype(jnp.float32))
        shared_part = y.astype(jnp.float32) - y_routed.astype(jnp.float32)
        assert int(pairs.over) == 0
        seen += int(pairs.held)
    assert seen == T * 3 and (np.sort(experts, -1) == np.sort(chosen, -1)).all(-1).mean() > 0.9
    _close(x.astype(jnp.float32) + routed + shared_part, want, LAYER_TOL)


# ---------------------------------------------------------------------------
# through the search
# ---------------------------------------------------------------------------


def test_the_reorder_moves_both_kinds_of_latent_leaf():
    B, K = 2, 3
    rows = jnp.arange(B * K, dtype=jnp.float32)
    leaf = lambda *shape: rows.reshape((B * K,) + (1,) * len(shape)) + jnp.zeros((B * K,) + shape)  # noqa: E731
    cache = dsa.DsaCache(latents=(leaf(5, FULL_W), leaf(5, SWA_W)), index_keys=(leaf(5, 16),),
                         routes=leaf(30), selected=leaf(80))
    shared = d3.Counters(t=jnp.int32(7), moe_counts=jnp.arange(8).reshape(2, 4),
                         step_visits=jnp.arange(10).reshape(2, 5), pairs=jnp.arange(6).reshape(2, 3),
                         attended=jnp.arange(2), window=jnp.arange(2), fused=jnp.arange(4).reshape(2, 2))
    parent = jnp.array([[2, 0, 1], [1, 1, 0]])
    moved = bs._reorder_beams(bs.StepState(cache, shared), B, K, jnp.arange(B)[:, None], parent)
    want = (jnp.arange(B)[:, None] * K + parent).reshape(-1).astype(jnp.float32)
    leaves = jax.tree_util.tree_leaves(moved.beam)
    assert [x.shape[-1] for x in leaves[:2]] == [FULL_W, SWA_W]
    for x in leaves:
        assert np.array_equal(np.asarray(x).reshape(B * K, -1)[:, 0], np.asarray(want))
    assert np.array_equal(moved.shared.window, shared.window)


def test_the_search_serves_what_the_reference_scores_and_reports_its_window(params, weights):
    """The beam's served tokens: each served caption's score is the sum of
    the reference's log-probabilities of its tokens (teacher-forced on
    them, no cache), and ``BeamResult.decoder_stats`` holds the window's
    counters and the state's bytes split by kind of leaf."""
    ctx, _ = _inputs(seed=2, B=4)
    T, K = 8, 3
    out = bs.beam_search_jit(params, CONFIG, ctx, 1, beam_size=K, valid_size=100, max_len=T, early_exit=False)
    stats = out.decoder_stats
    assert stats["step_selected"].shape == (4, K, T, 2, 16) and stats["step_routes"].shape == (4, K, T, 12)
    attended, visible = np.asarray(stats["swa_attended"]).tolist()
    assert attended == 3 * 4 * K * T * 9 and visible == 3 * 4 * K * sum(N + t + 1 for t in range(T))
    assert np.asarray(stats["prefill_fused_blocks"]).tolist() == [0, 5]
    assert np.asarray(stats["prefill_fused_blocks_by_kind"]).tolist() == [[0, 2], [0, 3]]
    window = 3 * 2 * SWA_W * (4 * KEPT + 4 * K * T)
    full = 2 * 2 * (FULL_W + 16) * (4 * N + 4 * K * T)
    records = 4 * K * T * (12 + 2 * 16) * 4
    assert int(stats["state_bytes_window"]) == window and int(stats["state_bytes"]) == window + full + records
    words, lengths = np.asarray(out.words[:, 0]), np.asarray(out.lengths[:, 0])
    logits, _, _ = _reference(weights, ctx, words)
    logp = jax.nn.log_softmax(logits, axis=-1)
    for b in range(4):
        n = int(lengths[b])
        want = float(np.take_along_axis(np.asarray(logp[b, :n]), words[b, :n, None], axis=-1).sum())
        assert abs(float(out.log_scores[b, 0]) - want) < 0.25, (b, float(out.log_scores[b, 0]), want)


def test_a_sliding_layer_that_keeps_its_whole_prefix_shows_in_the_window_s_bytes(params, monkeypatch):
    """``state_bytes_window`` is the bytes of the sliding layers' own
    leaves, not arithmetic on the ``Config``: with ``sliding_window_size``
    as it was and the prefix kept whole it reads the whole prefix."""
    import collections

    ctx, _ = _inputs(B=2)
    K, T = 3, 4
    result = collections.namedtuple("Result", "decoder_stats")(None)

    def window_bytes():
        search = decoders.search(params, CONFIG, ctx, K, T)
        return int(search.finish(result, search.state0).decoder_stats["state_bytes_window"])

    assert window_bytes() == 3 * 2 * SWA_W * (2 * KEPT + 2 * K * T)
    monkeypatch.setattr(d3, "_kept", lambda config, positions: positions)
    assert window_bytes() == 3 * 2 * SWA_W * (2 * N + 2 * K * T)


def test_the_prefix_stays_per_image_and_a_sliding_layer_s_is_its_tail(params):
    ctx, _ = _inputs(B=2)
    search = decoders.search(params, CONFIG, ctx, 3, 20)
    beam = jax.tree_util.tree_leaves(search.state0.beam)
    assert all(x.shape[0] == 6 and N not in x.shape[1:] for x in beam)
    text = jax.jit(lambda: search.step_fn(search.state0, jnp.zeros((6,), jnp.int32))[1]).lower().as_text()
    assert f"tensor<2x{N}x{FULL_W}xbf16>" in text and f"tensor<2x{KEPT}x{SWA_W}xbf16>" in text
    assert f"tensor<6x{N}x{FULL_W}x" not in text and f"x{N}x{SWA_W}x" not in text
