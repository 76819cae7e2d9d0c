"""The command-a-plus caption decoder (models/cohere2_moe.py: a PARALLEL
block, one LayerNorm for both branches, over grouped-query attention;
rotary sliding layers beside a full layer with no positional term; four
shared experts averaged beside a held share of the routed ones; keys and
values of two lengths in one search state) at toy widths on the CPU, held
against the plain float32 reference under benchmark/reference (which
imports nothing of the program, repeats keys and values over their group,
bounds the window by a comparison of positions and applies the shared
experts apart), on seeded weights whose values are bfloat16-representable.
``sliding_window_size`` is 9 against 36 + 20 positions, so the band is
active in the prefill, the kept tail is 8 of 36 positions, and the steps
slide past tail and suffix both.

Tolerances, each x the compared output's scale (tests/test_deepseek_v3.py
has the reasons: bfloat16 products and a bfloat16 residual stream against
float32 ``highest``): a layer 3e-2, the whole forward 6e-2, two paths of
the program against each other 1e-2.  No selection here, so no flips.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from drivers.decode_offline_gqa import _sabotaged_program  # noqa: E402
from reference import cohere2_captioner as ref  # noqa: E402
from reference import params_cohere2  # noqa: E402
from reference.params import nest  # noqa: E402

from sat_tpu.config import Config  # noqa: E402
from sat_tpu.models import cohere2_moe as c2  # noqa: E402
from sat_tpu.models import decoders, lm_common  # noqa: E402
from sat_tpu.ops import flash_prefill  # noqa: E402

from fixtures import plain_moe_ffn  # noqa: E402
from test_glm_moe_dsa import FORWARD_TOL, LAYER_TOL, PATH_TOL, _close  # noqa: E402

bs = importlib.import_module("sat_tpu.ops.beam_search")

KINDS = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention")
TOY = dict(
    decoder="cohere2_moe", cnn="vgg16", image_size=96, hidden_size=64, moe_intermediate_size=24,
    num_hidden_layers=4, num_dense_layers=0, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    num_experts=16, num_experts_per_tok=3, experts_held=2, first_expert=4, n_shared_experts=4,
    sliding_window_size=9, layer_types=KINDS, tie_word_embeddings=True, vocabulary_size=100,
    max_caption_length=20, beam_size=3, norm_eps=1e-5, rope_theta=100.0, norm_topk_prob=True,
    use_expert_bias=False, routed_scaling_factor=1.0, logit_scale=1.0,
)
CONFIG = Config(**TOY)


def _model(toy):
    return {**toy, "layer_types": list(toy["layer_types"])}


MODEL = _model(TOY)
N = CONFIG.num_ctx                  # 36: a 96-px image's 6 x 6 grid
KV_W = 2 * 16                       # what a token leaves in a layer's cache: keys, and as many values
KEPT = 8                            # window - 1 of the prefix's 36 positions


def _weights(model, seed=7):
    return params_cohere2.make_weights(model, seed, only=lambda n: n.startswith("params/decoder/"))


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


def _inputs(seed=0, B=2, T=20, n=N):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    ctx = jax.random.normal(k1, (B, n, CONFIG.dim_ctx)).astype(jnp.bfloat16).astype(jnp.float32)
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
    made, counts, _ = c2.prefill(params, config, ctx)
    prefix = prefix or made
    cache = c2.start_beams(config, prefix, 1, T, decoders.tile_beams)
    counters = c2.init_counters(counts, T)
    words_in = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), tokens[:, :-1]], axis=1)
    step = jax.jit(lambda c, n, w: c2.step(params, config, prefix, c, n, w))
    cached = []
    for t in range(T):
        cache, counters, logits = step(cache, counters, words_in[:, t])
        cached.append(logits)
    return jnp.stack(cached, axis=1), prefix, cache, counters


# ---------------------------------------------------------------------------
# the configuration and the tree
# ---------------------------------------------------------------------------


def test_the_program_s_tree_is_the_benchmark_s_spec():
    shapes = jax.eval_shape(lambda: c2.init_params(jax.random.PRNGKey(0), CONFIG))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    got = {"params/decoder/" + "/".join(str(k.key) for k in path): (tuple(leaf.shape), str(leaf.dtype))
           for path, leaf in flat}
    want = {name: (tuple(shape), dtype) for name, (shape, _, dtype) in params_cohere2.decoder_spec(MODEL).items()}
    assert got == want
    # ONE norm a layer, a head of 16 that is not 64 / 8, grouped keys, four shared experts side by side,
    # no selection bias and no head but the embedding
    layer = "params/decoder/lm/layers/03/"
    assert got[layer + "self_attn/q_proj"] == ((64, 128), "bfloat16")
    assert got[layer + "self_attn/k_proj"] == ((64, 32), "bfloat16")
    assert got[layer + "feed_forward/shared/w1"] == ((64, 96), "bfloat16")
    assert got[layer + "feed_forward/w1"] == ((2, 64, 24), "bfloat16")
    assert not [k for k in got if k.endswith(("expert_bias", "ffn_norm", "operator_norm", "lm_head"))]


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=("latent_attention",) * 4), "layer_types"),
    (dict(layer_types=KINDS[:3]), "layer_types"),
    (dict(num_key_value_heads=3), "num_key_value_heads"),
    (dict(head_dim=15), "even head"),
    (dict(sliding_window_size=0), "sliding_window_size"),
    (dict(num_dense_layers=1), "num_dense_layers"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(phase="serve"), "does not run with phase='serve'"),
    (dict(phase="bulk"), "does not run with phase='bulk'"),
    (dict(phase="route"), "does not run with phase='route'"),
    (dict(mesh_shape=(2, 1)), "one device only"),
    (dict(save_attention_maps=True), "save_attention_maps"),
    # a head's width and the logits' factor are this stack's: no other decoder leaves them out in silence
    (dict(decoder="lfm2_moe", layer_types=("conv", "full_attention") * 2, num_dense_layers=1), 'only decoder="cohere2_moe"'),
    (dict(decoder="lfm2_moe", layer_types=("conv", "full_attention") * 2, num_dense_layers=1, head_dim=0,
          logit_scale=0.5), 'only decoder="cohere2_moe"'),
])
def test_the_configuration_refuses_what_it_cannot_run(change, match):
    with pytest.raises(ValueError, match=match):
        Config(**{**TOY, **change})


# ---------------------------------------------------------------------------
# the grouped kernel
# ---------------------------------------------------------------------------


def _plain_grouped(q, k, v, window, scale):
    """Every score, float32, keys and values repeated over their group."""
    (nh, S, _), kv = q.shape, k.shape[0]
    k, v = np.repeat(k, nh // kv, axis=0), np.repeat(v, nh // kv, axis=0)
    ahead = np.arange(S)[:, None] - np.arange(S)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    scores = np.where(seen, np.einsum("hsd,htd->hst", q, k) * scale, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("hst,htd->shd", probs, v).reshape(S, -1)


@pytest.mark.parametrize("S,window,tiles,heads,kv", [
    (64, None, (16, 8, 4), 8, 2),       # a program's four heads share ONE key/value head (the published 16 of 16)
    (64, None, (16, 8, 8), 8, 2),       # a program's heads span two key/value heads
    (64, 33, (16, 8, 4), 8, 2),         # a window of several key tiles: the band meets 5-6 of 8
    (64, 40, (16, 16, 2), 8, 4),        # fewer heads a program than a group
    (64, 9, (16, 8, 8), 8, 1),          # one key/value head for all (multi-query), a band narrower than a tile
    (48, 17, (8, 8, 3), 6, 2),          # a group of three
    (32, 100, (8, 16, 4), 4, 4),        # group 1 under a window wider than the sequence: the form that was
], ids=["shared-head", "two-heads", "window-tiles", "under-group", "multi-query", "group-3", "ungrouped"])
def test_the_grouped_kernel_against_the_lax_form_and_every_score(S, window, tiles, heads, kv):
    keys = jax.random.split(jax.random.PRNGKey(S + heads + (window or 0)), 3)
    q = jax.random.normal(keys[0], (heads, S, 16)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(key, (kv, S, 16)).astype(jnp.bfloat16) for key in keys[1:])
    scale = 16 ** -0.5
    got = flash_prefill.flash_prefill(q, k, v, None, scale=scale, tiles=tiles, interpret=True, window=window)
    want = _plain_grouped(*(np.asarray(x, np.float32) for x in (q, k, v)), window, scale)
    _close(got, want, 2e-2)     # bfloat16 weights in the second product
    lows, masks = lm_common.causal_blocks(S, window)
    _close(got, lm_common.attend_blocks(q, k, v, masks, scale, lows), 1e-2)


def test_the_grouped_kernel_refuses_heads_that_split_a_key_value_head():
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    with pytest.raises(ValueError, match="share whole key/value heads"):
        jax.eval_shape(lambda q, k, v: flash_prefill.flash_prefill(
            q, k, v, None, scale=1.0, tiles=(8, 8, 4), interpret=True), sd(12, 16, 8), sd(2, 16, 8), sd(2, 16, 8))
    with pytest.raises(ValueError, match="share whole key/value heads"):
        jax.eval_shape(lambda q, k, v: flash_prefill.flash_prefill(
            q, k, v, None, scale=1.0, tiles=(8, 8, 4), interpret=True), sd(8, 16, 8), sd(3, 16, 8), sd(3, 16, 8))


def test_the_grouped_kernel_fetches_one_key_value_head_a_program_at_the_published_shape():
    """128 query / 8 key-value heads of 128 over 9,216 positions, a window
    of 4,096, tiles of 512 x 256 x 16: a program's 16 heads read ONE
    key/value head (blocks [1, 256, 128], never [16, ..]), and the grid's
    key axis is as long as the band's 18 tiles, not 36."""
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    grids, blocks = {}, {}
    for window in (4096, None):
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, window=window: flash_prefill.flash_prefill(
                q, k, v, None, scale=1.0, interpret=True, window=window)
        )(sd(128, 9216, 128), sd(8, 9216, 128), sd(8, 9216, 128))
        call = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns if e.primitive.name == "pallas_call"][0]
        mapping = call.params["grid_mapping"]
        grids[window] = tuple(mapping.grid)
        blocks[window] = [tuple(int(getattr(n, "block_size", n)) for n in b.block_shape)
                          for b in mapping.block_mappings]
    assert grids == {4096: (8, 18, 18), None: (8, 18, 36)}
    assert blocks[4096][:3] == [(16, 512, 128), (1, 256, 128), (1, 256, 128)]


# ---------------------------------------------------------------------------
# one layer's two forms
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layer,t", [(2, 0), (2, 3), (2, 7), (2, 8), (2, 15), (3, 0), (3, 15)])
def test_a_step_s_window_at_the_prefix_s_boundary(params, layer, t):
    """One layer: the whole-sequence form over N + t + 1 positions against
    the step at position N + t over what the layer kept of the prefix and
    a suffix of t + 1.  A sliding layer (2): at t = 0 the band holds the
    whole tail of 8 and the token itself, at t = 8 the last of the tail has
    slid out.  The full layer (3) sees all N + t + 1, with no rope."""
    m = params["lm"]["layers"][f"{layer:02d}"]["self_attn"]
    sliding = layer == 2
    u = jax.random.normal(jax.random.PRNGKey(5), (N + t + 1, 64)).astype(jnp.bfloat16)
    want, (keys, values) = c2.attend_sequence(m, CONFIG, layer, u)
    first = N - KEPT if sliding else 0
    suffix = tuple(jnp.zeros((1, 20, KV_W), jnp.bfloat16).at[0, :t].set(x[N:N + t]) for x in (keys, values))
    prefix = (keys[None, first:N], values[None, first:N])
    got, (suffix, seen) = c2.attend_step(m, CONFIG, layer, u[-1:], prefix, suffix, jnp.int32(t))
    assert int(seen) == (9 if sliding else N + t + 1)
    assert np.array_equal(np.asarray(suffix[0][0, t], np.float32), np.asarray(keys[N + t], np.float32))
    _close(got[0], want[-1], PATH_TOL)
    if sliding:     # the same over the prefix kept whole: what the tail leaves out is never seen
        whole, (_, seen) = c2.attend_step(
            m, CONFIG, layer, u[-1:], (keys[None, :N], values[None, :N]), suffix, jnp.int32(t))
        assert int(seen) == 9 and np.array_equal(np.asarray(whole, np.float32), np.asarray(got, np.float32))


def _second_product_qkv(m, config, layer, u):
    """``_sequence_qkv`` as it stood until PR 45, the reference of the
    tests below: a sliding layer's partner by a SECOND product with the
    maps' columns swapped in pairs (``deepseek_v3._swapped_columns``)."""
    from sat_tpu.models.deepseek_v3 import _swapped_columns

    c = config
    H, d = u.shape[-1], c2._head_dim(c)
    w_q, w_k, w_v = (m[name].reshape(H, n, d) for name, n in (
        ("q_proj", c.num_attention_heads), ("k_proj", c.num_key_value_heads), ("v_proj", c.num_key_value_heads)))

    def product(w):
        return jnp.einsum("sh,hnd->nsd", u, w, preferred_element_type=jnp.float32).astype(jnp.bfloat16)

    q, k, v = product(w_q), product(w_k), product(w_v)
    if not c2._turns(c, layer):
        return q, k, v
    cos, sin = c2._rope_tables(jnp.arange(u.shape[0]), c.rope_theta, d)

    def turned(x, w):
        partner = product(_swapped_columns(w))
        return (x.astype(jnp.float32) * cos + partner.astype(jnp.float32) * sin).astype(jnp.bfloat16)

    return turned(q, w_q), turned(k, w_k), v


def _bits(x):
    return np.asarray(x).view(np.uint16)


@pytest.mark.parametrize("layer", [0, 3], ids=["sliding", "full"])
@pytest.mark.parametrize("d,heads,kv", [(16, 8, 2), (128, 4, 2)], ids=["toy_d16", "published_d128"])
def test_a_whole_sequence_s_rope_takes_its_partner_from_the_product_it_has(d, heads, kv, layer):
    """A sliding layer's queries and keys of a whole sequence
    (``x * cos + (x P) * sin``, P the signed swap inside a head's d lanes)
    are, BIT FOR BIT, what the second product by the maps' swapped columns
    gave (``x * cos + (u W P) * sin``): the partner was the bfloat16
    rounding of the same dot products, and the swap moves those same
    bfloat16 values.  u and the maps are small multiples of powers of two,
    so a dot product is exact in float32 in whatever order a backend sums
    it (with free values the CPU's two products round apart by one ulp on
    one element in 10,000 at d = 128: PERF.md section 6).  And within two
    bfloat16 roundings of the rows' form (``_rope``'s roll of ``u W``); a
    full layer's are the plain products."""
    config = Config(**{**TOY, "head_dim": d, "num_attention_heads": heads, "num_key_value_heads": kv})
    S, H = 24, config.hidden_size
    keys = jax.random.split(jax.random.PRNGKey(3), 4)

    def exact(key, shape, most, over):
        return (jax.random.randint(key, shape, -most, most + 1).astype(jnp.float32) / over).astype(jnp.bfloat16)

    m = {name: exact(key, (H, n * d), 64, 64) for name, n, key in (
        ("q_proj", heads, keys[0]), ("k_proj", kv, keys[1]), ("v_proj", kv, keys[2]))}
    u = exact(keys[3], (S, H), 16, 8)
    got = c2._sequence_qkv(m, config, layer, u)
    want = _second_product_qkv(m, config, layer, u)
    assert [x.shape for x in got] == [(heads, S, d), (kv, S, d), (kv, S, d)]
    for x, y in zip(got, want):
        assert x.dtype == jnp.bfloat16 and np.array_equal(_bits(x), _bits(y))
    for x, name, n in ((got[0], "q_proj", heads), (got[1], "k_proj", kv)):
        plain = lm_common.mm(u, m[name])
        if layer == 3:      # the full layer: no positional term
            assert np.array_equal(_bits(jnp.swapaxes(x, 0, 1).reshape(S, n * d)), _bits(plain))
        else:
            rows = c2._rope(plain.reshape(S, n, d).astype(jnp.float32), jnp.arange(S), config.rope_theta)
            _close(jnp.swapaxes(x, 0, 1), rows, 2e-2)     # two bfloat16 roundings: of the product, of the sum
            assert float(jnp.max(jnp.abs(jnp.swapaxes(x, 0, 1).astype(jnp.float32) - plain.reshape(S, n, d)))) > 0.1


def test_a_sliding_layer_s_whole_sequence_multiplies_by_each_map_once_at_the_published_shape():
    """Traced at the published shape (9,216 positions of 4,096; 128 query
    and 8 key/value heads of 128), nothing compiled: THREE products by the
    layer's maps, one by ``W_q`` [4096, 16384], one each by ``W_k`` and
    ``W_v`` [4096, 1024], where five stood (``W_q`` and ``W_k`` a second
    time for the rope's partner); what the rope adds is two products by
    the [128, 128] signed permutation, 1/32 of a map's.  A full layer has
    the three alone."""
    from test_aot_tpu import _command_a_config
    from test_deepseek_v3 import _all_eqns

    config = _command_a_config()
    S, H = config.num_ctx, config.hidden_size
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    m = {"q_proj": sd(H, 16384), "k_proj": sd(H, 1024), "v_proj": sd(H, 1024)}

    def products(layer):
        traced = jax.make_jaxpr(lambda m, u: c2._sequence_qkv(m, config, layer, u))(m, sd(S, H))
        assert [x.shape for x in traced.out_avals] == [(128, S, 128), (8, S, 128), (8, S, 128)]
        return sorted(
            tuple(v.aval.shape for v in e.invars) for e in _all_eqns(traced.jaxpr) if e.primitive.name == "dot_general"
        )

    maps = [((H, 8, 128), (S, H)), ((H, 8, 128), (S, H)), ((H, 128, 128), (S, H))]
    assert (S, H) == (9216, 4096) and products(3) == maps
    assert products(0) == [((8, S, 128), (128, 128)), ((128, S, 128), (128, 128))] + maps


def test_the_gradient_through_a_whole_sequence_is_the_second_product_s(params, monkeypatch):
    """``teacher_forced`` differentiated (the connector trains through the
    sliding layers' rope): the swap inside the head is a product as the
    maps' are, and its gradient the second product's within two paths'
    rounding."""
    ctx, tokens = _inputs(seed=4)

    def loss(connector):
        logits = c2.teacher_forced({**params, "connector": connector}, CONFIG, ctx, tokens)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits), tokens[..., None], axis=-1)
        return -jnp.mean(picked)

    got = jax.grad(loss)(params["connector"])
    monkeypatch.setattr(c2, "_sequence_qkv", _second_product_qkv)
    want = jax.grad(loss)(params["connector"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert leaves
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0, path
        _close(g, w, PATH_TOL)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("blocks", ["one_block", "blocks_of_8"])
def test_teacher_forced_logits_against_the_plain_full_forward(params, weights, blocks, monkeypatch):
    if blocks == "blocks_of_8":
        monkeypatch.setattr(lm_common, "QUERY_BLOCK", 8)
    ctx, tokens = _inputs()
    want, routes = _reference(weights, ctx, tokens)
    got = jax.jit(lambda p, c, t: c2.teacher_forced(p, CONFIG, c, t))(params, ctx, tokens)
    _close(got, want, FORWARD_TOL)
    assert routes.shape == (4, 2, N + 20, 3)


def test_prefill_then_20_cached_steps_equal_the_full_forward(params, weights, small_blocks):
    ctx, tokens = _inputs(seed=1)
    B, T = tokens.shape
    cached, prefix, cache, counters = _cached_logits(params, CONFIG, ctx, tokens)
    # keys and values of TWO lengths in one state: a sliding layer's tail, the full layer's whole prefix
    assert [x.shape for x in prefix.keys] == [(B, KEPT, KV_W)] * 3 + [(B, N, KV_W)]
    assert [x.shape for x in cache.values] == [(B, T, KV_W)] * 4
    tf = c2.teacher_forced(params, CONFIG, ctx, tokens)
    _close(cached, tf, PATH_TOL)
    assert np.asarray(counters.window).tolist() == [3 * T * B * 9, 3 * B * sum(N + t + 1 for t in range(T))]
    want, routes = _reference(weights, ctx, tokens)
    _close(cached, want, FORWARD_TOL)
    taken = np.asarray(cache.routes).reshape(B, T, 4, 3).transpose(2, 0, 1, 3)
    assert (np.sort(taken, -1) == np.sort(routes[:, :, N:], -1)).all(-1).mean() > 0.9


def test_the_tail_alone_gives_the_logits_of_a_cache_kept_whole(params, monkeypatch):
    """The sliding layers' prefix kept WHOLE ([B, 36, 32]) against its last
    8 positions: the same logits to the bit, a fifth of the bytes."""
    ctx, tokens = _inputs(seed=3)
    cached, prefix, _, _ = _cached_logits(params, CONFIG, ctx, tokens)
    monkeypatch.setattr(c2, "_kept", lambda config, positions: positions)
    whole, _, _ = c2.prefill(params, CONFIG, ctx)
    assert [x.shape[1] for x in whole.keys] == [N] * 4
    for kept, tail in zip(whole.values[:3], prefix.values[:3]):
        assert np.array_equal(np.asarray(kept[:, N - KEPT:], np.float32), np.asarray(tail, np.float32))
    again, _, _, _ = _cached_logits(params, CONFIG, ctx, tokens, prefix=whole)
    assert np.array_equal(np.asarray(again), np.asarray(cached))


@pytest.mark.parametrize("hook,blocks", [(True, [[8, 8], [24, 24]]), (False, [[0, 8], [0, 24]])], ids=["fused", "lax"])
def test_prefill_through_the_grouped_kernel_then_20_cached_steps_equal_the_full_forward(hook, blocks, monkeypatch):
    """A 128-px image: 64 prefix positions, whole blocks of 8 queries, so
    the prefill takes ``ops/flash_prefill.py``'s kernel (interpreted) where
    the hook says it is available, in its grouped form, the sliding layers
    under a window of 33: several key tiles of 8."""
    toy = {**TOY, "image_size": 128, "sliding_window_size": 33}
    config = Config(**toy)
    weights = _weights(_model(toy))
    params = jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))
    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 8)
    monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", hook)
    monkeypatch.setattr(flash_prefill, "_TILES", (8, 8, 4))
    ctx, tokens = _inputs(seed=4, n=64)
    cached, prefix, _, counters = _cached_logits(params, config, ctx, tokens)
    assert np.asarray(counters.fused).tolist() == blocks
    assert [x.shape[1] for x in prefix.keys] == [32, 32, 32, 64]
    want, _ = _reference(weights, ctx, tokens, _model(toy))
    _close(cached, want, FORWARD_TOL)


# ---------------------------------------------------------------------------
# what the benchmark's sabotaged programs take away is there
# ---------------------------------------------------------------------------

# a stream whose scores spread (the rehearsal's reason: at 64 wide every softmax is near uniform), and weights
# under which each branch weighs in the logits: q_proj and k_proj x 4 (peaked scores), o_proj and every w2 x 4
# (the draws scale them down by sqrt(2 x layers)); every value stays bfloat16-exact
SHARP = {**TOY, "hidden_size": 256}


@pytest.fixture(scope="module")
def sharp():
    weights = _weights(_model(SHARP))
    for name in weights:
        if name.endswith(("q_proj", "k_proj", "o_proj", "w2")):
            weights[name] = (weights[name].astype(np.float32) * 4).astype(weights[name].dtype)
    ctx, tokens = _inputs()
    want, _ = _reference(weights, ctx, tokens, _model(SHARP))
    return jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder")), ctx, tokens, want


@pytest.mark.parametrize("changed", ["nothing", "no_window", "rope_in_full", "serial_block", "shared_sum", "rms_norm"])
def test_each_part_changed_in_the_program_changes_the_logits(sharp, changed):
    """The window, the full layer's lack of a positional term, the parallel
    block, the shared experts' MEAN and the mean LayerNorm takes out each
    move the logits.  The measure is the mean gap to the reference over the
    logits, in units of their scale: the sound program reads 0.0017, the
    serial block 0.022, RMS for LayerNorm 0.029, the shared experts summed
    0.051, rope in the full layer 0.058, no window 0.20."""
    params, ctx, tokens, want = sharp
    sound = Config(**SHARP)
    config = sound.replace(sliding_window_size=N + 21) if changed == "no_window" else sound
    with _sabotaged_program(changed):       # the benchmark's own swaps of the program's functions
        got = c2.teacher_forced(params, config, ctx, tokens)
    gap = float(np.abs(np.asarray(got) - want).mean() / np.abs(want).max())
    assert gap < 0.005 if changed == "nothing" else gap > 0.015, (changed, gap)


# ---------------------------------------------------------------------------
# the expert layer: its eight shares, and the serial callers' results
# ---------------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_reference_s_uncut_layer():
    """experts_held 2 of 16 = one of EIGHT chips: the routed parts of the
    eight shares and the shared experts' mean counted ONCE are the uncut
    reference's expert branch."""
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
    for first in range(0, 16, 2):
        held = {**f, **{w: f[w][first:first + 2] for w in ("w1", "w3", "w2")}}
        config = Config(**{**toy, "experts_held": 2, "first_expert": first})
        share = jax.jit(lambda f, u, config=config: lm_common.moe_experts(f, config, u, 0.0, shared_mean_of=4))
        y, _, experts, pairs = share(held, u)
        y_routed = share({k: v for k, v in held.items() if k != "shared"}, u)[0]
        routed, shared_part = routed + y_routed, y - y_routed
        assert int(pairs.over) == 0
        seen += int(pairs.held)
    assert seen == T * 3 and (np.sort(experts, -1) == np.sort(chosen, -1)).all(-1).mean() > 0.9
    _close(routed + shared_part, want, LAYER_TOL)
    # the mean, not the sum: four times the shared part is the sum's
    summed = lm_common.shared_experts(f, u, 1)
    assert np.array_equal(np.asarray(summed / 4), np.asarray(lm_common.shared_experts(f, u, 4)))


@pytest.mark.parametrize("held,shared", [(0, True), (0, False), (4, True), (4, False)],
                         ids=["all-shared", "all", "share-shared", "share"])
def test_the_serial_callers_get_the_parent_s_result_to_the_bit(held, shared):
    """``moe_ffn`` wraps the ONE expert layer in its own norm and its own
    add: x + what ``moe_experts`` returns on ``ffn_norm(x)``.  With every
    expert held (``experts_held`` 0) that is, to the bit, the plain layer
    written out (``fixtures.plain_moe_ffn``); at a share, what the held
    experts and the shared one add to it."""
    config = Config(**{**TOY, "decoder": "deepseek_v3", "layer_types": ("latent_attention",) * 4, "head_dim": 0,
                       "use_expert_bias": True, "experts_held": held, "first_expert": 4 if held else 0})
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 12))
    linear = lambda *shape: (0.2 * jax.random.normal(next(keys), shape)).astype(jnp.bfloat16)  # noqa: E731
    f = lm_common.ffn_params(config, 0, linear, shared=False)
    f["expert_bias"] = 0.1 * jax.random.normal(next(keys), (16,))
    if shared:
        f["shared"] = {"w1": linear(64, 48), "w3": linear(64, 48), "w2": linear(48, 64)}
    p = {"ffn_norm": (1 + 0.1 * jax.random.normal(next(keys), (64,))).astype(jnp.bfloat16), "feed_forward": f}
    x = jax.random.normal(next(keys), (40, 64)).astype(jnp.bfloat16)
    got, counts, experts, pairs = jax.jit(lambda p, x: lm_common.moe_ffn(p, config, x, 1e-20))(p, x)
    u = lm_common.rms_norm(x, p["ffn_norm"], config.norm_eps).astype(jnp.bfloat16)
    y, counts2, experts2, _ = lm_common.moe_experts(f, config, u, 1e-20)
    assert np.array_equal(np.asarray(x + y.astype(x.dtype), np.float32), np.asarray(got, np.float32))
    assert np.array_equal(counts, counts2) and np.array_equal(experts, experts2) and int(pairs.over) == 0
    if held:
        # against the uncut layer: what the four held experts add, and the shared expert's
        whole = {**p, "feed_forward": {**f, **{w: jnp.zeros((16,) + f[w].shape[1:], f[w].dtype).at[4:8].set(f[w])
                                               for w in ("w1", "w3", "w2")}}}
        want, _, chosen = plain_moe_ffn(whole, config, x, 1e-20)
        assert np.array_equal(experts, chosen)
        _close(got, want, 1e-2)     # the zero experts' pairs add exact zeros, in another order of the k-term sum
    else:
        want, sizes, chosen = jax.jit(lambda p, x: plain_moe_ffn(p, config, x, 1e-20))(p, x)
        assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
        assert np.array_equal(counts, sizes) and np.array_equal(experts, chosen)
        assert (int(pairs.held), int(pairs.routed), int(pairs.fetched)) == (120, 120, 120)


# ---------------------------------------------------------------------------
# through the search
# ---------------------------------------------------------------------------


def test_the_reorder_moves_keys_and_values_of_both_lengths_alike():
    B, K = 2, 3
    rows = jnp.arange(B * K, dtype=jnp.float32)
    leaf = lambda *shape: rows.reshape((B * K,) + (1,) * len(shape)) + jnp.zeros((B * K,) + shape)  # noqa: E731
    cache = c2.GqaCache(keys=(leaf(5, KV_W), leaf(5, KV_W)), values=(leaf(5, KV_W), leaf(5, KV_W)), routes=leaf(30))
    shared = c2.Counters(t=jnp.int32(7), moe_counts=jnp.arange(8).reshape(2, 4),
                         step_visits=jnp.arange(10).reshape(2, 5), pairs=jnp.arange(12).reshape(2, 6),
                         window=jnp.arange(2), fused=jnp.arange(4).reshape(2, 2))
    parent = jnp.array([[2, 0, 1], [1, 1, 0]])
    moved = bs._reorder_beams(bs.StepState(cache, shared), B, K, jnp.arange(B)[:, None], parent)
    want = (jnp.arange(B)[:, None] * K + parent).reshape(-1).astype(jnp.float32)
    for x in jax.tree_util.tree_leaves(moved.beam):
        assert np.array_equal(np.asarray(x).reshape(B * K, -1)[:, 0], np.asarray(want))
    assert np.array_equal(moved.shared.window, shared.window) and np.array_equal(moved.shared.pairs, shared.pairs)


def test_the_search_serves_what_the_reference_scores_and_reports_its_window(params, weights):
    """The beam's served tokens: each served caption's score is the sum of
    the reference's log-probabilities of its tokens (teacher-forced on
    them, no cache), and ``BeamResult.decoder_stats`` holds the window's
    counters, the held share's pairs and the state's bytes split by kind of
    leaf."""
    ctx, _ = _inputs(seed=2, B=4)
    T, K = 8, 3
    out = bs.beam_search_jit(params, CONFIG, ctx, 1, beam_size=K, valid_size=100, max_len=T, early_exit=False)
    stats = out.decoder_stats
    assert stats["step_routes"].shape == (4, K, T, 12) and stats["prefix_routes"].shape == (4, N, 12)
    attended, visible = np.asarray(stats["swa_attended"]).tolist()
    assert attended == 3 * 4 * K * T * 9 and visible == 3 * 4 * K * sum(N + t + 1 for t in range(T))
    assert np.asarray(stats["prefill_fused_blocks"]).tolist() == [0, 4]
    assert np.asarray(stats["prefill_fused_blocks_by_kind"]).tolist() == [[0, 1], [0, 3]]
    pairs = np.asarray(stats["moe_pairs"])
    assert pairs[:, 1].tolist() == [4 * 4 * N * 3, 4 * 4 * K * T * 3] and pairs[:, 2].tolist() == [0, 0]
    assert np.asarray(stats["moe_combine"])[:, 2].tolist() == [4 * 4, 4 * T]
    window = 3 * 2 * 2 * KV_W * (4 * KEPT + 4 * K * T)
    full = 2 * 2 * KV_W * (4 * N + 4 * K * T)
    records = 4 * K * T * 12 * 4
    assert int(stats["state_bytes_window"]) == window and int(stats["state_bytes"]) == window + full + records
    words, lengths = np.asarray(out.words[:, 0]), np.asarray(out.lengths[:, 0])
    logits, _ = _reference(weights, ctx, words)
    logp = jax.nn.log_softmax(logits, axis=-1)
    for b in range(4):
        n = int(lengths[b])
        want = float(np.take_along_axis(np.asarray(logp[b, :n]), words[b, :n, None], axis=-1).sum())
        assert abs(float(out.log_scores[b, 0]) - want) < 0.25, (b, float(out.log_scores[b, 0]), want)


def test_a_sliding_layer_that_keeps_its_whole_prefix_shows_in_the_window_s_bytes(params, monkeypatch):
    """``state_bytes_window`` is the bytes of the sliding layers' own
    leaves, not arithmetic on the ``Config``."""
    import collections

    ctx, _ = _inputs(B=2)
    K, T = 3, 4
    result = collections.namedtuple("Result", "decoder_stats")(None)

    def window_bytes():
        search = decoders.search(params, CONFIG, ctx, K, T)
        return int(search.finish(result, search.state0).decoder_stats["state_bytes_window"])

    assert window_bytes() == 3 * 2 * 2 * KV_W * (2 * KEPT + 2 * K * T)
    monkeypatch.setattr(c2, "_kept", lambda config, positions: positions)
    assert window_bytes() == 3 * 2 * 2 * KV_W * (2 * N + 2 * K * T)


def test_the_prefix_stays_per_image_and_a_sliding_layer_s_is_its_tail(params):
    ctx, _ = _inputs(B=2)
    search = decoders.search(params, CONFIG, ctx, 3, 20)
    beam = jax.tree_util.tree_leaves(search.state0.beam)
    assert all(x.shape[0] == 6 and N not in x.shape[1:] for x in beam)
    text = jax.jit(lambda: search.step_fn(search.state0, jnp.zeros((6,), jnp.int32))[1]).lower().as_text()
    assert f"tensor<2x{N}x{KV_W}xbf16>" in text and f"tensor<2x{KEPT}x{KV_W}xbf16>" in text
    assert f"tensor<6x{N}x{KV_W}x" not in text and f"tensor<6x{KEPT}x{KV_W}x" not in text
