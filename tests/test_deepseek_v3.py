"""The DeepSeek-V3 caption decoder (models/deepseek_v3.py: latent attention
in its two forms, small experts beside a shared one, an untied head) at toy
widths on the CPU, held against the plain float32 reference under
benchmark/reference (which imports nothing of the program and has the
EXPANDED form only), on seeded weights whose values are
bfloat16-representable, so that program and reference hold the same
numbers and differ only in arithmetic.

Tolerances are tests/test_lfm2.py's, for its reasons: a layer at 3e-2 x
the output's scale, a whole forward at 6e-2 x the logits' scale, two paths
of the PROGRAM that do the same arithmetic in another order at 1e-2 (an ulp
or two of bfloat16).  The absorbed step and the expanded form are two such
paths: the program rounds its products to bfloat16 in both (it has no
float32 switch), so the program's two forms are compared at that
tolerance, and the identity itself (W_kvb's key half into the query, its
value half after the weighted sum) is held to a few ulp of float32 by this
file's own twin of both forms.
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

from reference import kanana2_captioner as ref  # noqa: E402
from reference import params_kanana2  # noqa: E402
from reference.params import nest  # noqa: E402

from sat_tpu.config import Config  # noqa: E402
from sat_tpu.models import deepseek_v3 as ds  # noqa: E402
from sat_tpu.models import decoders, lm_common  # noqa: E402
from sat_tpu.models.captioner import compute_loss  # noqa: E402

bs = importlib.import_module("sat_tpu.ops.beam_search")

TOY = dict(
    decoder="deepseek_v3", cnn="vgg16", image_size=32, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=24, num_hidden_layers=4, num_dense_layers=1, num_attention_heads=4,
    num_experts=8, num_experts_per_tok=3, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_shared_experts=2, tie_word_embeddings=False,
    layer_types=("latent_attention",) * 4,
    # 100 = no multiple of 128 and no power of two, as 128,256 = 1002 x 128 is none
    vocabulary_size=100, max_caption_length=20, beam_size=3, norm_eps=1e-6, rope_theta=1e6,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=2.448,
)
CONFIG = Config(**TOY)
MODEL = {**TOY, "layer_types": list(TOY["layer_types"])}
WIDTH = TOY["kv_lora_rank"] + TOY["qk_rope_head_dim"]      # 40: what a token leaves in the cache
LAYER_TOL = 3e-2     # x the output's scale: see the module docstring
FORWARD_TOL = 6e-2
PATH_TOL = 1e-2


@pytest.fixture(scope="module")
def weights():
    """Seeded decoder leaves, {path: numpy}, as the benchmark makes them."""
    return params_kanana2.make_weights(MODEL, 7, only=lambda n: n.startswith("params/decoder/"))


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))


def _inputs(seed=0, B=2, T=20):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    N, D = CONFIG.num_ctx, CONFIG.dim_ctx
    ctx = jax.random.normal(k1, (B, N, D)).astype(jnp.bfloat16).astype(jnp.float32)
    tokens = jax.random.randint(k2, (B, T), 2, CONFIG.vocabulary_size)
    return ctx, tokens


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * float(np.abs(want).max()))


def _subtree(weights, prefix):
    path = "params/decoder/" + prefix
    return weights[path] if path in weights else nest(weights, path)


def test_the_program_s_tree_is_the_benchmark_s_spec():
    """Names, shapes and dtypes: the untied head and the shared expert are
    leaves of their own."""
    shapes = jax.eval_shape(lambda: ds.init_params(jax.random.PRNGKey(0), CONFIG))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    program = {"params/decoder/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
               for path, leaf in flat}
    spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype)
            in params_kanana2.decoder_spec(MODEL).items()}
    assert program == spec
    assert spec["params/decoder/lm/lm_head"] == ((64, 100), "bfloat16")
    assert spec["params/decoder/lm/layers/01/feed_forward/shared/w1"] == ((64, 48), "bfloat16")
    assert "params/decoder/lm/layers/00/feed_forward/shared/w1" not in spec     # the dense layer has none


def test_rope_turns_interleaved_pairs_and_scores_as_the_reference_s_halves_layout():
    """The program keeps the pairs where they are, the reference (as the
    source's modelling code) brings them to the halves layout first: the
    same rotation of the same pairs, so every q . k is equal."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 3, 8))
    got_x, got_y = (ds._rope(a, jnp.arange(5), 1e6) for a in (x, y))
    want_x, want_y = (ref._rope(a, 1e6) for a in (x, y))
    np.testing.assert_allclose(np.asarray(got_x)[..., 0::2], np.asarray(want_x)[..., :4], atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_x)[..., 1::2], np.asarray(want_x)[..., 4:], atol=1e-6)
    np.testing.assert_allclose(jnp.einsum("bshd,bthd->bhst", got_x, got_y),
                               jnp.einsum("bshd,bthd->bhst", want_x, want_y), atol=1e-5)
    # position 0 is not turned; pair i of position 1 turns by theta^(-2i/d)
    np.testing.assert_allclose(got_x[:, 0], x[:, 0], atol=1e-7)
    angle = 1e6 ** (-2 * 1 / 8)
    np.testing.assert_allclose(got_x[0, 1, 0, 2], x[0, 1, 0, 2] * np.cos(angle) - x[0, 1, 0, 3] * np.sin(angle),
                               atol=1e-6)


def _rope_rolled(x, positions, theta):
    """``_rope`` as it stood before its partner and its tables got names of
    their own (PR 38), kept here as the yardstick of "to the bit"."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.repeat(positions.astype(jnp.float32)[:, None, None] * inv, 2, axis=-1)
    even = jnp.arange(d) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return x * jnp.cos(angle) + partner * jnp.sin(angle)


THETAS = pytest.mark.parametrize("theta", [1e6, 8e6], ids=["kanana2", "glm52"])


@THETAS
def test_rope_is_the_rolled_form_to_the_bit(theta):
    """The steps' rope and the one rotary key: today's numbers."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 56, 3, 8))
    got = jax.jit(lambda x: ds._rope(x, jnp.arange(56), theta))(x)
    want = jax.jit(lambda x: _rope_rolled(x, jnp.arange(56), theta))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cos, sin = ds._rope_tables(jnp.arange(56), theta, 8, lead=5)
    assert cos.shape == sin.shape == (56, 13)
    assert (np.asarray(cos)[:, :5] == 1).all() and (np.asarray(sin)[:, :5] == 0).all()


def test_the_swapped_columns_are_the_partner_of_the_rotary_columns(params):
    """``W_r P``: ``W_r``'s own columns swapped in pairs, the one moved to
    the even place negated, behind ``lead`` columns of zeros; and
    ``u (W_r P)`` is the partner of ``u W_r`` (exactly: every product of two
    bfloat16 numbers and every sum of 64 of them is exact in float64)."""
    w = params["lm"]["layers"]["00"]["self_attn"]["q_proj"].reshape(64, 4, 24)[..., 16:]
    got = np.asarray(ds._swapped_columns(w, lead=3), np.float64)
    w = np.asarray(w, np.float64)
    assert got.shape == (64, 4, 11) and (got[..., :3] == 0).all()
    np.testing.assert_array_equal(got[..., 3::2], -w[..., 1::2])
    np.testing.assert_array_equal(got[..., 4::2], w[..., 0::2])
    np.testing.assert_array_equal(got[..., 3:], np.asarray(ds._partner(jnp.asarray(w, jnp.float32))))
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (7, 64)).astype(jnp.bfloat16), np.float64)
    product = np.einsum("sr,rhd->shd", u, w)
    partner = np.stack([-product[..., 1::2], product[..., 0::2]], axis=-1).reshape(product.shape)
    np.testing.assert_array_equal(np.einsum("sr,rhd->shd", u, got[..., 3:]), partner)


@THETAS
def test_a_whole_sequence_s_query_is_the_rolled_one_to_the_bit(params, theta):
    """``_sequence_queries`` (three products over the flat width, the
    partner out of the swapped columns) against ``_queries`` (one product,
    split and rolled): the same dot products, the same roundings."""
    config = dataclasses.replace(CONFIG, rope_theta=theta)
    m = params["lm"]["layers"]["01"]["self_attn"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 56, 64)).astype(jnp.bfloat16)
    positions = jnp.arange(56)
    got = jax.jit(lambda h: ds._sequence_queries(m, ds.widths(config), h, positions))(h)
    want = jax.jit(lambda h: ds._queries(m, ds.widths(config), h, positions))(h)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.bfloat16 and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


def test_whole_sequences_take_the_swapped_columns_and_the_steps_the_roll(params, monkeypatch):
    """The two forms part by call site: ``attend_expanded`` (teacher
    forcing, the prefill) asks ``_sequence_queries``, ``attend_absorbed``
    (a step's rows, bound by reading ``W_q``) asks ``_queries``."""
    asked = []
    for name in ("_queries", "_sequence_queries"):
        def spy(*args, _name=name, _fn=getattr(ds, name)):
            asked.append(_name)
            return _fn(*args)
        monkeypatch.setattr(ds, name, spy)
    ctx, tokens = _inputs()
    jax.eval_shape(lambda: ds.teacher_forced(params, CONFIG, ctx, tokens))
    assert asked == ["_sequence_queries"] * 4
    del asked[:]
    prefix, counts, _ = jax.eval_shape(lambda: ds.prefill(params, CONFIG, ctx))
    assert asked == ["_sequence_queries"] * 4
    del asked[:]
    zeros = lambda tree: jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), tree)  # noqa: E731
    prefix = zeros(prefix)
    cache = ds.start_beams(CONFIG, prefix, 1, 20, decoders.tile_beams)
    jax.eval_shape(lambda: ds.step(params, CONFIG, prefix, cache, ds.init_counters(zeros(counts), 20),
                                   jnp.zeros((2,), jnp.int32)))
    assert asked == ["_queries"] * 4


@pytest.mark.parametrize("layer,moe", [(0, False), (1, True)], ids=["mla+dense_ffn", "mla+experts+shared"])
def test_each_layer_kind_against_the_reference(params, weights, layer, moe):
    """One layer of the program (through its whole-sequence, expanded path)
    against the reference's ``layer`` on the same input."""
    one = dataclasses.replace(CONFIG, num_hidden_layers=1, layer_types=("latent_attention",),
                              num_dense_layers=0 if moe else 1)
    name = ds.layer_name(layer)
    lm = {**params["lm"], "layers": {"00": params["lm"]["layers"][name]}}
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(3), (2, 9, CONFIG.hidden_size))).astype(jnp.bfloat16)
    got, state, counts, routes = ds.sequence_forward(lm, one, x)
    p = ref._f32(nest(weights, f"params/decoder/lm/layers/{name}"))
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.layer(p, x.astype(jnp.float32), moe, MODEL)
    _close(got, want, LAYER_TOL)
    assert state.latents[0].shape == (2, 9, WIDTH) and state.latents[0].dtype == jnp.bfloat16
    if moe:
        assert np.array_equal(np.sort(routes, -1), np.sort(np.asarray(chosen), -1))
        assert int(counts.sum()) == 2 * 9 * CONFIG.num_experts_per_tok      # nothing dropped


def test_the_latent_attention_alone_against_the_reference(params, weights):
    m = params["lm"]["layers"]["02"]["self_attn"]
    h = (0.5 * jax.random.normal(jax.random.PRNGKey(4), (2, 11, CONFIG.hidden_size))).astype(jnp.bfloat16)
    got, latents = ds.attend_expanded(m, CONFIG, h)
    with jax.default_matmul_precision("highest"):
        want = ref.mla_mixer(ref._f32(nest(weights, "params/decoder/lm/layers/02/self_attn")),
                             h.astype(jnp.float32), MODEL, "f32")
    _close(got, want, LAYER_TOL)
    assert latents.shape == (2, 11, WIDTH)


def _twin_forms(m, h):
    """Both forms of one layer's attention for the LAST position of
    h [S, H], float32, straight from the equations (no rope: it enters both
    forms alike, as one more slice of the contraction)."""
    c = CONFIG
    nh, rank, nope, rope = c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim
    f = lambda a: np.asarray(a, np.float32)  # noqa: E731
    q = (h[-1] @ f(m["q_proj"])).reshape(nh, nope + rope)
    raw = h @ f(m["kv_a_proj"])
    lat, k_rope = raw[:, :rank], raw[:, rank:]
    w = f(m["kv_b_proj"]).reshape(rank, nh, -1)
    scale = np.float32((nope + rope) ** -0.5)
    soft = lambda s: np.exp(s - s.max(-1, keepdims=True)) / np.exp(s - s.max(-1, keepdims=True)).sum(-1, keepdims=True)  # noqa: E731
    # expanded: keys and values of every position from its latent
    kv = np.einsum("sc,chd->shd", lat, w)
    s_exp = (np.einsum("hd,shd->hs", q[:, :nope], kv[..., :nope]) + q[:, nope:] @ k_rope.T) * scale
    o_exp = np.einsum("hs,shd->hd", soft(s_exp), kv[..., nope:])
    # absorbed: the key half into the query, the value half after the sum
    q_lat = np.einsum("hd,chd->hc", q[:, :nope], w[..., :nope])
    s_abs = (q_lat @ lat.T + q[:, nope:] @ k_rope.T) * scale
    o_abs = np.einsum("hc,chd->hd", soft(s_abs) @ lat, w[..., nope:])
    return o_exp, o_abs


def test_the_absorbed_form_is_the_expanded_form_exactly(params):
    m = params["lm"]["layers"]["01"]["self_attn"]
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (13, CONFIG.hidden_size)), np.float32)
    o_exp, o_abs = _twin_forms(m, h)
    np.testing.assert_allclose(o_abs, o_exp, rtol=0, atol=8 * np.finfo(np.float32).eps * np.abs(o_exp).max())


def test_the_absorbed_step_equals_the_expanded_form_for_one_layer(params):
    """The program's two forms: the last position of a 10-position sequence
    through ``attend_expanded``, against ``attend_absorbed`` over a prefix of
    6 latents (one image, K = 2 rows that hold the same token) and a suffix
    of 3 + the token itself."""
    m = params["lm"]["layers"]["01"]["self_attn"]
    h = (0.5 * jax.random.normal(jax.random.PRNGKey(6), (1, 10, CONFIG.hidden_size))).astype(jnp.bfloat16)
    want, latents = ds.attend_expanded(m, CONFIG, h)
    N, t, T = 6, 3, 5
    suffix = jnp.zeros((2, T, WIDTH), jnp.bfloat16).at[:, :t].set(latents[:, N:N + t])
    got, suffix = ds.attend_absorbed(m, CONFIG, jnp.tile(h[:, -1], (2, 1)), latents[:, :N], suffix, jnp.int32(t))
    _close(got[0], want[0, -1], PATH_TOL)
    assert np.array_equal(got[0], got[1])
    # the token's own latent went into the suffix at t, the one the expanded form made
    assert np.array_equal(np.asarray(suffix[0, t], np.float32), np.asarray(latents[0, -1], np.float32))


def test_prefill_then_20_cached_steps_equal_the_full_forward(params, weights):
    """Logits, not tokens: the N prefix positions once (expanded), then 20
    one-token ABSORBED steps through the latent cache, against (a) the
    program's own full forward, expanded throughout, and (b) the
    reference's full forward with no cache.  The whole stack."""
    ctx, tokens = _inputs()
    B, T = tokens.shape
    N = ctx.shape[1]
    full = ds.teacher_forced(params, CONFIG, ctx, tokens)
    prefix, counts, _ = ds.prefill(params, CONFIG, ctx)
    assert all(x.shape == (B, N, WIDTH) for x in prefix.latents) and len(prefix.latents) == 4
    cache = ds.start_beams(CONFIG, prefix, 1, T, decoders.tile_beams)
    counters = ds.init_counters(counts, T)
    words_in = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), tokens[:, :-1]], axis=1)
    step = jax.jit(lambda c, n, w: ds.step(params, CONFIG, prefix, c, n, w))
    cached = []
    for t in range(T):
        cache, counters, logits = step(cache, counters, words_in[:, t])
        cached.append(logits)
    cached = jnp.stack(cached, axis=1)
    _close(cached, full, PATH_TOL)
    assert int(counters.t) == T and cached.shape == (B, T, 100)
    assert np.asarray(counters.moe_counts).sum(axis=1).tolist() == [B * (N + T) * 3] * 3
    want, _ = ref.forward(lambda pre: _subtree(weights, pre), MODEL, np.asarray(ctx), np.asarray(tokens))
    _close(cached, want, FORWARD_TOL)


def test_a_bias_changes_the_choice_and_never_the_weight(params):
    """``expert_bias`` (the source's ``e_score_correction_bias``) selects and
    does not weigh; the chosen scores are divided by their sum + 1e-20 and
    scaled by 2.448."""
    f = dict(params["lm"]["layers"]["01"]["feed_forward"])
    h = jax.random.normal(jax.random.PRNGKey(4), (32, CONFIG.hidden_size)).astype(jnp.bfloat16)
    f["expert_bias"] = jnp.zeros((8,), jnp.float32)
    plain, _ = lm_common.route(f, CONFIG, h, 1e-20)
    f["expert_bias"] = jnp.zeros((8,), jnp.float32).at[5].set(10.0)
    lifted, w = lm_common.route(f, CONFIG, h, 1e-20)
    assert not np.array_equal(np.sort(plain, -1), np.sort(lifted, -1))
    assert (np.asarray(lifted) == 5).any(axis=-1).all()
    scores = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), f["gate"].astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(lifted), axis=-1)
    np.testing.assert_allclose(np.asarray(w), 2.448 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    chosen, _ = ref.route(ref._f32({"gate": f["gate"], "expert_bias": f["expert_bias"]}),
                          h.astype(jnp.float32), MODEL)
    assert np.array_equal(np.sort(lifted, -1), np.sort(np.asarray(chosen), -1))


def test_the_shared_expert_adds_exactly_its_own_output(params):
    """With and without the ``shared`` leaves the layer differs by S(h), the
    shared SwiGLU of the normed input, and by nothing else: same routes,
    same counts."""
    p = params["lm"]["layers"]["02"]
    bare = {**p, "feed_forward": {k: v for k, v in p["feed_forward"].items() if k != "shared"}}
    # maps x16 (a power of two: bfloat16-exact) so the sums stand clear of the residual's rounding
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(8), (40, CONFIG.hidden_size))).astype(jnp.bfloat16)
    zero = jnp.zeros_like(x)
    with_, sizes, experts, _ = ds._ffn(p, CONFIG, 2, zero + x)
    without, sizes0, experts0, _ = ds._ffn(bare, CONFIG, 2, zero + x)
    assert np.array_equal(sizes, sizes0) and np.array_equal(experts, experts0)
    s = p["feed_forward"]["shared"]
    h = lm_common.rms_norm(x, p["ffn_norm"], CONFIG.norm_eps).astype(jnp.bfloat16)
    shared = lm_common.mm(lm_common.swiglu(lm_common.mm(h, s["w1"]), lm_common.mm(h, s["w3"])), s["w2"])
    diff = with_.astype(jnp.float32) - without.astype(jnp.float32)
    # each side was rounded once to bfloat16 at the residual's scale
    np.testing.assert_allclose(diff, shared.astype(jnp.float32), rtol=0,
                               atol=2 ** -7 * float(jnp.abs(x.astype(jnp.float32)).max()))
    assert float(jnp.abs(shared.astype(jnp.float32)).max()) > 0


def test_uneven_routing_with_an_empty_expert_drops_nothing(params):
    """One expert takes every token (as one of each token's three), one
    takes none: every routed pair is still computed, against the
    reference's dense every-expert-masked form with its shared expert."""
    p = jax.tree_util.tree_map(lambda a: a, params["lm"]["layers"]["03"])
    bias = jnp.zeros((8,), jnp.float32).at[3].set(10.0).at[6].set(-10.0)
    big = {k: p["feed_forward"][k] * 8 for k in ("w1", "w3", "w2")}
    p["feed_forward"] = {**p["feed_forward"], **big, "expert_bias": bias}
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(5), (64, CONFIG.hidden_size))).astype(jnp.bfloat16)
    y, sizes, experts, pairs = lm_common.moe_ffn(p, CONFIG, x, 1e-20)
    assert (int(pairs.held), int(pairs.over)) == (64 * 3, 0)
    sizes = np.asarray(sizes)
    assert sizes[3] == 64 and sizes[6] == 0 and sizes.sum() == 64 * 3
    rp = ref._f32({**p["feed_forward"], "expert_bias": bias})
    h = ref._rms(x.astype(jnp.float32), jnp.asarray(p["ffn_norm"], jnp.float32), 1e-6)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(rp, h, MODEL, "f32")
    _close(y.astype(jnp.float32) - x.astype(jnp.float32), want, LAYER_TOL)


def test_the_untied_head_is_not_the_embedding(params):
    lm = params["lm"]
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(9), (3, CONFIG.hidden_size))).astype(jnp.bfloat16)
    untied = ds._head(lm, CONFIG, x)
    tied = ds._head({k: v for k, v in lm.items() if k != "lm_head"}, CONFIG, x)
    h = lm_common.rms_norm(x, lm["norm"], CONFIG.norm_eps).astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose(untied, h @ lm["lm_head"].astype(jnp.float32), atol=1e-5)
    np.testing.assert_allclose(tied, h @ lm["embed_tokens"].astype(jnp.float32).T, atol=1e-5)
    assert untied.shape == (3, 100) and float(jnp.abs(untied - tied).max()) > 0.05
    # a tied configuration holds no second map at all
    tied_tree = jax.eval_shape(lambda: ds.init_params(
        jax.random.PRNGKey(0), dataclasses.replace(CONFIG, tie_word_embeddings=True)))
    assert "lm_head" not in tied_tree["lm"] and "lm_head" in lm


def test_the_reorder_moves_the_suffix_and_the_record_and_nothing_else():
    """One tree-wide gather: with a permuting parent the per-beam latents
    and the record of routes follow their beam; the counters are untouched;
    the prefix is no part of the state at all."""
    B, K = 2, 3
    rows = jnp.arange(B * K, dtype=jnp.float32)
    leaf = lambda *shape: rows.reshape((B * K,) + (1,) * len(shape)) + jnp.zeros((B * K,) + shape)  # noqa: E731
    cache = ds.LatentCache(latents=(leaf(5, WIDTH), leaf(5, WIDTH)), routes=leaf(30))
    shared = ds.StepCounters(t=jnp.int32(7), moe_counts=jnp.arange(8).reshape(2, 4),
                             step_visits=jnp.arange(10).reshape(2, 5))
    parent = jnp.array([[2, 0, 1], [1, 1, 0]])
    moved = bs._reorder_beams(bs.StepState(cache, shared), B, K, jnp.arange(B)[:, None], parent)
    want = (jnp.arange(B)[:, None] * K + parent).reshape(-1).astype(jnp.float32)
    leaves = jax.tree_util.tree_leaves(moved.beam)
    assert len(leaves) == 3
    for x in leaves:
        assert np.array_equal(np.asarray(x).reshape(B * K, -1)[:, 0], np.asarray(want))
    assert int(moved.shared.t) == 7 and np.array_equal(moved.shared.moe_counts, shared.moe_counts)


def _all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _all_eqns(inner)


def test_the_prefix_stays_latent_and_per_image_in_the_search(params):
    """The beam program at B = 2, K = 3, N = 9, T = 6: the prefix is closed
    over as [2, 9, 40] per layer; no array holds it per beam ([6, 9, ...],
    [2, 3, 9, ...]) or expanded per head ([2, 9, 4 heads, ...]) inside the
    loop, and the state's size is that of the latents."""
    config = dataclasses.replace(CONFIG, image_size=48)            # a 3 x 3 grid: N is no other size here
    ctx = jax.random.normal(jax.random.PRNGKey(1), (2, 9, CONFIG.dim_ctx))
    jaxpr = jax.make_jaxpr(
        lambda p, c: bs.beam_search(p, config, c, 1, beam_size=3, valid_size=100, max_len=6, early_exit=False)
    )(params, ctx)
    loops = [e for e in _all_eqns(jaxpr.jaxpr) if e.primitive.name == "while"]
    assert len(loops) == 1
    body = loops[0].params["body_jaxpr"].jaxpr
    read = {tuple(v.aval.shape) for v in body.invars}
    made = {tuple(v.aval.shape) for e in _all_eqns(body) for v in e.outvars}
    assert (2, 9, WIDTH) in read                    # closed over, as it came from the prefill
    wide = {s for s in made if 9 in s and s[-1] >= 16 and s not in ((2, 9, WIDTH), (2, 9, 32))}
    assert not wide, wide                           # only scores and weights [.., 9] know of N
    out = bs.beam_search_jit(params, config, ctx, 1, beam_size=3, valid_size=100, max_len=6)
    layers, moe, k = 4, 3, 3
    want = layers * (2 * 9 * WIDTH + 6 * 6 * WIDTH) * 2 + 6 * (6 * moe * k) * 4
    assert float(out.decoder_stats["state_bytes"]) == want


def test_the_search_keeps_an_image_s_prefix_per_image_at_a_vocabulary_of_100(params):
    """A vocabulary that is no power of two and no multiple of 128 through
    ``_expand_step``: a one-image batch searched alone and beside another
    image gives the same best caption, every word is under 100, and the
    best caption's score is the sum of its teacher-forced log-probabilities."""
    ctx, _ = _inputs(seed=1)
    both = bs.beam_search_jit(params, CONFIG, ctx, 1, beam_size=3, valid_size=100, max_len=6)
    alone = bs.beam_search_jit(params, CONFIG, ctx[:1], 1, beam_size=3, valid_size=100, max_len=6)
    assert np.array_equal(both.words[0], alone.words[0])
    np.testing.assert_allclose(both.log_scores[0], alone.log_scores[0], atol=1e-4)
    assert both.alphas is None and both.decoder_stats["moe_counts"].shape == (3, 8)
    assert int(both.words.max()) < 100 and int(both.words.min()) >= 0
    assert int(both.decoder_stats["moe_counts"][0].sum()) == 2 * (CONFIG.num_ctx + 3 * 6) * 3
    logits = ds.teacher_forced(params, CONFIG, ctx, both.words[:, 0])
    logp = jax.nn.log_softmax(logits, axis=-1)
    served = jnp.arange(6)[None, :] < both.lengths[:, :1]          # a caption may end before step 6
    total = (jnp.take_along_axis(logp, both.words[:, 0][..., None], axis=-1)[..., 0] * served).sum(axis=1)
    np.testing.assert_allclose(both.log_scores[:, 0], total, atol=5e-3)


def test_the_routes_that_come_back_are_those_of_the_caption_s_own_tokens(params):
    """The record of chosen experts rides the per-beam tree beside the
    latents: per prefix position from the prefill, per step along each live
    beam's ancestry; both equal what the whole-sequence pass chooses over
    ``[prefix; <start>; the caption]``, up to near-ties."""
    ctx, _ = _inputs(seed=2, B=6)
    T, N, k, moe = 8, CONFIG.num_ctx, CONFIG.num_experts_per_tok, 3
    out = bs.beam_search_jit(params, CONFIG, ctx, 1, beam_size=3, valid_size=100, max_len=T,
                             early_exit=False)
    stats = out.decoder_stats
    assert stats["prefix_routes"].shape == (6, N, moe * k) and stats["step_routes"].shape == (6, 3, T, moe * k)
    agree = []
    for b in range(6):
        for beam in range(3):
            words = out.words[b, beam]
            if int(out.lengths[b, beam]) < T or bool((words == 1).any()):
                continue
            x = lm_common.sequence_inputs(params, ctx[b:b + 1], words[None])
            _, _, _, want = ds.sequence_forward(params["lm"], CONFIG, x)
            got = jnp.concatenate([stats["prefix_routes"][b], stats["step_routes"][b, beam]], axis=0)
            sets = lambda r: np.sort(np.asarray(r).reshape(N + T, moe, k), -1)  # noqa: E731
            agree.append((sets(got) == sets(want[0])).all(-1))
    assert len(agree) >= 3
    agree = np.stack(agree)
    assert agree[:, :N].mean() > 0.97 and agree[:, N:].mean() > 0.85, (agree[:, :N].mean(), agree[:, N:].mean())


def test_train_loss_and_connector_gradient_against_the_reference(params, weights):
    ctx, tokens = _inputs(seed=2, T=8)
    masks = (jnp.arange(8)[None, :] < jnp.array([[8], [5]])).astype(jnp.float32)
    batch = {"contexts": ctx, "word_idxs": tokens, "masks": masks}

    def loss_of(connector):
        variables = {"params": {"cnn": {}, "decoder": {**params, "connector": connector}}}
        return compute_loss(variables, CONFIG, batch, rng=jax.random.PRNGKey(0), train=True)

    (loss, aux), grad = jax.value_and_grad(loss_of, has_aux=True)(params["connector"])
    assert aux["attentions"] is None and float(aux["metrics"]["attention_loss"]) == 0.0
    want_loss, want_grad = ref.train_loss(weights, MODEL, np.asarray(ctx), np.asarray(tokens), masks)
    assert abs(float(loss) - float(want_loss)) < 2e-2 * float(want_loss)
    for leaf in ("kernel", "bias"):
        g, w = np.asarray(grad[leaf], np.float64).ravel(), np.asarray(want_grad[leaf], np.float64).ravel()
        assert g @ w / (np.linalg.norm(g) * np.linalg.norm(w)) > 0.995, leaf
        assert abs(np.linalg.norm(g) / np.linalg.norm(w) - 1) < 0.05, leaf


def test_the_stack_is_frozen_as_the_cnn_is(params):
    from sat_tpu.train.step import merge_params, split_trainable

    tree = {"cnn": {"conv": jnp.ones(2)}, "decoder": params}
    trainable, frozen = split_trainable(tree, CONFIG)
    assert set(trainable["decoder"]) == {"connector"} and set(frozen["decoder"]) == {"lm"}
    merged = merge_params(frozen, trainable)
    assert jax.tree_util.tree_structure(merged) == jax.tree_util.tree_structure(tree)
    thawed, _ = split_trainable(tree, dataclasses.replace(CONFIG, train_lm=True))
    assert set(thawed["decoder"]) == {"connector", "lm"}


def test_return_alphas_is_refused_by_the_search_too(params):
    ctx, _ = _inputs()
    with pytest.raises(ValueError, match="return_alphas"):
        bs.beam_search(params, CONFIG, ctx, 1, return_alphas=True)


def test_embedding_and_head_round_trip_the_checkpoint_bit_exactly(tmp_path, params):
    """The whole decoder tree through the npz path: the embedding and the
    untied head are separate bfloat16 leaves and both come back bit for
    bit, as does the float32 ``expert_bias``."""
    from sat_tpu.train.checkpoint import load_flat, restore_checkpoint, save_checkpoint
    from sat_tpu.train.step import TrainState

    config = Config(**{**TOY, "save_dir": str(tmp_path)})
    state = TrainState(params={"decoder": params}, batch_stats={}, opt_state=(), step=jnp.int32(0))
    path = save_checkpoint(state, config)
    flat = load_flat(path)
    for name in ("embed_tokens", "lm_head"):
        got, want = flat[f"params/decoder/lm/{name}"], np.asarray(params["lm"][name])
        assert got.dtype == want.dtype == jnp.bfloat16
        assert np.array_equal(got.view(np.uint16), want.view(np.uint16))
    assert flat["params/decoder/lm/embed_tokens"].shape == flat["params/decoder/lm/lm_head"].shape[::-1]
    restored, count = restore_checkpoint(jax.eval_shape(lambda: state), save_dir=str(tmp_path))
    assert count == len(jax.tree_util.tree_leaves(params))
    bias = restored.params["decoder"]["lm"]["layers"]["01"]["feed_forward"]["expert_bias"]
    assert bias.dtype == jnp.float32
    for got, want in zip(jax.tree_util.tree_leaves(restored.params["decoder"]), jax.tree_util.tree_leaves(params)):
        assert got.dtype == want.dtype and np.array_equal(
            np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8))


def test_the_grouped_product_s_tiles_come_from_its_own_shape():
    """The 768-wide experts' tiles divide their product (every timed width
    is held to its tuples below); a width never timed gets tiles that
    divide it."""
    tiling = lm_common._gmm_tiling
    for pairs in (4608, 301056):
        for k, n in ((2048, 768), (768, 2048)):
            tm, tk, tn = tiling(pairs, k, n)
            assert k % tk == 0 and n % tn == 0 and tm in ((256, 512) if pairs >= 8192 else (128,))
    assert tiling(100, 64, 24) == (128, 64, 24) and tiling(100, 4096, 1536) == (128, 2048, 512)


# (k, n) of a timed product -> (the pairs of its cell's step and prefill, the
# tiles PR 43's tree gave each): PR 44 swept the 1,792-wide experts' alone, PR 47 added the 512-wide
_TILED = {
    (6144, 2048): ((192, 8192), ((128, 2048, 1024), (256, 2048, 1024))),
    (2048, 6144): ((192, 8192), ((128, 2048, 1024), (256, 2048, 1024))),
    (2048, 1792): ((3072, 200704), None),
    (1792, 2048): ((3072, 200704), None),
    (2048, 768): ((4608, 301056), ((128, 2048, 768), (256, 2048, 768))),
    (768, 2048): ((4608, 301056), ((128, 768, 2048), (512, 768, 2048))),
    (4096, 4096): ((96, 36864), ((128, 4096, 512), (256, 2048, 1024))),
    # PR 47: the 512-wide experts (256 held of 512), the rows of a step's 384 tokens and of a prefill pass
    (2048, 512): ((3840, 62720), ((128, 2048, 512), (256, 2048, 512))),
    (512, 2048): ((3840, 62720), ((128, 512, 2048), (256, 512, 2048))),
}


@pytest.mark.parametrize("prefill", [False, True], ids=["step", "prefill"])
@pytest.mark.parametrize("kn", sorted(_TILED), ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_a_timed_width_s_tiles_in_both_regimes(kn, prefill):
    """Every key of the table, at its own cell's pairs.  The five widths
    PR 44 did not sweep return the parent's tuples, letter for letter
    (their cells' programs must not move).  The 1,792-wide experts' tiles
    DIVIDE the product: whole lanes of 128, no ragged output tile
    (1,792 under 1,024 or 512 ran a seventh more lanes than it has), no
    contraction under a wider tile (1,792 under 2,048 masked both operands
    in every visit), and a row tile that leaves the cell's rows unpadded."""
    assert sorted(_TILED) == sorted(lm_common._GMM_TILES)
    (k, n), (pairs, kept) = kn, _TILED[kn]
    tm, tk, tn = lm_common._gmm_tiling(pairs[prefill], k, n)
    if kept is not None:
        assert (tm, tk, tn) == kept[prefill]
        return
    assert tk % 128 == 0 and tn % 128 == 0 and k % tk == 0 and n % tn == 0
    assert tm % 16 == 0 and pairs[prefill] % tm == 0
    assert (tk, tn) == (k, n) or prefill     # a step's tile takes the expert's map whole
