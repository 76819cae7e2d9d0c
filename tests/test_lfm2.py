"""The LFM2-MoE caption decoder (models/lfm2.py) at toy widths on the CPU,
held against the plain float32 reference under benchmark/reference (which
imports nothing of the program), on seeded weights whose values are
bfloat16-representable, so that program and reference hold the same
numbers and differ only in arithmetic.

Tolerances.  The program multiplies bfloat16 by bfloat16 with float32
accumulation and keeps the residual stream in bfloat16: one rounding of a
value is 2**-9 of it (8 bits of mantissa), and a layer's output passes
through three to six such roundings, so a layer is compared at
3e-2 x the output's scale (atol) and a whole 5-layer forward at 6e-2 x
the logits' scale.  Two paths of the PROGRAM that do the same arithmetic
in another order (full forward against prefill + cached steps: the step
sums an attention's prefix and suffix parts in float32 before the one
rounding) differ by where a rounding falls, an ulp or two of bfloat16:
1e-2 x the scale.  The router is float32 at ``HIGHEST`` in both, so its
choices are compared exactly.
"""

import dataclasses
import importlib
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from reference import lfm2_captioner as ref  # noqa: E402
from reference import params_lfm2  # noqa: E402
from reference.params import nest  # noqa: E402

from sat_tpu.config import Config  # noqa: E402
from sat_tpu.models import lfm2, lm_common  # noqa: E402
from sat_tpu.models.captioner import compute_loss  # noqa: E402

bs = importlib.import_module("sat_tpu.ops.beam_search")

TOY = dict(
    decoder="lfm2_moe", cnn="vgg16", image_size=32, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=48, num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4,
    num_key_value_heads=2, num_experts=8, num_experts_per_tok=2, conv_L_cache=3,
    layer_types=("conv", "full_attention", "conv", "conv", "full_attention"),
    vocabulary_size=96, max_caption_length=20, beam_size=3, norm_eps=1e-5, rope_theta=1e6,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1.0,
)
CONFIG = Config(**TOY)
MODEL = {**TOY, "layer_types": list(TOY["layer_types"])}
LAYER_TOL = 3e-2     # x the output's scale: see the module docstring
FORWARD_TOL = 6e-2
PATH_TOL = 1e-2


@pytest.fixture(scope="module")
def weights():
    """Seeded decoder leaves, {path: numpy}, as the benchmark makes them."""
    return params_lfm2.make_weights(MODEL, 7, only=lambda n: n.startswith("params/decoder/"))


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree_util.tree_map(jnp.asarray, nest(weights, "params/decoder"))


def _inputs(seed=0, B=2, T=20):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    N, D = CONFIG.num_ctx, CONFIG.dim_ctx
    # grid values a bfloat16 holds exactly: the connector's product then
    # has the same operands in program and reference
    ctx = jax.random.normal(k1, (B, N, D)).astype(jnp.bfloat16).astype(jnp.float32)
    tokens = jax.random.randint(k2, (B, T), 2, CONFIG.vocabulary_size)
    return ctx, tokens


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * float(np.abs(want).max()))


@pytest.mark.parametrize("layer,kind,moe", [(0, "conv", False), (1, "full_attention", True),
                                            (2, "conv", True)],
                         ids=["conv+dense_ffn", "attention+experts", "conv+experts"])
def test_each_layer_kind_against_the_reference(params, weights, layer, kind, moe):
    """One layer of the program (through its whole-sequence path) against
    the reference's ``layer`` on the same input."""
    one = dataclasses.replace(
        CONFIG, num_hidden_layers=1, layer_types=(kind,), num_dense_layers=0 if moe else 1)
    name = lfm2.layer_name(layer)
    lm = {**params["lm"], "layers": {"00": params["lm"]["layers"][name]}}
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(3), (2, 9, CONFIG.hidden_size))).astype(jnp.bfloat16)
    got, state, counts, routes = lfm2.sequence_forward(lm, one, x)
    p = ref._f32(nest(weights, f"params/decoder/lm/layers/{name}"))
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.layer(p, x.astype(jnp.float32), kind, moe, MODEL)
    _close(got, want, LAYER_TOL)
    if moe:
        assert np.array_equal(np.sort(routes, -1), np.sort(np.asarray(chosen), -1))
        assert int(counts.sum()) == 2 * 9 * CONFIG.num_experts_per_tok      # nothing dropped
    if kind == "conv":
        assert state.conv[0].shape == (2, CONFIG.conv_L_cache, CONFIG.hidden_size)
    else:
        assert state.keys[0].shape == (2, 9, 2 * 16)


def test_prefill_then_20_cached_steps_equal_the_full_forward(params, weights):
    """Logits, not tokens: the N prefix positions once, then 20 one-token
    steps through the cache of two kinds, against (a) the program's own
    full forward, same arithmetic in another order, and (b) the
    reference's full forward with no cache."""
    ctx, tokens = _inputs()
    B, T = tokens.shape
    full = lfm2.teacher_forced(params, CONFIG, ctx, tokens)
    prefix, counts, _ = lfm2.prefill(params, CONFIG, ctx)
    cache = lfm2.init_cache(CONFIG, prefix.conv, B, T)
    counters = lfm2.init_counters(counts, T)
    words_in = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), tokens[:, :-1]], axis=1)
    step = jax.jit(lambda c, n, w: lfm2.step(params, CONFIG, prefix, c, n, w))
    cached = []
    for t in range(T):
        cache, counters, logits = step(cache, counters, words_in[:, t])
        cached.append(logits)
    cached = jnp.stack(cached, axis=1)
    _close(cached, full, PATH_TOL)
    N = ctx.shape[1]
    assert int(counters.t) == T
    # experts that took a token at each step: between 2 (B = 2 rows may agree) and 4
    assert counters.step_visits.shape == (4, T) and bool(((counters.step_visits >= 2) & (counters.step_visits <= 4)).all())
    assert np.asarray(counters.moe_counts).sum(axis=1).tolist() == [B * (N + T) * 2] * 4
    want, _ = ref.forward(lambda pre: _subtree(weights, pre), MODEL, np.asarray(ctx), np.asarray(tokens))
    _close(cached, want, FORWARD_TOL)


def _subtree(weights, prefix):
    path = "params/decoder/" + prefix
    return weights[path] if path in weights else nest(weights, path)


def test_a_bias_changes_the_choice_and_never_the_weight(params):
    """``expert_bias`` selects and does not weigh: with a bias that lifts
    an expert the scores would not choose, that expert is chosen, and its
    weight is its own sigmoid score over the chosen scores' sum."""
    f = dict(params["lm"]["layers"]["01"]["feed_forward"])
    h = jax.random.normal(jax.random.PRNGKey(4), (32, CONFIG.hidden_size)).astype(jnp.bfloat16)
    f["expert_bias"] = jnp.zeros((8,), jnp.float32)
    plain, _ = lfm2._route(f, CONFIG, h)
    f["expert_bias"] = jnp.zeros((8,), jnp.float32).at[5].set(10.0)
    lifted, w = lfm2._route(f, CONFIG, h)
    assert not np.array_equal(np.sort(plain, -1), np.sort(lifted, -1))
    assert (np.asarray(lifted) == 5).any(axis=-1).all()
    scores = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), f["gate"].astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(lifted), axis=-1)
    np.testing.assert_allclose(np.asarray(w), picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    chosen, _ = ref.route(ref._f32({"gate": f["gate"], "expert_bias": f["expert_bias"]}),
                          h.astype(jnp.float32), MODEL)
    assert np.array_equal(np.sort(lifted, -1), np.sort(np.asarray(chosen), -1))


def test_uneven_routing_drops_nothing(params):
    """One expert takes every token (as one of each token's two), one
    takes none: every routed pair is still computed, against the
    reference's dense every-expert-masked form."""
    p = jax.tree_util.tree_map(lambda a: a, params["lm"]["layers"]["02"])
    bias = jnp.zeros((8,), jnp.float32).at[3].set(10.0).at[6].set(-10.0)
    # the experts' maps x8 each (a power of two: still bfloat16-exact), so
    # that at toy widths their sum stands clear of the residual's rounding
    big = {k: p["feed_forward"][k] * 8 for k in ("w1", "w3", "w2")}
    p["feed_forward"] = {**p["feed_forward"], **big, "expert_bias": bias}
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(5), (64, CONFIG.hidden_size))).astype(jnp.bfloat16)
    y, sizes, experts, pairs = lfm2.moe_ffn(p, CONFIG, x)
    assert (int(pairs.held), int(pairs.over), int(pairs.visited)) == (64 * 2, 0, int((np.asarray(sizes) > 0).sum()))
    sizes = np.asarray(sizes)
    assert sizes[3] == 64 and sizes[6] == 0 and sizes.sum() == 64 * 2       # half of all pairs; none
    rp = ref._f32({**p["feed_forward"], "expert_bias": bias})
    h = ref._rms(x.astype(jnp.float32), jnp.asarray(p["ffn_norm"], jnp.float32), 1e-5)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(rp, h, MODEL, "f32")
    _close(y.astype(jnp.float32) - x.astype(jnp.float32), want, LAYER_TOL)


@pytest.mark.parametrize("k,n", [(2048, 1792), (1792, 2048)], ids=["w1_w3", "w2"])
def test_the_grouped_product_at_the_published_expert_widths_against_a_loop(k, n):
    """``grouped_matmul`` off the TPU (``ragged_dot``) at the 1,792-wide
    experts' two shapes against one plain product an expert, on sizes no
    tile divides: an empty expert, one of a single row, the rest uneven."""
    sizes = np.array([0, 1, 17, 5, 40, 9], np.int32)
    rng = np.random.default_rng(k)
    rows = jnp.asarray(rng.standard_normal((int(sizes.sum()), k), np.float32), jnp.bfloat16)
    w = jnp.asarray(0.02 * rng.standard_normal((len(sizes), k, n), np.float32), jnp.bfloat16)
    got = lm_common.grouped_matmul(rows, w, jnp.asarray(sizes))
    assert got.shape == (rows.shape[0], n) and got.dtype == jnp.bfloat16
    ends = np.cumsum(sizes)
    want = np.concatenate([
        np.asarray(rows[end - size:end], np.float32) @ np.asarray(w[e], np.float32)
        for e, (size, end) in enumerate(zip(sizes, ends))
    ])
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=2 ** -7 * np.abs(want).max())


_SWEEP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts", "gmm_tile_sweep.py")


def _tile_sweep(*args):
    return subprocess.run([sys.executable, _SWEEP, *args], capture_output=True, text=True, timeout=420,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_the_tile_sweep_rehearses_on_the_cpu_and_reports_no_device_time(tmp_path):
    """``scripts/gmm_tile_sweep.py`` (the tool that filled the 1,792-wide
    rows of ``_GMM_TILES``) at toy widths: both consumers compile and run
    under two pairs of tiles each, the standing pair first; off the chip a
    run has its wall time and NO ms a call."""
    out = tmp_path / "sweep.json"
    proc = _tile_sweep("--rehearse", "--out", str(out))
    assert proc.returncode == 0, proc.stderr[-3000:]
    results = json.loads(out.read_text())["results"]
    assert [r["regime"] for r in results] == ["prefill", "prefill", "step", "step"]
    standing = lm_common._GMM_TILES
    assert tuple(results[0]["w13"]) == standing[(2048, 1792)][1] and tuple(results[0]["w2"]) == standing[(1792, 2048)][1]
    assert all(r["wall_ms"] > 0 and "ms_a_call" not in r and "module_ms" not in r for r in results)


def test_the_tile_sweep_reads_each_program_s_grouped_products_from_the_trace():
    """A program's ``gmm`` calls are the ops that start inside its module's
    interval, by the width of their output; any other op is left out."""
    from types import SimpleNamespace as ns

    spec = importlib.util.spec_from_file_location("gmm_tile_sweep", _SWEEP)
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    event = lambda name, start, dur: ns(name=name, start_ns=start, duration_ns=dur)  # noqa: E731
    gmm = lambda i, n, start, dur: event(f"%gmm.{i} = bf16[3072,{n}]{{1,0:T(8,128)(2,1)}} custom-call(s32[] %a)", start, dur)  # noqa: E731
    device = ns(name="/device:TPU:0", lines=[
        ns(name="XLA Modules", events=[event("jit_step_00_a(123)", 1000, 1000), event("jit_step_01_b(9)", 3000, 500),
                                        event("jit_step_00_a(123)", 5000, 900)]),
        ns(name="XLA Ops", events=[gmm(1, 1792, 1100, 50), gmm(3, 2048, 1300, 70), gmm(1, 1792, 3100, 40),
                                   event("%fusion.2 = bf16[3072,1792]{1,0} fusion(bf16[] %gmm.1)", 1400, 99),
                                   gmm(1, 1792, 5100, 54), gmm(9, 1792, 9000, 1)]),
    ])
    got = sweep.gmm_ns_by_module([ns(name="/host:CPU", lines=[]), device])
    assert got == {"jit_step_00_a": {"runs": [1000, 900], "calls": {1792: [50, 54], 2048: [70]}},
                   "jit_step_01_b": {"runs": [500], "calls": {1792: [40]}}}
    assert sweep.gmm_ns_by_module([ns(name="/host:CPU", lines=[])]) == {}


def test_the_tile_sweep_gives_no_time_without_a_chip():
    proc = _tile_sweep()
    assert proc.returncode != 0 and "a time comes from the chip" in proc.stderr


def test_the_reorder_moves_conv_and_key_value_state_together_and_nothing_else():
    """One tree-wide gather: with a permuting parent every per-beam leaf
    follows its beam, the shared leaves are untouched, and a plain tree
    (the LSTM's three leaves) is the same gather."""
    B, K = 2, 3
    rows = jnp.arange(B * K, dtype=jnp.float32)
    leaf = lambda *shape: rows.reshape((B * K,) + (1,) * len(shape)) + jnp.zeros((B * K,) + shape)  # noqa: E731
    cache = lfm2.BeamCache(conv=(leaf(3, 4), leaf(3, 4)), keys=(leaf(5, 8),), values=(leaf(5, 8),),
                           routes=leaf(30))
    shared = lfm2.StepCounters(t=jnp.int32(7), moe_counts=jnp.arange(8).reshape(2, 4),
                               step_visits=jnp.arange(10).reshape(2, 5))
    parent = jnp.array([[2, 0, 1], [1, 1, 0]])
    moved = bs._reorder_beams(bs.StepState(cache, shared), B, K, jnp.arange(B)[:, None], parent)
    want = (jnp.arange(B)[:, None] * K + parent).reshape(-1).astype(jnp.float32)
    for x in jax.tree_util.tree_leaves(moved.beam):
        assert np.array_equal(np.asarray(x).reshape(B * K, -1)[:, 0], np.asarray(want))
    assert int(moved.shared.t) == 7 and np.array_equal(moved.shared.moe_counts, shared.moe_counts)
    lstm = bs.DecoderState(memory=leaf(4), output=leaf(4), recurrent=leaf(4))
    for x in bs._reorder_beams(lstm, B, K, jnp.arange(B)[:, None], parent):
        assert np.array_equal(np.asarray(x)[:, 0], np.asarray(want))


def test_the_search_keeps_an_image_s_prefix_per_image(params):
    """Beam search over the two caches returns what a search whose every
    beam carries its OWN copy of the prefix returns: a one-image batch
    run three ways (alone; beside another image; beam 1 = greedy) gives
    the same best caption, and the expert counts come back with it."""
    ctx, _ = _inputs(seed=1)
    both = bs.beam_search_jit(params, CONFIG, ctx, 1, beam_size=3, valid_size=96, max_len=6)
    alone = bs.beam_search_jit(params, CONFIG, ctx[:1], 1, beam_size=3, valid_size=96, max_len=6)
    assert np.array_equal(both.words[0], alone.words[0])
    np.testing.assert_allclose(both.log_scores[0], alone.log_scores[0], atol=1e-4)
    stats = both.decoder_stats
    assert both.alphas is None and stats["moe_counts"].shape == (4, 8)
    # every (row, position) routed: B * (N + K * steps) tokens x 2 experts
    assert int(stats["moe_counts"][0].sum()) == 2 * (CONFIG.num_ctx + 3 * 6) * 2
    # the best caption's score is the sum of the teacher-forced log-probabilities of its tokens
    logits = lfm2.teacher_forced(params, CONFIG, ctx, both.words[:, 0])
    logp = jax.nn.log_softmax(logits, axis=-1)
    total = jnp.take_along_axis(logp, both.words[:, 0][..., None], axis=-1)[..., 0].sum(axis=1)
    np.testing.assert_allclose(both.log_scores[:, 0], total, atol=2e-3)


def test_the_routes_that_come_back_are_those_of_the_caption_s_own_tokens(params):
    """The beam program's record of chosen experts: per prefix position
    from the prefill, and per step along each live beam's ancestry (the
    record is a per-beam leaf, so the search's reorder moves it: the test
    of the reorder above).  Both
    equal what the program's whole-sequence pass chooses over
    ``[prefix; <start>; the caption]``, up to a near-tie that the two
    orders of arithmetic (an ulp of bfloat16 apart) break differently."""
    ctx, _ = _inputs(seed=2, B=3)
    T, N, k = 8, CONFIG.num_ctx, CONFIG.num_experts_per_tok
    out = bs.beam_search_jit(params, CONFIG, ctx, 1, beam_size=3, valid_size=96, max_len=T,
                             early_exit=False)
    stats = out.decoder_stats
    assert stats["prefix_routes"].shape == (3, N, 4 * k) and stats["step_routes"].shape == (3, 3, T, 4 * k)
    lm = params["lm"]
    agree = []
    for b in range(3):
        for beam in range(3):
            words = out.words[b, beam]
            if int(out.lengths[b, beam]) < T or bool((words == 1).any()):
                continue          # a finished caption: not a live beam's
            words_in = jnp.concatenate([jnp.zeros((1,), jnp.int32), words[:-1]])
            x = jnp.concatenate([lfm2._prefix(params, ctx[b:b + 1]), lfm2._embed(lm, words_in)[None]], axis=1)
            _, _, _, want = lfm2.sequence_forward(lm, CONFIG, x)
            got = jnp.concatenate([stats["prefix_routes"][b], stats["step_routes"][b, beam]], axis=0)
            sets = lambda r: np.sort(np.asarray(r).reshape(N + T, 4, k), -1)  # noqa: E731
            agree.append((sets(got) == sets(want[0])).all(-1))
    assert len(agree) >= 3                       # the toy's captions seldom end early
    agree = np.stack(agree)
    assert agree[:, :N].mean() > 0.97 and agree[:, N:].mean() > 0.9, (agree[:, :N].mean(), agree[:, N:].mean())


def test_train_loss_and_connector_gradient_against_the_reference(params, weights):
    """The masked token cross-entropy alone, and its gradient in the
    connector (all that trains), against ``jax.grad`` of the reference.
    The gradient passes back through the whole bfloat16 stack: compared
    by its direction and norm (cosine above 0.995, norm within 5%)."""
    ctx, tokens = _inputs(seed=2, T=8)
    masks = (jnp.arange(8)[None, :] < jnp.array([[8], [5]])).astype(jnp.float32)
    cnn = {}
    batch = {"contexts": ctx, "word_idxs": tokens, "masks": masks}

    def loss_of(connector):
        variables = {"params": {"cnn": cnn, "decoder": {**params, "connector": connector}}}
        total, aux = compute_loss(variables, CONFIG, batch, rng=jax.random.PRNGKey(0), train=True)
        return total, aux

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
    assert "cnn" in frozen and "cnn" not in trainable
    merged = merge_params(frozen, trainable)
    assert jax.tree_util.tree_structure(merged) == jax.tree_util.tree_structure(tree)
    thawed, _ = split_trainable(tree, dataclasses.replace(CONFIG, train_lm=True))
    assert set(thawed["decoder"]) == {"connector", "lm"}


@pytest.mark.parametrize("kw,said", [
    (dict(phase="serve"), "phase='serve'"), (dict(phase="bulk"), "phase='bulk'"),
    (dict(phase="route"), "phase='route'"), (dict(mesh_shape=(2, 1)), "mesh_shape"),
    (dict(context_parallel=2), "context_parallel"), (dict(save_attention_maps=True), "return_alphas"),
    (dict(layer_types=("conv",)), "layer_types"),
])
@pytest.mark.parametrize("decoder", ["lfm2_moe", "deepseek_v3"])
def test_what_this_decoder_cannot_run_is_refused_by_name(decoder, kw, said):
    """Both language-model decoders, one rule: each case names what it
    refuses and the decoder it refuses it for."""
    toy = TOY if decoder == "lfm2_moe" else {
        **TOY, "decoder": decoder, "layer_types": ("latent_attention",) * 5, "tie_word_embeddings": False}
    Config(**toy)                                  # the toy itself is accepted
    with pytest.raises(ValueError, match=said) as refusal:
        Config(**{**toy, **kw})
    assert said == "layer_types" or f"decoder={decoder!r}" in str(refusal.value)


def test_lfm2_s_head_is_its_embedding_and_a_config_that_unties_it_is_refused():
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        Config(**{**TOY, "tie_word_embeddings": False})


def test_return_alphas_is_refused_by_the_search_too(params):
    ctx, _ = _inputs()
    with pytest.raises(ValueError, match="return_alphas"):
        bs.beam_search(params, CONFIG, ctx, 1, return_alphas=True)


def test_bfloat16_leaves_round_trip_the_checkpoint_bit_exactly(tmp_path, params):
    """numpy's format has no bfloat16: the leaves are stored by a uint16
    view with a dtype note in the sidecar, and restored by the view back:
    every bit pattern of a bfloat16, NaNs' payloads included."""
    from sat_tpu.resilience import lineage
    from sat_tpu.train.checkpoint import load_flat, restore_checkpoint, save_checkpoint
    from sat_tpu.train.step import TrainState

    every = np.arange(65536, dtype=np.uint16).view(jnp.bfloat16)
    tree = {"decoder": {"lm": {"every": jnp.asarray(every), "bias": jnp.arange(3.0)}}}
    config = Config(**{**TOY, "save_dir": str(tmp_path)})
    state = TrainState(params=tree, batch_stats={}, opt_state=(), step=jnp.int32(4))
    path = save_checkpoint(state, config)
    with np.load(path) as z:
        assert z["params/decoder/lm/every"].dtype == np.uint16
    assert lineage.read_sidecar_meta(path)["dtypes"] == {"params/decoder/lm/every": "bfloat16"}
    assert lineage.verify_checkpoint(path)[0]
    flat = load_flat(path)
    assert flat["params/decoder/lm/every"].dtype == every.dtype
    assert np.array_equal(flat["params/decoder/lm/every"].view(np.uint16), every.view(np.uint16))
    skeleton = jax.eval_shape(lambda: state)
    restored, count = restore_checkpoint(skeleton, save_dir=str(tmp_path))
    assert count == 2 and int(restored.step) == 4
    got = np.asarray(restored.params["decoder"]["lm"]["every"])
    assert got.dtype == every.dtype and np.array_equal(got.view(np.uint16), every.view(np.uint16))
    os.remove(lineage.sidecar_path(path))              # the note lost: refuse, never cast
    with pytest.raises(ValueError, match="dtype note"):
        restore_checkpoint(skeleton, model_file=path)
