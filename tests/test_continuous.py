"""Continuous step-level batching tests (docs/SERVING.md).

Pins the stepped-decode contracts the continuous-batching ISSUE promises:

* BITWISE parity — the stepped slot-pool decode (staggered admission,
  early retirement, slot reuse) produces `BeamResult`s identical to the
  monolithic `beam_search` per request: words, log_scores and lengths,
  including the early-exit and valid_size paths; the attention maps to
  one float32 ulp (`_assert_alphas_match` says why not bitwise).  Both
  drivers run the same `_expand_step` body, and these tests prove the
  carry freeze preserves equality end to end;
* `return_steps` plumbing through `beam_search_jit` / `greedy_decode`;
* `PagedSlotPool` bookkeeping: capacity, page-local seeding, harvest
  frees slots, reset empties the pool;
* `ContinuousBatcher` flow control: inter-step admission beyond pool
  capacity, 504 deadline triage, drain-to-completion then 503;
* `BucketOverflow` → 429 with a Retry-After hint (batch mode), and the
  429 surface carrying the header end-to-end;
* the HTTP surface in `--serve_mode continuous`: caption parity vs the
  monolithic oracle, ZERO XLA compiles during the request phase, /stats
  decode-step percentiles + slot-pool occupancy, /metrics gauges;
* wedge containment: an injected stuck decode step fails in-flight
  slots with fast 500s, the pool re-warms, health recovers.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from sat_tpu.models.decoder import init_decoder_params

# the ops package re-exports the beam_search FUNCTION, which shadows the
# submodule on every attribute-style import — load the module directly
bs = importlib.import_module("sat_tpu.ops.beam_search")
from sat_tpu.serve.batcher import ContinuousBatcher, MicroBatcher, Rejected
from sat_tpu.serve.engine import BucketOverflow
from sat_tpu.serve.server import CaptionServer
from sat_tpu.serve.slot_pool import PagedSlotPool

from tests.test_beam_search import EOS, tiny_config
from tests.test_serve import (  # noqa: F401  (fixture re-export)
    _fixture_files,
    _get,
    _post,
    _zero_image,
    served,
)


# ---------------------------------------------------------------------------
# Stepped-decode parity at the ops layer (no engine, tiny params)
# ---------------------------------------------------------------------------


def _ops_setup(B=5, seed=0, **kw):
    cfg = tiny_config(**kw)
    params = init_decoder_params(jax.random.PRNGKey(seed), cfg)
    contexts = jnp.asarray(
        np.random.default_rng(seed).normal(
            size=(B, cfg.num_ctx, cfg.dim_ctx)
        ),
        jnp.float32,
    )
    return cfg, params, contexts


def _stepped_decode_all(
    cfg, params, contexts, pages, width, *,
    return_alphas=False, valid_size=None, admit_every=1, k=1,
):
    """Run every request through a pages×width slot pool with staggered
    admission (one new request every ``admit_every`` ticks while slots
    are free), harvesting/retiring the tick each slot finishes.  ``k=1``
    drives the pool with single ``decode_step`` dispatches (the fused
    path's correctness baseline); ``k>1`` runs one fused
    ``decode_multi_step`` window per tick, admissions landing only
    between windows.  Returns per-request host BeamResults in
    submission order."""
    B = contexts.shape[0]
    S = pages * width
    seed = jax.jit(bs.init_slots, static_argnames=("config", "beam_size"))
    step = jax.jit(
        bs.decode_step,
        static_argnames=("config", "eos_id", "beam_size", "valid_size"),
    )
    multi = jax.jit(
        bs.decode_multi_step,
        static_argnames=("config", "eos_id", "beam_size", "valid_size"),
    )
    harv = jax.jit(bs.harvest_slots, static_argnames=("return_alphas",))
    ret = jax.jit(bs.retire_slots)

    carry = bs.init_slot_pool(
        cfg, slots=S, return_alphas=return_alphas
    )
    free = list(range(S))
    binding = {}  # slot -> request index
    results = {}
    next_req = 0
    ticks = 0
    while len(results) < B:
        # staggered admission: at most one page seeding per loop, only
        # on admit_every ticks — requests land mid-decode of others
        if free and next_req < B and ticks % admit_every == 0:
            s = free.pop(0)
            lane_ctx = contexts[next_req][None]        # 1-wide lane
            slot_src = np.zeros((S,), np.int32)
            admit = np.zeros((S,), np.bool_)
            admit[s] = True
            carry = seed(
                params, cfg, carry, lane_ctx,
                jnp.asarray(slot_src), jnp.asarray(admit),
            )
            binding[s] = next_req
            next_req += 1
        ticks += 1
        mask = np.zeros((S,), np.bool_)
        for s in binding:
            mask[s] = True
        if k == 1:
            carry, done = step(
                params, cfg, carry, jnp.asarray(mask), EOS,
                valid_size=valid_size,
            )
        else:
            carry, done, steps_run = multi(
                params, cfg, carry, jnp.asarray(mask), EOS,
                jnp.int32(k), valid_size=valid_size,
            )
            assert int(np.asarray(steps_run)) <= k
        done = np.asarray(done)
        if done.any():
            out = harv(carry, return_alphas=return_alphas)
            retire = np.zeros((S,), np.bool_)
            for s in np.nonzero(done)[0]:
                s = int(s)
                if s not in binding:
                    continue
                r = binding.pop(s)
                results[r] = bs.BeamResult(
                    words=np.asarray(out.words)[s],
                    log_scores=np.asarray(out.log_scores)[s],
                    lengths=np.asarray(out.lengths)[s],
                    alphas=(
                        None if out.alphas is None
                        else np.asarray(out.alphas)[s]
                    ),
                    steps_run=np.asarray(out.steps_run)[s],
                )
                retire[s] = True
                free.append(s)
            carry = ret(carry, jnp.asarray(retire))
        assert ticks < 10 * B * cfg.max_caption_length, "pool livelock"
    return [results[r] for r in range(B)]


# Attention maps of two PROGRAMS OF DIFFERENT SHAPES are not bitwise equal
# on the CPU backend, and the pool is not the cause: the monolithic search
# at B=1 differs from itself at B=5 in the same way (1 float32 ulp,
# 4.66e-10 at alpha ~ 1/196, in <= 21 of 17,640 elements).  The optimized
# HLO of the two is the same but for its shapes (one exp, one divide over
# [B, K, 196] in decoder/attend), and `attend_with_precomputed` jitted
# alone is bitwise at every shape and layout ([B, K] beams per grid or the
# pool's [S*K, 1]): what differs is the code XLA:CPU emits for those loops
# inside the whole step program.  An ulp in one of 196 weights is below
# the rounding of the context it sums to, so logits, words, scores and
# lengths are bitwise, and are held so below; programs of EQUAL shapes
# (stepped against the fused window) stay bitwise in the maps too.  The
# bound is set from the dtype, four times what was measured.
ALPHA_RTOL = 4 * float(np.finfo(np.float32).eps)


def _assert_alphas_match(mono_alphas, pool_alphas, where):
    np.testing.assert_allclose(
        pool_alphas, mono_alphas, rtol=ALPHA_RTOL, atol=0.0,
        err_msg=str(where),
    )


@pytest.mark.parametrize("valid_size", [None, 25])
def test_stepped_parity_staggered_admission(valid_size):
    """5 requests through a 2x2 pool, admitted one per step: words,
    scores and lengths bitwise-equal to the monolithic search, alphas to
    an ulp, with early finishers retiring (and their slots reseeding)
    mid-run."""
    cfg, params, contexts = _ops_setup(B=5)
    mono = bs.beam_search(
        params, cfg, contexts, EOS,
        return_alphas=True, valid_size=valid_size,
    )
    stepped = _stepped_decode_all(
        cfg, params, contexts, pages=2, width=2,
        return_alphas=True, valid_size=valid_size,
    )
    for i, got in enumerate(stepped):
        assert np.array_equal(np.asarray(mono.words)[i], got.words), i
        assert np.array_equal(
            np.asarray(mono.log_scores)[i], got.log_scores
        ), i
        assert np.array_equal(np.asarray(mono.lengths)[i], got.lengths), i
        _assert_alphas_match(np.asarray(mono.alphas)[i], got.alphas, i)


def test_stepped_parity_bursty_admission_and_single_slot():
    """Degenerate geometries: a 1-wide pool (fully serial reuse) and
    bursty admission every 3 steps still match the oracle bitwise."""
    cfg, params, contexts = _ops_setup(B=3, seed=7)
    mono = bs.beam_search(params, cfg, contexts, EOS)
    for pages, width, every in ((1, 1, 1), (1, 2, 3)):
        stepped = _stepped_decode_all(
            cfg, params, contexts, pages=pages, width=width,
            admit_every=every,
        )
        for i, got in enumerate(stepped):
            assert np.array_equal(
                np.asarray(mono.words)[i], got.words
            ), (pages, width, i)
            assert np.array_equal(
                np.asarray(mono.log_scores)[i], got.log_scores
            ), (pages, width, i)


def test_stepped_per_slot_steps_reflect_early_exit():
    """harvest_slots reports per-slot step counts: an early-sealing
    request runs fewer steps than max_caption_length."""
    cfg, params, contexts = _ops_setup(B=4)
    stepped = _stepped_decode_all(cfg, params, contexts, pages=2, width=2)
    steps = [int(r.steps_run) for r in stepped]
    assert all(1 <= s <= cfg.max_caption_length for s in steps)
    mono = bs.beam_search_jit(
        params, cfg, contexts, EOS,
        beam_size=cfg.beam_size, return_steps=True,
    )
    # the pool runs each slot exactly as long as the monolithic whole-
    # batch early exit would have run its slowest member
    assert max(steps) == int(np.asarray(mono.steps_run))


# ---------------------------------------------------------------------------
# Fused decode window (decode_multi_step) — ISSUE 16
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [2, 4, 8])
def test_fused_window_bitwise_parity_staggered(k):
    """K fused steps per dispatch vs K=1 stepped decode under staggered
    admission: words, scores, lengths, alphas AND per-slot step counts
    bitwise-equal — the fused while_loop body IS decode_step, and a slot
    frozen mid-window stays frozen exactly as it would between host
    dispatches."""
    cfg, params, contexts = _ops_setup(B=5)
    base = _stepped_decode_all(
        cfg, params, contexts, pages=2, width=2, return_alphas=True, k=1,
    )
    fused = _stepped_decode_all(
        cfg, params, contexts, pages=2, width=2, return_alphas=True, k=k,
    )
    for i, (want, got) in enumerate(zip(base, fused)):
        assert np.array_equal(want.words, got.words), (k, i)
        assert np.array_equal(want.log_scores, got.log_scores), (k, i)
        assert np.array_equal(want.lengths, got.lengths), (k, i)
        assert np.array_equal(want.alphas, got.alphas), (k, i)
        assert int(want.steps_run) == int(got.steps_run), (k, i)
    # and both match the monolithic oracle (transitivity made explicit)
    mono = bs.beam_search(params, cfg, contexts, EOS, return_alphas=True)
    for i, got in enumerate(fused):
        assert np.array_equal(np.asarray(mono.words)[i], got.words), (k, i)
        assert np.array_equal(
            np.asarray(mono.log_scores)[i], got.log_scores
        ), (k, i)
        assert np.array_equal(
            np.asarray(mono.lengths)[i], got.lengths
        ), (k, i)
        _assert_alphas_match(np.asarray(mono.alphas)[i], got.alphas, (k, i))


@pytest.mark.parametrize("valid_size", [None, 25])
def test_fused_window_bitwise_parity_bursty(valid_size):
    """Bursty admission (every 3 ticks) through degenerate geometries
    with a deep window: still bitwise vs the K=1 baseline, valid_size
    masking included."""
    cfg, params, contexts = _ops_setup(B=3, seed=7)
    for pages, width in ((1, 1), (1, 2)):
        base = _stepped_decode_all(
            cfg, params, contexts, pages=pages, width=width,
            admit_every=3, valid_size=valid_size, k=1,
        )
        fused = _stepped_decode_all(
            cfg, params, contexts, pages=pages, width=width,
            admit_every=3, valid_size=valid_size, k=4,
        )
        for i, (want, got) in enumerate(zip(base, fused)):
            assert np.array_equal(want.words, got.words), (pages, width, i)
            assert np.array_equal(
                want.log_scores, got.log_scores
            ), (pages, width, i)
            assert int(want.steps_run) == int(got.steps_run), (
                pages, width, i,
            )


def test_fused_window_on_device_early_exit():
    """A pool that seals mid-window stops iterating ON DEVICE: steps_run
    comes back < k, and a fully inactive pool runs zero steps."""
    cfg, params, contexts = _ops_setup(B=1)
    mono = bs.beam_search_jit(
        params, cfg, contexts, EOS,
        beam_size=cfg.beam_size, return_steps=True,
    )
    n = int(np.asarray(mono.steps_run))
    S = 2
    carry = bs.init_slot_pool(cfg, slots=S)
    slot_src = np.zeros((S,), np.int32)
    admit = np.zeros((S,), np.bool_)
    admit[0] = True
    carry = bs.init_slots(
        params, cfg, carry, contexts[0][None],
        jnp.asarray(slot_src), jnp.asarray(admit),
    )
    mask = np.zeros((S,), np.bool_)
    mask[0] = True
    carry, done, steps_run = bs.decode_multi_step(
        params, cfg, carry, jnp.asarray(mask), EOS, k=n + 4,
    )
    # the slot seals after exactly its monolithic step count and the
    # while_loop exits the moment nothing is active — never burning the
    # remaining window
    assert int(np.asarray(steps_run)) == n < n + 4
    done = np.asarray(done)
    assert done[0] and not done[1]
    # drained pool (the harvested slot's mask dropped): zero iterations
    carry, done2, steps2 = bs.decode_multi_step(
        params, cfg, carry, jnp.zeros((S,), jnp.bool_), EOS, k=4,
    )
    assert int(np.asarray(steps2)) == 0
    assert not np.asarray(done2).any()


def test_adaptive_k_policy_units():
    """Queue pressure forces the shallow lane; an idle queue runs deep."""
    from sat_tpu.serve.batcher import choose_decode_depth

    depths = (1, 2, 4, 8)
    assert choose_decode_depth(depths, 0, 0) == 8    # idle -> deepest
    assert choose_decode_depth(depths, 1, 0) == 1    # queued burst
    assert choose_decode_depth(depths, 7, 3) == 1    # both
    assert choose_decode_depth(depths, 0, 2) == 1    # held pending
    assert choose_decode_depth((1,), 0, 0) == 1      # ladder of one
    assert choose_decode_depth((1, 4), 0, 0) == 4


def test_serve_decode_depth_config_validation():
    cfg = tiny_config()
    assert cfg.serve_decode_depth == (1, 2, 4, 8)
    # list arrivals normalize to a hashable tuple (jit static arg rule)
    assert cfg.replace(
        serve_decode_depth=[1, 3]
    ).serve_decode_depth == (1, 3)
    for bad in ((), (2, 4), (1, 4, 2), (1, 1, 2), (1, 0)):
        with pytest.raises(ValueError):
            cfg.replace(serve_decode_depth=bad)
    # JSON round-trip restores the tuple
    from sat_tpu.config import Config

    assert Config.from_dict(
        {"serve_decode_depth": [1, 2]}
    ).serve_decode_depth == (1, 2)


@pytest.mark.parametrize(
    "pages,width,every", [(2, 2, 1), (1, 2, 3), (1, 1, 1)]
)
def test_stepped_pallas_vs_xla_slot_pool_parity(monkeypatch, pages, width, every):
    """Fused-kernel decode at slot-pool geometry vs the XLA combine.

    The pool batches dead slots alongside live ones (inactive-slot masks,
    staggered admission, mid-pool retirement with slot reuse) — exactly
    the geometry the row-masked kernel exists for.  The interpret-mode
    kernel must produce the SAME captions as the XLA attend across every
    geometry, and scores must agree to kernel-numerics tolerance."""
    from sat_tpu.ops import pallas_attention

    cfg, params, contexts = _ops_setup(
        B=5, use_pallas_attention=True, num_attend_layers=2
    )
    xla = _stepped_decode_all(
        cfg.replace(use_pallas_attention=False), params, contexts,
        pages=pages, width=width, admit_every=every,
    )
    monkeypatch.setattr(pallas_attention, "FORCE_INTERPRET", True)
    fused = _stepped_decode_all(
        cfg, params, contexts, pages=pages, width=width, admit_every=every,
    )
    for i, (want, got) in enumerate(zip(xla, fused)):
        assert np.array_equal(want.words, got.words), (pages, width, i)
        np.testing.assert_allclose(
            got.log_scores, want.log_scores, rtol=1e-4, atol=1e-5,
            err_msg=str((pages, width, i)),
        )


def test_stepped_pallas_matches_monolithic_pallas(monkeypatch):
    """With the kernel forced on BOTH paths, the stepped slot-pool decode
    still matches the monolithic search caption-for-caption — the row
    mask changes nothing for live rows."""
    from sat_tpu.ops import pallas_attention

    cfg, params, contexts = _ops_setup(
        B=4, seed=3, use_pallas_attention=True, num_attend_layers=2
    )
    monkeypatch.setattr(pallas_attention, "FORCE_INTERPRET", True)
    mono = bs.beam_search(params, cfg, contexts, EOS)
    stepped = _stepped_decode_all(cfg, params, contexts, pages=2, width=2)
    for i, got in enumerate(stepped):
        assert np.array_equal(np.asarray(mono.words)[i], got.words), i
        np.testing.assert_allclose(
            got.log_scores, np.asarray(mono.log_scores)[i],
            rtol=1e-5, atol=1e-6, err_msg=str(i),
        )


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="compiled (non-interpret) Pallas kernel needs a real TPU",
)
def test_stepped_pallas_vs_xla_on_tpu():
    """Same parity assertion with the Mosaic-compiled kernel on a real
    chip (the serve-path configuration: --serve_mode continuous runs this
    kernel every decode step)."""
    cfg, params, contexts = _ops_setup(
        B=5, use_pallas_attention=True, num_attend_layers=2
    )
    xla = _stepped_decode_all(
        cfg.replace(use_pallas_attention=False), params, contexts,
        pages=2, width=2,
    )
    fused = _stepped_decode_all(cfg, params, contexts, pages=2, width=2)
    for i, (want, got) in enumerate(zip(xla, fused)):
        assert np.array_equal(want.words, got.words), i
        np.testing.assert_allclose(
            got.log_scores, want.log_scores, rtol=1e-4, atol=1e-5,
        )


def test_return_steps_plumbing():
    """return_steps rides beam_search_jit and greedy_decode without
    perturbing results; off by default (None)."""
    cfg, params, contexts = _ops_setup(B=3)
    base = bs.beam_search_jit(
        params, cfg, contexts, EOS, beam_size=cfg.beam_size
    )
    assert base.steps_run is None
    counted = bs.beam_search_jit(
        params, cfg, contexts, EOS,
        beam_size=cfg.beam_size, return_steps=True,
    )
    n = int(np.asarray(counted.steps_run))
    assert 1 <= n <= cfg.max_caption_length
    assert np.array_equal(
        np.asarray(base.words), np.asarray(counted.words)
    )
    assert np.array_equal(
        np.asarray(base.log_scores), np.asarray(counted.log_scores)
    )
    g0 = bs.greedy_decode(params, cfg, contexts, EOS)
    g1 = bs.greedy_decode(params, cfg, contexts, EOS, return_steps=True)
    assert g0.steps_run is None and g1.steps_run is not None
    assert np.array_equal(np.asarray(g0.words), np.asarray(g1.words))


def test_bucket_overflow_carries_hint_fields():
    class _E:  # minimal stand-in; pick_bucket only needs .buckets
        buckets = (1, 4)
    from sat_tpu.serve.engine import ServeEngine
    with pytest.raises(BucketOverflow) as exc:
        ServeEngine.pick_bucket(_E(), 9)
    assert exc.value.n == 9 and exc.value.largest == 4
    assert isinstance(exc.value, ValueError)  # old callers still catch


# ---------------------------------------------------------------------------
# Slot pool + continuous batcher over a real engine
# ---------------------------------------------------------------------------


def _make_pool(served, pages=1, page_width=2):
    pool = PagedSlotPool(
        served["engine"], pages=pages, page_width=page_width,
        tel=served["tel"],
    )
    pool.warmup()
    return pool


def test_slot_pool_bookkeeping_and_zero_recompile_reuse(served):
    engine, tel = served["engine"], served["tel"]
    pool = _make_pool(served, pages=2, page_width=2)
    assert pool.slots == 4 and pool.free_count() == 4
    img = _zero_image(engine)
    n = pool.admit([(img, f"r{i}") for i in range(6)])
    assert n == 4  # surplus stays with the caller
    assert pool.occupancy() == 4 and pool.free_count() == 0
    assert pool.inflight_payloads() == ["r0", "r1", "r2", "r3"]
    compiles0 = tel.counters().get("jax/compiles", 0)
    for _ in range(engine.config.max_caption_length):
        done = np.asarray(pool.step())  # sync-ok: test drain
        if done.any():
            payloads, words, lengths, scores, steps, _alphas = pool.harvest(done)
            assert words.shape[0] == len(payloads)
            assert steps.shape == (len(payloads),)
    assert pool.occupancy() == 0 and pool.free_count() == 4
    # identical zero images: every slot sealed the same step, one harvest
    # reseeding + stepping reuse the warmed executables — nothing compiled
    assert pool.admit([(img, "again")]) == 1
    np.asarray(pool.step())  # sync-ok: test drain
    assert tel.counters().get("jax/compiles", 0) == compiles0
    pool.reset()
    assert pool.occupancy() == 0 and pool.inflight_payloads() == []


def test_multi_step_all_lanes_zero_recompile(served):
    """Every ladder depth steps the pool without a single XLA compile
    (the depth is a runtime operand of ONE warmed executable), and an
    off-ladder depth raises instead of silently widening the policy."""
    engine, tel = served["engine"], served["tel"]
    pool = _make_pool(served, pages=1, page_width=2)
    assert pool.decode_depths == (1, 2, 4, 8)
    img = _zero_image(engine)
    compiles0 = tel.counters().get("jax/compiles", 0)
    for k in pool.decode_depths:
        assert pool.admit([(img, f"lane{k}")]) == 1
        guard = 0
        while pool.occupancy():
            done, steps_dev = pool.multi_step(k)
            done = np.asarray(done)  # sync-ok: test drain
            steps = int(np.asarray(steps_dev))  # sync-ok: test drain
            assert 1 <= steps <= k
            if done.any():
                pool.harvest(done)
            guard += 1
            assert guard <= 2 * engine.config.max_caption_length
    assert tel.counters().get("jax/compiles", 0) == compiles0
    with pytest.raises(KeyError):
        pool.multi_step(3)
    # the lifecycle clone shares the fused executable (zero compiles there)
    clone = pool.clone_warmed("canary")
    assert clone._multi_exec is pool._multi_exec
    assert tel.counters().get("jax/compiles", 0) == compiles0


def test_continuous_batcher_admits_beyond_capacity_and_drains(served):
    """5 requests into a 2-slot pool: inter-step admission cycles them
    all through; drain completes everything then rejects 503."""
    engine = served["engine"]
    b = ContinuousBatcher(
        engine, pool=_make_pool(served, pages=1, page_width=2),
        queue_depth=8, tel=served["tel"],
    )
    img = _zero_image(engine)
    reqs = [b.submit(img) for _ in range(5)]
    b.start()
    b.drain()
    for r in reqs:
        assert r.done.is_set()
        assert r.error is None and r.result is not None
        assert r.bucket == 2  # the page width is the dispatch "bucket"
        assert r.result["captions"]
    with pytest.raises(Rejected) as exc:
        b.submit(img)
    assert exc.value.status == 503
    assert served["tel"].counters().get("serve/admitted", 0) >= 5


def test_continuous_expired_deadline_fails_fast_504(served):
    engine = served["engine"]
    b = ContinuousBatcher(
        engine, pool=_make_pool(served, pages=1, page_width=2),
        queue_depth=8, tel=served["tel"],
    )
    img = _zero_image(engine)
    expired = b.submit(img, deadline_unix=time.time() - 1.0)
    live = b.submit(img)
    b.start()
    try:
        assert expired.done.wait(timeout=10.0)
        assert live.done.wait(timeout=60.0)
        assert expired.error is not None and expired.error[0] == 504
        assert live.error is None and live.result is not None
    finally:
        b.drain()


def test_micro_batcher_maps_bucket_overflow_to_429(served):
    """A batch the warmed ladder can't hold sheds 429 (backpressure),
    not 500 — constructed directly with max_batch past the ladder."""
    engine = served["engine"]
    b = MicroBatcher(
        engine, max_batch=8, max_wait_ms=5.0, queue_depth=16,
        tel=served["tel"],
    )
    img = _zero_image(engine)
    reqs = [b.submit(img) for _ in range(6)]  # > buckets[-1] == 4
    b.start()
    try:
        for r in reqs:
            assert r.done.wait(timeout=30.0)
        statuses = {r.error[0] for r in reqs if r.error is not None}
        assert statuses == {429}
        assert all("exceeds the largest warmed bucket" in r.error[1]
                   for r in reqs)
    finally:
        b.drain()


# ---------------------------------------------------------------------------
# HTTP end-to-end in --serve_mode continuous
# ---------------------------------------------------------------------------


def _continuous_config(served, **kw):
    base = dict(
        serve_mode="continuous", serve_slot_pages=2, serve_page_width=2,
    )
    base.update(kw)
    return served["config"].replace(**base)


def _post_raw(port, data, timeout=60):
    """Like _post but also returns response headers (Retry-After)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/caption",
        data=data,
        method="POST",
        headers={"Content-Type": "image/jpeg"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_e2e_continuous_parity_stats_zero_recompiles(served):
    config = _continuous_config(served)
    engine, tel = served["engine"], served["tel"]
    server = CaptionServer(config, engine, port=0).start()
    try:
        port = server.port
        image_file = _fixture_files(served, 1)[0]
        jpeg = open(image_file, "rb").read()

        # oracle: the monolithic warmed path on the same image
        img = engine.loader.load_image(image_file)
        oracle = engine.decode_output(
            engine.dispatch(engine.pad_batch([img])[0]), 1
        )[0]

        compiles0 = tel.counters().get("jax/compiles", 0)

        status, payload, _ = _post_raw(port, jpeg)
        assert status == 200
        assert payload["captions"] == oracle["captions"]  # bitwise detok
        assert payload["bucket"] == 2  # page width, not a batch bucket

        # a burst past pool capacity (4 slots): everything completes via
        # inter-step admission, all identical to the oracle
        results = [None] * 7
        barrier = threading.Barrier(7)

        def client(i):
            barrier.wait()
            results[i] = _post_raw(port, jpeg)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(7)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert all(r is not None for r in results)
        assert all(s == 200 for s, _, _ in results)
        assert all(
            p["captions"][0]["caption"]
            == oracle["captions"][0]["caption"]
            for _, p, _ in results
        )

        # THE guarantee, extended to the stepped path: zero XLA compiles
        # in the request phase (admission, stepping, harvest, reseed)
        assert tel.counters().get("jax/compiles", 0) == compiles0

        status, stats = _get(port, "/stats")
        assert status == 200
        assert stats["serve_mode"] == "continuous"
        assert stats["slot_pool"] == {
            "slots": 4, "pages": 2, "page_width": 2, "busy": 0,
        }
        assert stats["compiles_since_ready"] == 0
        steps = stats["decode_steps"]
        assert steps["count"] >= 8
        assert 1 <= steps["p50"] <= steps["p95"]
        assert steps["p95"] <= config.max_caption_length
        assert "serve/step" in stats["latency_ms"]
        assert stats["counters"].get("serve/admitted", 0) >= 8

        # fused decode window observability: device steps per dispatch
        # in the engine block, bounded by the warmed ladder
        spd = stats["engine"]["steps_per_dispatch"]
        assert 1 <= spd["p50"] <= spd["p95"]
        assert spd["p95"] <= max(config.serve_decode_depth)
        assert stats["counters"].get("serve/dispatches", 0) >= 1
        # dispatch amortization: total steps never exceed dispatches x
        # the deepest lane, and the fused window actually engaged
        assert stats["counters"]["serve/steps"] <= (
            stats["counters"]["serve/dispatches"]
            * max(config.serve_decode_depth)
        )

        # /metrics exports the step distribution + occupancy gauges
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ).read().decode()
        assert 'sat_gauge{name="serve/decode_steps_p50"}' in body
        assert 'sat_gauge{name="serve/slot_occupancy"}' in body
        assert 'sat_gauge{name="serve/steps_per_dispatch"}' in body
        assert 'sat_gauge{name="serve/steps_per_dispatch_p95"}' in body
    finally:
        server.shutdown()


def test_e2e_429_carries_retry_after(served, monkeypatch):
    """Any 429 shed answers with a Retry-After header + retry_after_ms
    payload hint (satellite: BucketOverflow / queue-full backpressure)."""
    server = CaptionServer(served["config"], served["engine"], port=0)

    def shed(*a, **kw):
        raise Rejected(429, "queue full (test); shed")

    monkeypatch.setattr(server.batcher, "submit", shed)
    server.start()
    try:
        jpeg = open(_fixture_files(served, 1)[0], "rb").read()
        status, payload, headers = _post_raw(server.port, jpeg)
        assert status == 429
        assert payload["retry_after_ms"] >= 50
        assert int(headers["Retry-After"]) >= 1
        # RFC 7231: the header rounds the ms hint UP to whole seconds
        assert (
            int(headers["Retry-After"]) * 1000 >= payload["retry_after_ms"]
        )
    finally:
        server.shutdown()


def test_e2e_continuous_wedge_fails_slots_and_rewarms(served, monkeypatch):
    """SAT_FI_WEDGE_SERVE_BATCH in continuous mode: the wedged decode
    step fails its in-flight slots with fast 500s, the pool re-warms in
    the background, health recovers, and the next request serves."""
    engine, tel = served["engine"], served["tel"]
    rewarms_before = tel.counters().get("serve/rewarms", 0)
    monkeypatch.setenv("SAT_FI_WEDGE_SERVE_BATCH", "1")
    # generous timeout: the injected wedge parks the drain forever so
    # detection is unaffected, but a REAL step on a contended CI host can
    # stall past a tight bound and false-positive the retry below
    config = _continuous_config(served, serve_wedge_timeout_ms=2500.0)
    server = CaptionServer(config, engine, port=0).start()
    try:
        port = server.port
        jpeg = open(_fixture_files(served, 1)[0], "rb").read()
        status, payload, _ = _post_raw(port, jpeg, timeout=30)
        assert status == 500
        assert "wedged" in payload["error"]
        assert tel.counters().get("serve/wedged_batches", 0) >= 1
        # recovery: pool re-warmed (cached compiles), health back to ok.
        # The batcher fails the wedged slots BEFORE it fires the server's
        # degrade-and-re-warm hook (serve/batcher.py), so right after the
        # 500 health may still read "ok" from before the wedge: wait for
        # the re-warm itself, then for health
        deadline = time.time() + 30.0
        while time.time() < deadline:
            code, health = _get(port, "/healthz")
            rewarmed = tel.counters().get("serve/rewarms", 0) > rewarms_before
            if rewarmed and code == 200 and health["status"] == "ok":
                break
            time.sleep(0.05)
        assert code == 200 and health["status"] == "ok"
        assert tel.counters().get("serve/rewarms", 0) == rewarms_before + 1
        status, payload, _ = _post_raw(port, jpeg, timeout=60)
        assert status == 200 and payload["captions"]
        assert server.pool.occupancy() == 0
    finally:
        server.shutdown()


def test_cli_serve_mode_flag():
    from sat_tpu.cli import build_config

    config, _ = build_config(["--phase=serve", "--serve_mode=continuous"])
    assert config.serve_mode == "continuous"
    with pytest.raises(SystemExit):
        build_config(["--phase=serve", "--serve_mode=nope"])


def test_cli_serve_decode_depth_flag():
    from sat_tpu.cli import build_config

    config, _ = build_config(
        ["--phase=serve", "--serve_decode_depth=1,2,4"]
    )
    assert config.serve_decode_depth == (1, 2, 4)
    # --set rides the tuple-coercion path of _parse_override
    config, _ = build_config(
        ["--phase=serve", "--set", "serve_decode_depth=1,6"]
    )
    assert config.serve_decode_depth == (1, 6)
    with pytest.raises(ValueError):
        build_config(["--phase=serve", "--serve_decode_depth=2,4"])
