"""Serving engine: lineage-loaded frozen params + AOT-warmed decode programs.

The offline decode path (runtime.decode_dataset) jits ``encode`` and
``beam_search`` lazily at whatever batch shape the dataset happens to
produce.  A request-driven service cannot afford that: the first request
at a new batch size would eat a multi-second XLA compile, and a jitted
dispatch path can silently recompile forever if batch shapes vary.  The
engine therefore

* loads frozen params through the resilience lineage — the ``LAST_GOOD``
  pointer first (``lineage.last_good_checkpoint`` verifies the target and
  walks back past rot), falling back to ``restore_checkpoint``'s verifying
  newest-first walk when no pointer exists (the ``_restore_last_good``
  recipe, minus the train-state step juggling);
* AOT-compiles ``encode + beam_search`` for every batch bucket in
  ``config.serve_buckets`` at startup via ``jit.lower(...).compile()``
  through jax's persistent compile cache, and dispatches requests through
  the **compiled executables directly** — never the jit dispatch path —
  so a shape that slipped past bucketing raises instead of recompiling;
* owns pad-to-bucket shape selection and the host-side detokenize drain
  (the only host↔device sync on the serve path).

Warm-compile counts are measured through the ``jax.monitoring`` compile
listener (runtime._install_compile_listener → ``jax/compiles`` counter),
which is also how tests assert zero recompiles during the request phase.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..config import Config
from ..data.images import ImageLoader
from ..data.vocabulary import Vocabulary
from ..models.captioner import encode
from ..ops.beam_search import beam_search_jit
from ..resilience import lineage
from ..train.checkpoint import restore_checkpoint
from ..train.step import create_train_state


class BucketOverflow(ValueError):
    """A batch larger than the largest warmed bucket.  An admission-side
    overload signal, not a server fault: the frontend maps it to HTTP 429
    with a Retry-After hint instead of a 500."""

    def __init__(self, n: int, buckets: Sequence[int]):
        super().__init__(
            f"batch of {n} exceeds the largest warmed bucket "
            f"{buckets[-1]} (serve_buckets={tuple(buckets)})"
        )
        self.n = n
        self.largest = int(buckets[-1])


def load_serving_state(config: Config, model_file: Optional[str] = None):
    """Frozen-param load for serving; returns ``(state, source)``.

    An explicit ``model_file`` is the operator saying "this file" and is
    loaded as-is.  Otherwise the blessed ``LAST_GOOD`` pointer target wins
    (verified, with lineage's own walk-back past rotted candidates), and a
    save_dir that predates the lineage pointer falls back to
    ``restore_checkpoint``'s verifying newest-first walk.
    """
    import jax

    from ..data.vocabulary import vocab_fingerprint

    state = create_train_state(jax.random.PRNGKey(config.seed), config)
    # serving decodes against the configured vocabulary: a checkpoint
    # attesting a different one must fail here, loudly, not caption in
    # gibberish (train.checkpoint.VocabMismatchError)
    expect = vocab_fingerprint(config.vocabulary_file, config.vocabulary_size)
    if model_file:
        source = model_file
        state, count = restore_checkpoint(
            state, model_file=model_file, expect_vocab=expect
        )
    else:
        source = lineage.last_good_checkpoint(config.save_dir)
        if source is not None:
            state, count = restore_checkpoint(
                state, model_file=source, expect_vocab=expect
            )
        else:
            source = config.save_dir
            state, count = restore_checkpoint(
                state, save_dir=config.save_dir, expect_vocab=expect
            )
    if count == 0:
        raise ValueError(f"serving checkpoint {source} restored 0 tensors")
    return state, source


def _effective_buckets(buckets: Sequence[int], max_batch: int) -> Tuple[int, ...]:
    """The ladder actually worth warming: every bucket below max_batch,
    plus the first one that can hold a full max_batch dispatch.  (Config
    validation guarantees max_batch <= max(buckets), so the result is
    never empty and always covers a full batch.)"""
    out = [int(b) for b in buckets if b < max_batch]
    for b in buckets:
        if b >= max_batch:
            out.append(int(b))
            break
    return tuple(out)


class ServeEngine:
    """Frozen variables + one AOT executable pair per batch bucket."""

    def __init__(
        self,
        config: Config,
        state,
        vocabulary: Vocabulary,
        tel=None,
    ) -> None:
        self.config = config
        self.vocabulary = vocabulary
        self.eos_id = vocabulary.word2idx["."]
        self._tel = tel if tel is not None else telemetry.get()
        self.step = int(np.asarray(state.step))  # sync-ok: startup, before any request traffic
        # which device this replica answers on (/stats "engine.device"):
        # a one-chip fleet replica sees its chip as local device 0
        # whichever chip it is, so the launcher's assignment rides along
        import jax

        d0 = jax.local_devices()[0]
        self.device = {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "id": d0.id,
            "chip": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        }
        self._variables: Dict[str, Any] = {"params": state.params}
        if state.batch_stats:
            self._variables["batch_stats"] = state.batch_stats
        self._decoder_params = state.params["decoder"]
        self.encoder_quant = config.encoder_quant
        self.quantize_seconds = 0.0
        if config.encoder_quant != "off":
            # Quantize ONCE at load, before any AOT warmup, so the bucket
            # ladder and the slot-pool encode lanes all compile against
            # the quantized weights and the zero-steady-state-recompile
            # guarantee covers the quantized path unchanged.  The serve
            # variables then carry ONLY the quantized encoder: the fp32
            # cnn params (and the BN stats, folded into the conv biases)
            # leave the tree so warmed executables never hold both
            # copies of the encoder in HBM.
            from ..nn import quant

            t0 = time.perf_counter()
            qcnn = quant.quantize_encoder(self._variables, config)
            self._variables = {
                "params": {"decoder": state.params["decoder"]},
                "qcnn": qcnn,
            }
            self.quantize_seconds = time.perf_counter() - t0
            self._tel.gauge(
                "serve/encoder_quantize_seconds",
                round(self.quantize_seconds, 3),
            )
            print(
                f"sat_tpu: serve encoder quantized "
                f"({config.encoder_quant}, {config.cnn}) in "
                f"{self.quantize_seconds:.2f}s",
                file=sys.stderr,
                flush=True,
            )
        self.buckets = _effective_buckets(
            config.serve_buckets, config.serve_max_batch
        )
        self.loader = ImageLoader(
            size=config.image_size, raw=config.device_preprocess
        )
        self._image_dtype = (
            np.uint8 if config.device_preprocess else np.float32
        )
        self._compiled: Dict[int, Tuple[Any, Any]] = {}
        self.warm_compiles = 0
        self.warm_seconds = 0.0
        self.compiles_at_ready = 0
        # content-addressed encode cache (--encode_cache on): constructed
        # here, geometry fixed at warmup (engine buckets in batch mode,
        # pool lanes in continuous mode).  None when off — every caller
        # branches on that, so the off-knob path is byte-for-byte today's.
        self.encode_cache = None
        if config.encode_cache == "on":
            from .encode_cache import EncodeCache

            self.encode_cache = EncodeCache(
                config.encode_cache_mb, tel=self._tel
            )
        # context-row aval (shape [N, D], dtype) — set by whichever warmup
        # runs; the decode tier validates handoff grids against it
        self.ctx_row_shape: Optional[Tuple[int, ...]] = None
        self.ctx_row_dtype = None
        # width-1 encode executable for the encode tier's POST /encode
        # (warmed by the server when serve_tier="encode"; lazily compiled
        # otherwise, which counts as a compile — documented in SERVING.md)
        self._enc_one_exec = None
        self._enc_one_lock = threading.Lock()
        # second param slot for the lifecycle plane: a candidate tree with
        # the same treedef/shapes/dtypes as the incumbent, runnable
        # through the ALREADY-WARMED executables (params are runtime
        # arguments to the AOT programs, so the swap is a pointer flip,
        # never a compile).  None = no candidate staged.
        self._candidate: Optional[Dict[str, Any]] = None
        # multi-tenant resident models (docs/SERVING.md): N additional
        # device-resident param sets keyed by alias, each aval-validated
        # against the incumbent so they ALL run through the same warmed
        # executables — N models, one compiled ladder, zero extra
        # compiles
        self._residents: Dict[str, Dict[str, Any]] = {}

    # -- param slots (lifecycle + multi-tenant planes) ---------------------

    def slot_variables(self, slot: str = "incumbent") -> Dict[str, Any]:
        """The encode variables for ``slot``.  The canary slot falls back
        to the incumbent when no candidate is staged — in-flight canary
        work during a rollback completes against real params instead of
        crashing.  A resident-model alias resolves its own tree."""
        if slot == "canary" and self._candidate is not None:
            return self._candidate["variables"]
        resident = self._residents.get(slot)
        if resident is not None:
            return resident["variables"]
        return self._variables

    def slot_decoder_params(self, slot: str = "incumbent"):
        if slot == "canary" and self._candidate is not None:
            return self._candidate["decoder_params"]
        resident = self._residents.get(slot)
        if resident is not None:
            return resident["decoder_params"]
        return self._decoder_params

    @property
    def candidate_step(self) -> Optional[int]:
        return None if self._candidate is None else self._candidate["step"]

    def param_fingerprint(self, slot: str = "incumbent") -> Tuple:
        """Stable identity of the params a slot resolves to right now —
        the generation component of encode-cache keys, so a grid encoded
        under one model can never serve a hit under another (hot-swap,
        resident alias, or a different quant mode all change the key)."""
        if slot == "canary" and self._candidate is not None:
            return ("canary", self._candidate["step"], self.encoder_quant)
        resident = self._residents.get(slot)
        if resident is not None:
            return (slot, resident["step"], self.encoder_quant)
        return ("incumbent", self.step, self.encoder_quant)

    def _validate_compat(
        self, variables: Dict[str, Any], decoder_params, source: str,
        what: str = "candidate",
    ) -> None:
        """Assert a param tree is executable by the incumbent's warmed
        programs — same treedef, same leaf shapes and dtypes — or the
        first dispatch against it would either recompile (jit path) or
        crash (AOT path).  Shared by the lifecycle candidate slot and
        the multi-tenant resident slots; a mismatch raises ValueError
        before the tree can see a request."""
        import jax

        for name, have, want in (
            ("variables", variables, self._variables),
            ("decoder_params", decoder_params, self._decoder_params),
        ):
            have_leaves, have_def = jax.tree_util.tree_flatten(have)
            want_leaves, want_def = jax.tree_util.tree_flatten(want)
            if have_def != want_def:
                raise ValueError(
                    f"{what} {name} tree structure differs from the "
                    f"incumbent ({source}): warmed executables cannot "
                    "run it"
                )
            for h, w in zip(have_leaves, want_leaves):
                if h.shape != w.shape or h.dtype != w.dtype:
                    raise ValueError(
                        f"{what} {name} leaf {h.shape}/{h.dtype} vs "
                        f"incumbent {w.shape}/{w.dtype} ({source}): "
                        "geometry drift, rejecting"
                    )

    def install_candidate(
        self, variables: Dict[str, Any], decoder_params, step: int,
        source: str,
    ) -> None:
        """Stage a candidate param tree in the second slot, verified
        runnable by the warmed executables (``_validate_compat``); the
        caller rejects the checkpoint's lineage entry on mismatch."""
        self._validate_compat(variables, decoder_params, source)
        self._candidate = {
            "variables": variables,
            "decoder_params": decoder_params,
            "step": int(step),
            "source": source,
        }
        self._tel.gauge("lifecycle/candidate_step", int(step))

    def promote_candidate(self) -> int:
        """Flip the active slot: the candidate becomes the incumbent and
        the old incumbent's tree is dropped (its device buffers free once
        in-flight work referencing them drains).  Callers sequence this at
        the batcher's admission boundary so no batch straddles the flip.
        Returns the new serving step."""
        if self._candidate is None:
            raise RuntimeError("no candidate staged to promote")
        cand = self._candidate
        self._candidate = None
        self._variables = cand["variables"]
        self._decoder_params = cand["decoder_params"]
        self.step = cand["step"]
        self._tel.gauge("lifecycle/candidate_step", -1)
        if self.encode_cache is not None:
            # fingerprinted keys mean stale entries could never hit, but
            # flushing returns their rows immediately (lifecycle coherence)
            self.encode_cache.flush()
        return self.step

    def clear_candidate(self) -> None:
        """Drop a staged candidate (rollback): the incumbent is untouched
        and the canary slot falls back to it for any stragglers."""
        self._candidate = None
        self._tel.gauge("lifecycle/candidate_step", -1)
        if self.encode_cache is not None:
            self.encode_cache.flush()

    # -- resident models (multi-tenant plane) ------------------------------

    def install_resident(
        self, alias: str, variables: Dict[str, Any], decoder_params,
        step: int, source: str,
    ) -> None:
        """Register a device-resident param set under ``alias``
        (``X-Model`` / a tenant's default model).  Aval-validated like a
        lifecycle candidate — every resident runs through the SAME
        warmed executables, so serving N models costs zero additional
        compiles (the acceptance criterion tests/test_tenants.py pins).
        The two lifecycle slot names are reserved."""
        if alias in ("incumbent", "canary"):
            raise ValueError(
                f"resident alias {alias!r} collides with a lifecycle "
                "slot name"
            )
        self._validate_compat(
            variables, decoder_params, source, what=f"resident {alias!r}"
        )
        self._residents[alias] = {
            "variables": variables,
            "decoder_params": decoder_params,
            "step": int(step),
            "source": source,
        }
        self._tel.gauge("serve/resident_models", len(self._residents))

    def has_resident(self, alias: str) -> bool:
        return alias in self._residents

    def resident_step(self, alias: str) -> Optional[int]:
        resident = self._residents.get(alias)
        return None if resident is None else resident["step"]

    @property
    def resident_aliases(self) -> Tuple[str, ...]:
        return tuple(self._residents)

    # -- startup -----------------------------------------------------------

    def warmup(self) -> None:
        """AOT-compile encode + beam_search for every bucket.

        ``jit.lower(args).compile()`` builds each executable without
        running it (shape/dtype specs stand in for the images), lands it
        in the persistent compile cache, and hands back a callable that
        can *only* run at its compiled shape — the property the
        zero-recompile guarantee rests on."""
        import jax

        config = self.config
        size = config.image_size

        def encode_fn(variables, images):
            contexts, _ = encode(variables, config, images, train=False)
            return contexts

        enc_jit = jax.jit(encode_fn)
        beam_kwargs = dict(
            beam_size=config.beam_size,
            valid_size=len(self.vocabulary.words),
            # the quality plane reads coverage/entropy off the harvested
            # alphas, so quality-on warms executables that carry them in
            # the result pytree (drained with the batch — no extra sync);
            # off keeps the pre-quality memory/transfer footprint
            return_alphas=config.serve_quality == "on",
            # per-batch decode-step counts ride the result pytree and are
            # drained with it — the serve/decode_steps observability probe
            return_steps=True,
        )
        compiles0 = self._tel.counters().get("jax/compiles", 0)
        t0 = time.perf_counter()
        for b in self.buckets:
            images_sd = jax.ShapeDtypeStruct(
                (b, size, size, 3), self._image_dtype
            )
            ctx_sd = jax.eval_shape(enc_jit, self._variables, images_sd)
            enc_exec = enc_jit.lower(self._variables, images_sd).compile()
            beam_exec = beam_search_jit.lower(
                self._decoder_params, config, ctx_sd, self.eos_id,
                **beam_kwargs,
            ).compile()
            self._compiled[b] = (enc_exec, beam_exec)
            self.ctx_row_shape = tuple(int(d) for d in ctx_sd.shape[1:])
            self.ctx_row_dtype = np.dtype(ctx_sd.dtype)
        if self.encode_cache is not None:
            # ring sized off the real context-row aval, insert/gather
            # warmed at every bucket the dispatch path can use — part of
            # the same pre-ready warmup, so steady state never compiles
            self.encode_cache.ensure_store(
                self.ctx_row_shape, self.ctx_row_dtype,
                min_rows=max(self.buckets),
            )
            self.encode_cache.warm(self.buckets)
        self.warm_seconds = time.perf_counter() - t0
        counters = self._tel.counters()
        self.compiles_at_ready = counters.get("jax/compiles", 0)
        self.warm_compiles = self.compiles_at_ready - compiles0
        self._tel.gauge("serve/warm_buckets", len(self.buckets))
        self._tel.gauge("serve/warm_compiles", self.warm_compiles)
        self._tel.gauge("serve/warm_seconds", round(self.warm_seconds, 3))
        print(
            f"sat_tpu: serve warmup — buckets {self.buckets}, "
            f"{self.warm_compiles} XLA compiles in {self.warm_seconds:.1f}s "
            f"(cached compiles are free)",
            file=sys.stderr,
            flush=True,
        )

    # -- batching geometry -------------------------------------------------

    def pick_bucket(self, n: int) -> int:
        """Smallest warmed bucket that holds ``n`` requests."""
        for b in self.buckets:
            if b >= n:
                return b
        raise BucketOverflow(n, self.buckets)

    def pad_batch(self, images: List[np.ndarray]) -> Tuple[np.ndarray, int]:
        """Stack request images and zero-pad up to the chosen bucket.
        Beam search is row-independent, so pad rows cost device time but
        never perturb real rows (pinned by tests/test_serve.py)."""
        bucket = self.pick_bucket(len(images))
        size = self.config.image_size
        batch = np.zeros((bucket, size, size, 3), self._image_dtype)
        for i, image in enumerate(images):
            batch[i] = image
        return batch, bucket

    # -- request path ------------------------------------------------------

    def preprocess(self, data: bytes) -> np.ndarray:
        """POSTed JPEG/PNG bytes → one model input row (uint8 RGB when the
        device finishes preprocessing, float32 mean-subtracted otherwise).
        Raises ValueError on undecodable bytes (frontend maps to 400)."""
        return self.loader.load_bytes(data)

    def dispatch(
        self, images: np.ndarray, slot: str = "incumbent", costs=None,
        keys=None,
    ):
        """Async: padded batch [bucket,S,S,3] → BeamResult of device
        arrays.  Calls the AOT executables directly, so the only work on
        this thread is argument transfer — the device runs ahead while the
        host returns to batching (the ``device_prefetch`` overlap).
        ``slot`` selects which param tree the warmed executables run
        against (incumbent or the staged canary candidate).  ``costs``
        (optional) is the live requests' ``RequestCost`` accumulators —
        each is charged an equal share of the measured encode window
        (telemetry/metering.py; only meaningful with telemetry on, since
        the window is only measured inside the tel-gated block).
        ``keys`` (one crc32c per live request, cache-on only) routes the
        batch through the content-addressed cache: only unique misses hit
        the encode lane — at the smallest bucket that holds them — and
        every row is then gathered from the ring, so hit rows are the
        exact bits their original encode produced and hit requests are
        charged zero encode device-ms."""
        import jax

        variables = self.slot_variables(slot)
        decoder_params = self.slot_decoder_params(slot)
        enc_exec, beam_exec = self._compiled[images.shape[0]]
        cache = self.encode_cache
        if cache is not None and keys is not None:
            return self._dispatch_cached(
                images, slot, costs, keys, beam_exec, decoder_params
            )
        t0 = time.perf_counter_ns()
        contexts = enc_exec(variables, jax.device_put(images))
        if self._tel.enabled:
            # encode-lane timing (the serve/encode_ms introspection): only
            # with telemetry on do we wait out the encode before chaining
            # the beam dispatch — the device queue keeps its ordering and
            # the beam dispatch happens immediately after either way
            jax.block_until_ready(contexts)  # sync-ok: opt-in telemetry encode timing, gated on tel.enabled
            dur = time.perf_counter_ns() - t0
            self._tel.record("serve/encode", t0, dur)
            self._tel.record(f"serve/encode_lane{images.shape[0]}", t0, dur)
            if costs:
                share = dur // len(costs)
                for cost in costs:
                    if cost is not None:
                        cost.add_encode(share)
                self._tel.count("serve/encode_images", len(costs))
                self._tel.count("serve/encode_lane_slots", images.shape[0])
        return beam_exec(decoder_params, contexts)

    def _dispatch_cached(
        self, images, slot, costs, keys, beam_exec, decoder_params
    ):
        """Cache-routed batch dispatch: plan rows, encode unique misses
        at the smallest bucket that holds them, insert, gather the full
        bucket, beam.  Encode cost is attributed ONLY to the miss
        requests (an equal split of the measured miss-lane window), so
        hit and coalesced requests bill zero encode device-ms and the
        attributed≈measured identity holds."""
        import jax

        cache = self.encode_cache
        gen = self.param_fingerprint(slot)
        plan = cache.plan([(k, gen) for k in keys])
        bucket = images.shape[0]
        size = self.config.image_size
        try:
            if plan.n_miss:
                mb = self.pick_bucket(plan.n_miss)
                miss_images = np.zeros(
                    (mb, size, size, 3), self._image_dtype
                )
                for j, pos in enumerate(plan.miss_pos):
                    miss_images[j] = images[pos]
                enc_exec = self._compiled[mb][0]
                t0 = time.perf_counter_ns()
                lane_ctx = enc_exec(
                    self.slot_variables(slot), jax.device_put(miss_images)
                )
                if self._tel.enabled:
                    jax.block_until_ready(lane_ctx)  # sync-ok: opt-in telemetry encode timing, gated on tel.enabled
                    dur = time.perf_counter_ns() - t0
                    self._tel.record("serve/encode", t0, dur)
                    self._tel.record(f"serve/encode_lane{mb}", t0, dur)
                    miss_costs = (
                        [costs[p] for p in plan.miss_pos] if costs else []
                    )
                    if miss_costs:
                        share = dur // len(miss_costs)
                        for cost in miss_costs:
                            if cost is not None:
                                cost.add_encode(share)
                        self._tel.count(
                            "serve/encode_images", len(miss_costs)
                        )
                        self._tel.count("serve/encode_lane_slots", mb)
                cache.insert(mb, lane_ctx, plan.miss_rows)
            t0 = time.perf_counter_ns()
            contexts = cache.gather(bucket, plan.rows)
            if self._tel.enabled:
                # hit-path latency probe (the cache block's p95); its own
                # span, NOT a BUSY_SPAN, so metering identity is untouched
                jax.block_until_ready(contexts)  # sync-ok: opt-in telemetry gather timing, gated on tel.enabled
                self._tel.record(
                    "serve/cache_gather", t0, time.perf_counter_ns() - t0
                )
        except Exception:
            # the plan already registered the miss keys; their rows hold
            # garbage now, so un-plan them before propagating
            cache.drop([(k, gen) for k in plan.miss_keys])
            raise
        return beam_exec(decoder_params, contexts)

    def dispatch_contexts(
        self, contexts: List[np.ndarray], slot: str = "incumbent",
        costs=None,
    ):
        """Decode-tier batch dispatch: pre-encoded context grids (the
        tier handoff) → BeamResult, skipping the encode lane entirely.
        Grids were aval-checked at ingress, so stacking + zero-padding to
        the bucket feeds the warmed beam executable its exact compiled
        shape — zero encode device-ms charged, zero compiles."""
        import jax

        decoder_params = self.slot_decoder_params(slot)
        bucket = self.pick_bucket(len(contexts))
        beam_exec = self._compiled[bucket][1]
        batch = np.zeros(
            (bucket,) + tuple(self.ctx_row_shape), self.ctx_row_dtype
        )
        for i, ctx in enumerate(contexts):
            batch[i] = ctx
        self._tel.count("serve/context_dispatches")
        self._tel.count("serve/context_images", len(contexts))
        return beam_exec(decoder_params, jax.device_put(batch))

    # -- encode tier (POST /encode) ----------------------------------------

    def warm_encode_one(self) -> None:
        """AOT-compile the width-1 encode used by ``POST /encode`` (the
        encode tier's whole request path).  Called from server startup
        when ``serve_tier="encode"`` so the compile lands before ready;
        a ``both``-tier replica that never warmed it compiles lazily on
        the first /encode instead (one compile, documented)."""
        import jax

        if self._enc_one_exec is not None:
            return
        config = self.config
        size = config.image_size

        def encode_fn(variables, images):
            contexts, _ = encode(variables, config, images, train=False)
            return contexts

        images_sd = jax.ShapeDtypeStruct(
            (1, size, size, 3), self._image_dtype
        )
        enc_jit = jax.jit(encode_fn)
        ctx_sd = jax.eval_shape(enc_jit, self._variables, images_sd)
        self._enc_one_exec = enc_jit.lower(
            self._variables, images_sd
        ).compile()
        self.ctx_row_shape = tuple(int(d) for d in ctx_sd.shape[1:])
        self.ctx_row_dtype = np.dtype(ctx_sd.dtype)

    def encode_one(
        self, image: np.ndarray, slot: str = "incumbent"
    ) -> np.ndarray:
        """One preprocessed image row → its ``[N, D]`` context grid on
        the host (the /encode response body, pre-handoff-framing).
        Serialized by a lock: /encode arrives on HTTP threads, and the
        width-1 executable is cheap enough that queueing beats batching
        for the stateless encode tier."""
        import jax

        with self._enc_one_lock:
            if self._enc_one_exec is None:
                self.warm_encode_one()
            t0 = time.perf_counter_ns()
            ctx = self._enc_one_exec(
                self.slot_variables(slot), jax.device_put(image[None])
            )
            grid = np.asarray(ctx)[0]  # sync-ok: /encode response body — the grid must land on the host to be framed
            if self._tel.enabled:
                self._tel.record(
                    "serve/encode", t0, time.perf_counter_ns() - t0
                )
                self._tel.count("serve/encode_images")
                self._tel.count("serve/encode_lane_slots")
        return grid

    def drain_output(self, out, n: int) -> Tuple[np.ndarray, ...]:
        """Drain the device result for the ``n`` live rows: host arrays
        (words, lengths, log_scores, alphas-or-None).  This is the serve
        path's one
        host↔device sync — split from detokenization so the batcher can
        time (and the request tracer attribute) device wait separately
        from host string work."""
        # Whole-array transfers, sliced on the HOST: a device-side [:n]
        # slice is itself a jitted gather that would compile once per
        # distinct n — a hidden recompile the zero-recompile guarantee
        # (and its test) would trip over.
        words = np.asarray(out.words)[:n]  # sync-ok: serve detok boundary — batch results drained once
        lengths = np.asarray(out.lengths)[:n]  # sync-ok: serve detok boundary
        scores = np.asarray(out.log_scores)[:n]  # sync-ok: serve detok boundary
        alphas = None
        if out.alphas is not None:
            # part of the same batched result transfer (quality-on only)
            alphas = np.asarray(out.alphas)[:n]  # sync-ok: serve detok boundary, rides the batch drain
        if out.steps_run is not None:
            # raw loop-iteration count (not ns); /stats reports raw
            # percentiles and the bench divides by request count
            steps = int(np.asarray(out.steps_run))  # sync-ok: drained with the batch above
            self._tel.record("serve/decode_steps", 0, steps)
            # the monolithic search is one dispatch running `steps` decode
            # steps on-device — the whole-batch limit of the continuous
            # path's fused window, reported on the same probe so both
            # modes' dispatch amortization reads off one /stats block
            self._tel.record("serve/steps_per_dispatch", 0, steps)
        return words, lengths, scores, alphas

    def detok_rows(
        self, arrays: Tuple[np.ndarray, ...], n: int
    ) -> List[Dict[str, Any]]:
        """Detokenize every beam of ``n`` drained rows — pure host work on
        numpy arrays, no device access.  ``arrays`` may carry a trailing
        alphas element (quality-on drains); detok only needs the first
        three."""
        words, lengths, scores = arrays[:3]
        results = []
        for i in range(n):
            captions = []
            for k in range(words.shape[1]):
                length = max(1, int(lengths[i, k]))
                captions.append(
                    {
                        "caption": self.vocabulary.get_sentence(
                            words[i, k, :length]
                        ),
                        "log_prob": float(scores[i, k]),  # sync-ok: host numpy, already drained
                        "prob": float(np.exp(scores[i, k])),  # sync-ok: host numpy, already drained
                    }
                )
            results.append({"captions": captions})
        return results

    def decode_output(self, out, n: int) -> List[Dict[str, Any]]:
        """Drain + detokenize in one call (the pre-split contract; the
        batcher now calls the halves separately to time them)."""
        return self.detok_rows(self.drain_output(out, n), n)
