"""HTTP frontend for the captioning service (docs/SERVING.md).

A stdlib ``ThreadingHTTPServer`` — one Python thread per in-flight
request, which is exactly the concurrency this workload wants: request
threads spend their time in the JPEG codec (releases the GIL) or parked
on an Event while the batcher owns the device, so host preprocessing of
request n+1 overlaps device decode of batch n with no async framework.

Endpoints:

* ``POST /caption`` — body: JPEG/PNG bytes.  200 → ``{"captions": [{
  "caption", "log_prob", "prob"}, ...beam-ordered], "bucket",
  "model_step"}``.  400 undecodable body, 429 queue/quota shed, 503
  draining, 504 deadline/timeout.  ``X-Deadline-Ms`` (integer) overrides
  ``Config.serve_deadline_ms`` per request.  Under ``--tenants``,
  ``X-Tenant`` selects the tenant (quota bucket, scheduling weight, SLO
  lane; bare/unknown keys map to the default tenant) and ``X-Model``
  pins a resident param set; every 429/503 carries ``X-Shed-Scope:
  tenant|global`` with a scope-matched ``Retry-After`` (tenant bucket
  refill vs. observed service period).
* ``GET /healthz`` — readiness + the run-health heartbeat payload
  (telemetry.Heartbeat — same fields watchers poll from heartbeat.json).
  200 ready, 503 draining/stopped: a load balancer needs only the code.
* ``GET /stats`` — queue depth, bucket histogram, serve counters, and
  p50/p95/p99 latency per serve span (queue_wait / preprocess / dispatch
  / detok / request) from the telemetry ring.
* ``GET /metrics`` — Prometheus text exposition (format 0.0.4) of every
  counter/gauge/span aggregate (telemetry.promtext).
* ``POST /profile?duration_ms=N`` — start a bounded live ``jax.profiler``
  capture into ``<telemetry_dir>/profiles/<ts>/``; 409 while another
  capture runs, duration clamped to the hard cap (telemetry.profwin).
* ``GET /quality_reference`` — export the frozen quality-reference
  distributions (telemetry.quality) for ``--quality_reference`` on
  another replica; 404 with ``--serve_quality off``, 409 before one
  froze.

Every reply — including 400/429/503/504 sheds and 404s — echoes
``X-Request-Id`` (inbound value sanitized, or minted), and each
``POST /caption`` is traced per phase into ``access.jsonl`` plus its own
Perfetto lane (telemetry.tracectx).  Declared SLOs (``slo_*`` config)
are evaluated continuously; a burning objective flips ``/healthz`` to
503 "degraded" with the objective named (telemetry.slo).

Shutdown: SIGTERM/SIGINT (via ``resilience.preempt.GracefulShutdown``)
or ``request_shutdown()`` triggers the drain sequence — readiness flips
first, the batcher rejects new work and completes everything admitted,
then the listener and heartbeat close and ``serve()`` returns 0.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .. import telemetry
from ..config import Config
from ..data.vocabulary import Vocabulary
from ..lifecycle import LifecycleController
from ..lifecycle import canary as canary_mod
from ..resilience.preempt import GracefulShutdown
from ..telemetry import promtext, tracectx
from ..telemetry.capacity import CapacityModel, EncodeCacheSketch
from ..telemetry.heartbeat import Heartbeat
from ..telemetry.exemplar import ExemplarRecorder
from ..telemetry.metering import MeteringLedger
from ..telemetry.profwin import ProfileLatch
from ..telemetry.quality import QualityMonitor, QualityReference
from ..telemetry.slo import SLOEngine, objectives_from_config
from ..utils.summary import crc32c
from . import handoff
from .batcher import ContinuousBatcher, MicroBatcher, Rejected
from .engine import ServeEngine, load_serving_state
from .slot_pool import PagedSlotPool
from .tenants import TenantRegistry

_LATENCY_SPANS = (
    "serve/request",
    "serve/queue_wait",
    "serve/preprocess",
    "serve/dispatch",
    "serve/step",
    "serve/detok_queue",
    "serve/detok",
)

# /metrics histogram families (telemetry/promtext.py): true cumulative
# _bucket/_sum/_count exposition alongside the percentile gauges, so
# Prometheus picks its own quantiles server-side.  Latency bounds in
# seconds (the Prometheus convention); steps-per-dispatch raw counts
# matching the fused-decode K ladder.
_HISTOGRAMS: Dict[str, promtext.HistogramSpec] = {
    "sat_request_latency_seconds": (
        "serve/request",
        (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
        1e-9,
    ),
    "sat_steps_per_dispatch": (
        "serve/steps_per_dispatch",
        (1.0, 2.0, 4.0, 8.0, 16.0, 32.0),
        1.0,
    ),
}


def _percentiles_ms(tel, name: str) -> Optional[Dict[str, Any]]:
    """p50/p95/p99 (ms) of a span's recorded durations; None when empty.
    Host-side accounting over the telemetry ring — no device data."""
    data = np.asarray(tel.durations_ns(name), np.float64)  # sync-ok: host telemetry ring, not device data
    if data.size == 0:
        return None
    data = np.sort(data) / 1e6
    def pct(p: float) -> float:
        idx = min(data.size - 1, int(p / 100.0 * data.size))
        return round(float(data[idx]), 3)  # sync-ok: host numpy percentile
    return {
        "count": int(data.size),
        "p50": pct(50),
        "p95": pct(95),
        "p99": pct(99),
    }


def _percentiles_raw(tel, name: str) -> Optional[Dict[str, Any]]:
    """Like :func:`_percentiles_ms` but for spans that store raw counts
    (serve/decode_steps records loop iterations, not nanoseconds)."""
    data = np.asarray(tel.durations_ns(name), np.float64)  # sync-ok: host telemetry ring, not device data
    if data.size == 0:
        return None
    data = np.sort(data)
    def pct(p: float) -> float:
        idx = min(data.size - 1, int(p / 100.0 * data.size))
        return round(float(data[idx]), 3)  # sync-ok: host numpy percentile
    return {
        "count": int(data.size),
        "p50": pct(50),
        "p95": pct(95),
        "p99": pct(99),
    }


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "sat-serve"

    def log_message(self, fmt, *args):  # stderr per-request noise: off
        pass

    def _request_id(self) -> str:
        return tracectx.ensure_id(self.headers.get(tracectx.TRACE_HEADER))

    def _send(
        self,
        status: int,
        body: bytes,
        ctype: str,
        rid: str,
        headers: Optional[Dict[str, str]] = None,
    ) -> None:
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        # EVERY reply carries the correlation id — sheds and 404s too,
        # so clients can correlate a reject with their own logs
        self.send_header(tracectx.TRACE_HEADER, rid)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _reply(
        self,
        status: int,
        payload: Dict[str, Any],
        rid: str,
        headers: Optional[Dict[str, str]] = None,
    ) -> None:
        self._send(
            status, json.dumps(payload).encode(), "application/json", rid,
            headers=headers,
        )

    def do_GET(self) -> None:
        app = self.server.app
        rid = self._request_id()
        route = self.path.split("?", 1)[0]
        if route == "/healthz":
            payload, status = app.healthz()
            self._reply(status, payload, rid)
        elif route == "/stats":
            self._reply(200, app.stats(), rid)
        elif route == "/metrics":
            self._send(
                200, app.metrics_text().encode(), promtext.CONTENT_TYPE, rid
            )
        elif route == "/quality_reference":
            payload, status = app.quality_reference()
            self._reply(status, payload, rid)
        else:
            self._reply(404, {"error": f"no route {self.path}"}, rid)

    def do_POST(self) -> None:
        app = self.server.app
        rid = self._request_id()
        route, _, query = self.path.partition("?")
        if route in ("/reload", "/promote", "/rollback"):
            status, payload = app.admin_lifecycle(route[1:])
            self._reply(status, payload, rid)
            return
        if route == "/profile":
            import urllib.parse

            params = urllib.parse.parse_qs(query)
            try:
                duration_ms = (
                    int(params["duration_ms"][0])
                    if "duration_ms" in params
                    else None
                )
            except (ValueError, IndexError):
                self._reply(
                    400, {"error": "duration_ms must be an integer"}, rid
                )
                return
            ok, info = app.start_profile(duration_ms)
            if ok:
                self._reply(
                    200, {"profile_dir": info, "duration_ms": duration_ms}, rid
                )
            else:
                status = 409 if "in progress" in info else 503
                self._reply(status, {"error": info}, rid)
            return
        if route not in ("/caption", "/encode"):
            self._reply(404, {"error": f"no route {self.path}"}, rid)
            return
        try:
            length = int(self.headers.get("Content-Length", "0"))
        except ValueError:
            length = 0
        if length <= 0:
            self._reply(400, {"error": "empty body; POST image bytes"}, rid)
            return
        body = self.rfile.read(length)
        if route == "/encode":
            # encode tier: image bytes in, framed context grid out
            status, out_body, ctype = app.handle_encode(body)
            self._send(status, out_body, ctype, rid)
            return
        status, payload = app.handle_caption(
            body,
            deadline_ms=self.headers.get("X-Deadline-Ms"),
            request_id=rid,
            tenant=self.headers.get("X-Tenant"),
            model=self.headers.get("X-Model"),
            content_type=self.headers.get("Content-Type"),
        )
        headers = None
        if status in (429, 503) and "retry_after_ms" in payload:
            # RFC 7231 Retry-After is whole seconds; round up so a
            # compliant client never comes back before the hint (the
            # never-0s clamp).  One contract for both shed shapes: 429
            # queue/quota sheds and 503 drain-rejects carry the same
            # header the router's coherent edge shed speaks, and
            # X-Shed-Scope says WHOSE capacity ran out — "tenant" (your
            # bucket/lane; backing off helps only you) vs "global" (the
            # service; everyone should back off).
            secs = max(1, int(-(-payload["retry_after_ms"] // 1000)))
            headers = {
                "Retry-After": str(secs),
                "X-Shed-Scope": payload.get("shed_scope", "global"),
            }
        self._reply(status, payload, rid, headers=headers)


class CaptionServer:
    """Wires engine + micro-batcher + HTTP listener + heartbeat; owns the
    readiness flag and the drain sequence."""

    # ceiling on how long a handler thread waits for its result when the
    # request carries no deadline (a wedged device must not strand
    # connections forever)
    DEFAULT_WAIT_S = 120.0

    def __init__(
        self,
        config: Config,
        engine: ServeEngine,
        host: Optional[str] = None,
        port: Optional[int] = None,
    ) -> None:
        self.config = config
        self.engine = engine
        self._tel = telemetry.get()
        # multi-tenant plane (docs/SERVING.md): the registry maps
        # X-Tenant → quota bucket / scheduling weight / resident model /
        # SLO lane.  The empty --tenants spec is the degenerate
        # single-tenant registry (multi=False): no buckets, no weights
        # table, no per-tenant counters — the pre-tenant serving path,
        # bit for bit.
        self.tenants = TenantRegistry.parse(config.tenants)
        self._load_residents()
        weights = self.tenants.weights() if self.tenants.multi else None
        tdir = config.telemetry_dir or os.path.join(
            config.summary_dir, "telemetry"
        )
        # caption-quality plane (telemetry/quality.py): streaming signal
        # sketches + PSI drift vs a frozen reference, and the exemplar
        # flight recorder for outlier requests.  Off (the default) means
        # no monitor, no recorder, no alphas in the warmed executables —
        # bit-identical to the pre-quality serving path (pinned by
        # tests/test_quality.py).
        self.quality: Optional[QualityMonitor] = None
        self.exemplars: Optional[ExemplarRecorder] = None
        if config.serve_quality == "on":
            reference = None
            if config.serve_quality_reference:
                reference = QualityReference.load(
                    config.serve_quality_reference
                )
            self.quality = QualityMonitor(
                window=config.serve_quality_window,
                reference=reference,
                margin_min=config.serve_quality_margin_min,
                unk_max=config.serve_quality_unk_max,
                tel=self._tel,
            )
            self.exemplars = ExemplarRecorder(
                config.serve_quality_exemplar_dir
                or os.path.join(tdir, "exemplars"),
                budget_mb=config.serve_quality_exemplar_mb,
            )
            # replay context: scripts/replay_exemplar.py boots from THIS
            # meta, never from guessed flags
            self.exemplars.write_meta(
                {
                    "config": config.to_dict(),
                    "model_step": engine.step,
                    "vocab_crc32c": f"{crc32c(chr(10).join(engine.vocabulary.words).encode('utf-8')):08x}",
                }
            )
        # admission knobs come from THIS server's config (which may be a
        # replace() of the engine's — e.g. a tighter queue for the same
        # warmed engine), not the engine's defaults
        self.pool: Optional[PagedSlotPool] = None
        if config.serve_mode == "continuous":
            self.pool = PagedSlotPool(
                engine,
                pages=config.serve_slot_pages,
                page_width=config.serve_page_width,
                tel=self._tel,
            )
            self.batcher = ContinuousBatcher(
                engine,
                pool=self.pool,
                queue_depth=config.serve_queue_depth,
                tel=self._tel,
                on_wedge=self._on_wedge,
                wedge_timeout_ms=config.serve_wedge_timeout_ms,
                weights=weights,
                quality=self.quality,
                exemplars=self.exemplars,
            )
        else:
            self.batcher = MicroBatcher(
                engine,
                max_batch=config.serve_max_batch,
                max_wait_ms=config.serve_max_wait_ms,
                queue_depth=config.serve_queue_depth,
                tel=self._tel,
                on_wedge=self._on_wedge,
                wedge_timeout_ms=config.serve_wedge_timeout_ms,
                weights=weights,
                quality=self.quality,
                exemplars=self.exemplars,
            )
        self._host = host if host is not None else config.serve_host
        self._requested_port = (
            port if port is not None else config.serve_port
        )
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._http_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._ready = False
        # admitted /caption requests resident in this process (queued or
        # decoding) — a top-level /healthz load signal for the router's
        # poller alongside queue_depth
        self._in_flight = 0
        self._in_flight_lock = threading.Lock()
        # wedged-batch degraded state (docs/SERVING.md): /healthz reports
        # 503 "degraded" while the engine re-warms after a stuck in-flight
        # batch; requests are still admitted (the batcher is alive) — only
        # the balancer-facing health flips
        self._degraded = False
        self._t_start = time.time()
        self.heartbeat: Optional[Heartbeat] = None
        # fleet observability (telemetry.tracectx/profwin/slo): the
        # request tracer, the live-profile latch, and the SLO engine all
        # share the telemetry dir and the rotating-sink byte cap
        tdir = config.telemetry_dir or os.path.join(
            config.summary_dir, "telemetry"
        )
        cap_bytes = int(config.telemetry_log_cap_mb * 1e6)
        self.tracer = tracectx.RequestTracer(
            path=os.path.join(tdir, "access.jsonl"), cap_bytes=cap_bytes
        )
        self.profiles = ProfileLatch(tdir)
        # cost attribution + capacity plane (telemetry/metering.py,
        # telemetry/capacity.py): the per-tenant ledger, the would-be
        # encode-cache probe, and the headroom model — all host-side
        # arithmetic on already-synced boundaries, only constructed when
        # telemetry is live (attribution rides telemetry-gated windows)
        self.metering: Optional[MeteringLedger] = None
        self.capacity: Optional[CapacityModel] = None
        self._cache_sketch: Optional[EncodeCacheSketch] = None
        if config.serve_metering and self._tel.enabled:
            self.metering = MeteringLedger(
                path=os.path.join(tdir, "metering.jsonl"),
                cap_bytes=cap_bytes,
                tel=self._tel,
            )
            self._cache_sketch = EncodeCacheSketch()
            self.capacity = CapacityModel(
                self._tel,
                self.metering,
                # capacity denominator: decode seats — pool slots in
                # continuous mode, the largest warmed bucket in batch
                # (engine doubles/stubs without buckets fall back to the
                # configured batch ceiling)
                slots=(
                    self.pool.slots
                    if self.pool is not None
                    else max(
                        getattr(engine, "buckets", None)
                        or (config.serve_max_batch,)
                    )
                ),
                sketch=self._cache_sketch,
                # the REAL cache (when --encode_cache on): its measured
                # hit ratio publishes next to the sketch's prediction
                # plus the reconciliation delta
                # getattr: engine doubles in tests don't grow the attr
                cache=getattr(engine, "encode_cache", None),
            )
        self.slo = SLOEngine(
            self._tel,
            objectives_from_config(
                config,
                "serve",
                tenants=self.tenants.slo_lanes(
                    config.slo_serve_p99_ms, config.slo_error_ratio
                ),
            ),
            jsonl_path=os.path.join(tdir, "slo.jsonl"),
            cap_bytes=cap_bytes,
            fast_s=config.slo_window_fast_s,
            slow_s=config.slo_window_slow_s,
        )
        # model-lifecycle plane (sat_tpu/lifecycle): always constructed
        # so the admin endpoints (/reload /promote /rollback) work even
        # without the background poller; the poller thread itself only
        # starts when --model_reload > 0 (controller.start gates it)
        self.lifecycle = LifecycleController(
            config, engine, self.batcher, tel=self._tel
        )

    def _load_residents(self) -> None:
        """Load every registry-declared resident model into the engine
        through the lifecycle loader (integrity + vocab + full-coverage
        guards), each aval-validated against the incumbent so all share
        the warmed AOT executables.  A resident that fails its guards is
        a boot error — a tenant pointed at a model that cannot serve
        must not silently fall back to the incumbent."""
        for alias, path in sorted(self.tenants.models.items()):
            from ..lifecycle.loader import load_candidate

            staged = load_candidate(self.engine, self.config, path)
            self.engine.install_resident(
                alias,
                staged["variables"],
                staged["decoder_params"],
                staged["step"],
                staged["source"],
            )
            print(
                f"sat_tpu: resident model {alias!r} loaded from {path} "
                f"(step {staged['step']})",
                file=sys.stderr,
                flush=True,
            )

    @property
    def port(self) -> Optional[int]:
        return self._httpd.server_address[1] if self._httpd else None

    @property
    def ready(self) -> bool:
        return self._ready

    @property
    def in_flight(self) -> int:
        with self._in_flight_lock:
            return self._in_flight

    # -- request handlers (HTTP worker threads) ----------------------------

    def _finish_request(
        self,
        trace: "tracectx.RequestTrace",
        status: int,
        payload: Dict[str, Any],
        bucket: Optional[int] = None,
        slot: str = canary_mod.INCUMBENT,
        tenant: Optional[str] = None,
        cost=None,
    ) -> Tuple[int, Dict[str, Any]]:
        """Every terminal /caption reply funnels through here: the access
        log gets its record, the SLO error-ratio counters tick, the
        request's attributed cost is charged to its tenant's meter, and
        the payload learns its request id."""
        total_ns = time.perf_counter_ns() - trace.t_start_ns
        with self._in_flight_lock:
            self._in_flight = max(0, self._in_flight - 1)
        self._tel.count("serve/http_requests")
        if status >= 500:
            self._tel.count("serve/http_5xx")
        if tenant is not None and self.tenants.multi:
            # per-tenant SLO lane feed (same pattern as the canary lane
            # below): each tenant's own latency span and error-ratio
            # counters, so one tenant burning its objectives never
            # muddies another's — and the tenant dimension rides the
            # metric NAME, so /metrics exports it with no promtext
            # changes
            self._tel.count(f"serve/tenant_{tenant}_requests")
            if status >= 500:
                self._tel.count(f"serve/tenant_{tenant}_5xx")
            if status == 429:
                self._tel.count(f"serve/tenant_{tenant}_429")
            self._tel.record(
                f"serve/tenant_{tenant}_request", trace.t_start_ns, total_ns
            )
        if slot == canary_mod.CANARY:
            # the canary SLO engine scores ONLY canary-slot traffic: its
            # own latency span and error-ratio counters, so a bad
            # candidate burns its own objectives while the incumbent's
            # serve-phase SLOs stay clean
            self._tel.count("serve/canary_requests")
            if status >= 500:
                self._tel.count("serve/canary_5xx")
            self._tel.record(
                "serve/canary_request", trace.t_start_ns, total_ns
            )
        meter_tenant = tenant if tenant is not None else "default"
        if self.metering is not None:
            # queue/detok host phases lift straight off the trace — no
            # new timing; device phases arrive attributed on ``cost``
            phases = trace.phases
            self.metering.charge(
                meter_tenant,
                cost=cost,
                queue_ms=phases.get("queue_wait", (0, 0))[1] / 1e6,
                detok_ms=phases.get("detok", (0, 0))[1] / 1e6,
                error=status >= 500,
            )
        if self.capacity is not None:
            self.capacity.maybe_update()  # rate-limited; off-interval = one clock read
        self.tracer.finish(
            trace,
            status,
            total_ns,
            bucket=bucket,
            error=payload.get("error"),
            tenant=tenant,
            cost=cost,
        )
        payload["request_id"] = trace.trace_id
        return status, payload

    def handle_encode(self, body: bytes) -> Tuple[int, bytes, str]:
        """``POST /encode`` (the encode tier's request path): JPEG/PNG
        bytes → a framed context grid (serve/handoff.py) a decode-tier
        replica accepts on /caption.  Stateless per request — no slot,
        no queue — so the encode tier scales on batch-friendly replicas
        with zero decode state."""
        t0 = time.perf_counter_ns()

        def _err(status: int, payload: Dict[str, Any]):
            return status, json.dumps(payload).encode(), "application/json"

        if not self._ready:
            return _err(
                503, {"error": "server is draining; not accepting work"}
            )
        try:
            with self._tel.span("serve/preprocess"):
                image = self.engine.preprocess(body)
        except Exception as e:
            self._tel.count("serve/bad_input")
            return _err(
                400,
                {"error": "bad image",
                 "detail": f"cannot decode image bytes: {e}"},
            )
        try:
            grid = self.engine.encode_one(image)
        except Exception as e:
            self._tel.count("serve/encode_http_errors")
            return _err(500, {"error": f"encode failed: {e}"})
        self._tel.count("serve/encode_http")
        self._tel.record(
            "serve/encode_request", t0, time.perf_counter_ns() - t0
        )
        return (
            200,
            handoff.encode_grid(grid, step=self.engine.step),
            handoff.GRID_CONTENT_TYPE,
        )

    def handle_caption(
        self, body: bytes, deadline_ms=None, request_id=None,
        tenant=None, model=None, content_type=None,
    ) -> Tuple[int, Dict[str, Any]]:
        t_req0 = time.perf_counter_ns()
        trace = self.tracer.begin(request_id)
        trace.t_start_ns = t_req0
        with self._in_flight_lock:
            self._in_flight += 1  # paired decrement in _finish_request
        # tenant resolution: X-Tenant → registry spec (bare and unknown
        # keys map to the default tenant).  tname is None on the
        # degenerate single-tenant registry so no per-tenant counters or
        # payload fields appear — zero behavior change without --tenants
        spec = self.tenants.resolve(tenant)
        tname = spec.name if self.tenants.multi else None
        if tenant and tname is not None and not self.tenants.known(tenant):
            self._tel.count("serve/tenant_unknown")
        if not self._ready:
            return self._finish_request(
                trace,
                503,
                {
                    "error": "server is draining; not accepting work",
                    # same backoff contract as a 429 shed: tell the
                    # client when capacity is expected, never 0 seconds
                    "retry_after_ms": self._retry_hint_ms(),
                    "shed_scope": "global",
                },
                tenant=tname,
            )
        # token-bucket admission quota, enforced BEFORE preprocessing so
        # a flooding tenant is refused at the cost of a dict lookup: a
        # dry bucket is a tenant-scoped 429 whose Retry-After is that
        # bucket's own refill time, not the service p50
        if tname is not None and not self.tenants.try_admit(spec.name):
            self._tel.count("serve/shed")
            self._tel.count(f"serve/tenant_{spec.name}_shed")
            return self._finish_request(
                trace,
                429,
                {
                    "error": (
                        f"tenant {spec.name!r} admission quota exhausted "
                        f"({spec.rps:g} rps); shed"
                    ),
                    "retry_after_ms": self._tenant_retry_ms(spec.name),
                    "shed_scope": "tenant",
                },
                tenant=tname,
            )
        image = None
        context = None
        key = None
        base_ctype = (content_type or "").split(";", 1)[0].strip()
        if base_ctype == handoff.GRID_CONTENT_TYPE:
            # decode-tier ingress: the body is a pre-encoded context grid
            # from an encode-tier replica (serve/handoff.py) — verify the
            # frame (crc32c sidecar) and the aval against OUR warmed
            # executables before any device work
            try:
                grid, header = handoff.decode_grid(body)
                if self.engine.ctx_row_shape is None:
                    raise handoff.HandoffError(
                        "replica has no warmed context aval yet"
                    )
                handoff.check_aval(
                    grid, self.engine.ctx_row_shape,
                    self.engine.ctx_row_dtype,
                )
            except handoff.HandoffError as e:
                self._tel.count("serve/bad_handoff")
                return self._finish_request(
                    trace, 400,
                    {"error": "bad grid", "detail": str(e)},
                    tenant=tname,
                )
            gstep = header.get("step")
            if gstep is not None and int(gstep) != self.engine.step:
                # cross-generation handoff: the encoder ran a different
                # promote generation than this decoder — decoding it
                # would caption with mismatched params
                self._tel.count("serve/stale_handoff")
                return self._finish_request(
                    trace, 409,
                    {
                        "error": (
                            f"grid encoded at model step {gstep}; this "
                            f"replica serves step {self.engine.step}"
                        ),
                    },
                    tenant=tname,
                )
            context = grid
            self._tel.count("serve/grid_requests")
        else:
            try:
                with self._tel.span("serve/preprocess"):
                    image = self.engine.preprocess(body)
            except Exception as e:
                # undecodable POST body: a client problem, not a server
                # crash — counted so a flood of garbage uploads shows in
                # the heartbeat
                self._tel.count("serve/bad_input")
                return self._finish_request(
                    trace,
                    400,
                    {
                        "error": "bad image",
                        "detail": f"cannot decode image bytes: {e}",
                    },
                    tenant=tname,
                )
            if self._cache_sketch is not None:
                # would-be encode-cache probe (telemetry/capacity.py):
                # hash the raw POST bytes (no pixels retained) and ask
                # whether a bounded cache would have hit — the live Zipf
                # evidence the real cache below now reconciles against
                self._cache_sketch.observe(crc32c(body))
            if getattr(self.engine, "encode_cache", None) is not None:
                # content address for the REAL cache: the preprocessed
                # pixels (two byte-identical uploads of one image hash
                # equal here even if their container bytes differ)
                key = crc32c(image.tobytes())
        if deadline_ms is None or deadline_ms == "":
            budget_ms = self.config.serve_deadline_ms
        else:
            try:
                budget_ms = int(deadline_ms)
            except (TypeError, ValueError):
                return self._finish_request(
                    trace,
                    400,
                    {"error": "X-Deadline-Ms must be integer milliseconds"},
                    tenant=tname,
                )
        deadline_unix = (
            time.time() + budget_ms / 1e3 if budget_ms > 0 else None
        )
        # param-slot selection: an explicit X-Model (or the tenant's
        # default model) pins a resident param set; otherwise the
        # lifecycle canary router decides (a deterministic, sticky hash
        # of the request id — outside a canary window every request is
        # incumbent)
        alias = (model or "").strip() or spec.model
        if alias:
            if not self.engine.has_resident(alias):
                return self._finish_request(
                    trace,
                    400,
                    {
                        "error": f"unknown model {alias!r}",
                        "models": list(self.engine.resident_aliases),
                    },
                    tenant=tname,
                )
            slot = alias
        else:
            slot = self.lifecycle.route(trace.trace_id)
        try:
            req = self.batcher.submit(
                image, deadline_unix=deadline_unix, trace=trace, slot=slot,
                tenant=spec.name, raw=body, key=key, context=context,
            )
        except Rejected as e:
            # shed exemplar: a rate-limited sample of refused requests
            # lands in the flight recorder with its image bytes, so a
            # shed storm leaves replayable evidence, not just a counter
            self._record_terminal_exemplar(
                trace, e.status, "shed", tname, body
            )
            payload = {"error": e.reason}
            if e.status in (429, 503):
                # Retry-After computed from the SHEDDING SCOPE: a
                # tenant-lane shed hints the tenant's own bucket refill,
                # a global shed hints the observed service period
                payload["shed_scope"] = e.scope
                payload["retry_after_ms"] = (
                    self._tenant_retry_ms(spec.name)
                    if e.scope == "tenant"
                    else self._retry_hint_ms()
                )
            return self._finish_request(
                trace, e.status, payload, slot=slot, tenant=tname
            )
        wait_s = (
            budget_ms / 1e3 + 5.0 if deadline_unix else self.DEFAULT_WAIT_S
        )
        if not req.done.wait(timeout=wait_s):
            self._tel.count("serve/timeouts")
            self._record_terminal_exemplar(trace, 504, "timeout", tname, body)
            # the request may still be riding decode windows; charge
            # whatever device time it accrued so far — abandoned work is
            # still the tenant's cost
            return self._finish_request(
                trace, 504, {"error": "request timed out in service"},
                slot=slot, tenant=tname, cost=req.cost,
            )
        if req.error is not None:
            payload = {"error": req.error[1]}
            if req.error[0] in (429, 503):
                payload["retry_after_ms"] = self._retry_hint_ms()
                payload["shed_scope"] = "global"
            return self._finish_request(
                trace, req.error[0], payload, bucket=req.bucket, slot=slot,
                tenant=tname, cost=req.cost,
            )
        self._tel.record(
            "serve/request", t_req0, time.perf_counter_ns() - t_req0
        )
        payload = dict(req.result)
        payload["bucket"] = req.bucket
        payload["slot"] = slot
        if tname is not None:
            payload["tenant"] = tname
        if slot == canary_mod.CANARY:
            step = self.engine.candidate_step
            payload["model_step"] = (
                step if step is not None else self.engine.step
            )
        elif alias:
            payload["model"] = alias
            step = self.engine.resident_step(alias)
            payload["model_step"] = (
                step if step is not None else self.engine.step
            )
        else:
            payload["model_step"] = self.engine.step
            # shadow sampling: during a canary window, a sample of
            # incumbent answers is replayed against the candidate to
            # feed the caption-divergence gauge (bounded queue, never
            # blocks this handler thread).  Grid-ingress requests carry
            # no image to replay, so they never shadow.
            if image is not None:
                try:
                    self.lifecycle.maybe_shadow(
                        image, payload["captions"][0]["caption"]
                    )
                except (KeyError, IndexError, TypeError):
                    pass
        return self._finish_request(
            trace, 200, payload, bucket=req.bucket, slot=slot, tenant=tname,
            cost=req.cost,
        )

    def _record_terminal_exemplar(
        self,
        trace: "tracectx.RequestTrace",
        status: int,
        reason: str,
        tenant: Optional[str],
        body: bytes,
    ) -> None:
        """Shed/timeout outliers never reach the detok boundary, so the
        HTTP path records them directly (rate-limited by the recorder;
        failures swallowed — observability never fails a request)."""
        if self.exemplars is None:
            return
        try:
            self.exemplars.record(
                reasons=[reason],
                request_id=trace.trace_id,
                tenant=tenant or "default",
                status=status,
                image_bytes=body,
            )
        except Exception:
            self._tel.count("serve/quality_errors")

    def _retry_hint_ms(self) -> int:
        """Retry-After hint for 429 sheds: about one service period — the
        observed p50 end-to-end latency when we have one, else twice the
        batching window — clamped to a sane band so a cold server never
        tells clients to hammer it or to go away for minutes."""
        p = _percentiles_ms(self._tel, "serve/request")
        hint = (
            p["p50"] if p else 2.0 * max(1.0, self.config.serve_max_wait_ms)
        )
        return int(min(10_000.0, max(50.0, hint)))

    def _tenant_retry_ms(self, name: str) -> int:
        """Retry-After hint for a *tenant-scoped* shed: that tenant's
        own bucket refill time — when its next token exists — not the
        global service period.  Never 0 (the frontend's whole-second
        clamp rounds it up to >= 1 s on the header)."""
        return max(1, int(self.tenants.retry_after_s(name) * 1000.0) + 1)

    def healthz(self) -> Tuple[Dict[str, Any], int]:
        payload = self.heartbeat.payload() if self.heartbeat else {}
        # two degrade causes (docs/RESILIENCE.md): a wedged batch being
        # re-warmed, and a burning SLO — both flip the balancer-facing
        # health while requests are still admitted
        burning = self.slo.burning()
        # tenant-scoped lanes never degrade the replica's fleet-facing
        # health: one tenant burning ITS objective (a flood eating its
        # own quota) must not get the whole replica down-weighted — that
        # would spread tenant A's overload onto tenant B, the exact
        # failure the isolation plane exists to prevent.  The lanes stay
        # visible in slo_burning / /metrics for per-tenant alerting.
        # quality_* lanes are diagnostic the same way: caption drift is
        # a MODEL problem — rolling traffic to a replica serving the
        # same checkpoint fixes nothing, so /healthz stays ok while the
        # drift lanes burn (pinned by the quality_drift chaos scenario).
        service_burning = [
            n for n in burning if not n.startswith(("tenant_", "quality_"))
        ]
        degraded = self._degraded or bool(service_burning)
        payload.update(
            {
                "ready": self._ready,
                "status": (
                    "degraded"
                    if degraded
                    else ("ok" if self._ready else "draining")
                ),
                "uptime_s": round(time.time() - self._t_start, 1),
                # top-level load signals (queue + resident requests +
                # dispatch mode): the fleet router's poller reads these
                # from ONE cheap /healthz fetch per tick instead of the
                # heavier /stats document
                "queue_depth": self.batcher.queue_depth(),
                "in_flight": self.in_flight,
                "serve_mode": self.config.serve_mode,
                # fleet tier (encode/decode/both): the router's poller
                # routes image traffic to encode-capable replicas and
                # grid handoffs to decode-capable ones off this field
                "tier": self.config.serve_tier,
                "buckets": list(self.engine.buckets),
                "model_step": self.engine.step,
                # lifecycle plane: balancers and the fleet router see a
                # canary in flight from the same cheap poll
                "lifecycle_state": self.lifecycle.state,
            }
        )
        candidate = self.engine.candidate_step
        if candidate is not None:
            payload["candidate_step"] = candidate
        if self.tenants.multi:
            payload["tenants"] = sorted(self.tenants.names())
        if burning:
            payload["slo_burning"] = burning
        return payload, (200 if self._ready and not degraded else 503)

    def admin_lifecycle(self, action: str) -> Tuple[int, Dict[str, Any]]:
        """POST /reload | /promote | /rollback.  200 on success, 409 when
        the machine is in the wrong state for the verb (no candidate to
        promote, a cycle already in flight, a rejected/current step)."""
        lc = self.lifecycle
        if action == "reload":
            ok, detail = lc.request_reload()
        elif action == "promote":
            ok, detail = lc.promote()
        elif action == "rollback":
            ok, detail = lc.rollback()
        else:
            return 404, {"error": f"no lifecycle action {action!r}"}
        return (200 if ok else 409), {
            "ok": ok,
            "detail": detail,
            "state": lc.state,
            "model_step": self.engine.step,
        }

    # -- wedge containment (called from the batcher thread) ----------------

    def _on_wedge(self) -> None:
        """A stuck in-flight batch was just failed with 500s: flip health
        to 503 "degraded" so the balancer routes away, and re-warm the
        engine in the background — the AOT warmup rebuilds the compiled
        ladder (cheap under the persistent compile cache) and proves the
        device answers again before health recovers."""
        self._degraded = True
        self._tel.gauge("serve/degraded", 1)
        threading.Thread(
            target=self._rewarm, name="sat-serve-rewarm", daemon=True
        ).start()

    def _rewarm(self) -> None:
        try:
            if self.config.serve_mode == "continuous":
                # re-warm the slot pool (cached compiles) and rebuild the
                # empty carry; in-flight slots were already failed
                self.batcher.rewarm()
            else:
                self.engine.warmup()
        except Exception as e:
            # still wedged — stay degraded; the next wedge timeout (or an
            # operator) escalates
            print(
                f"sat_tpu: serve re-warm failed ({e!r}); staying degraded",
                file=sys.stderr,
                flush=True,
            )
            return
        self._tel.count("serve/rewarms")
        self._degraded = False
        self._tel.gauge("serve/degraded", 0)
        print(
            "sat_tpu: serve engine re-warmed after wedged batch; health "
            "restored",
            file=sys.stderr,
            flush=True,
        )

    def stats(self) -> Dict[str, Any]:
        counters = self._tel.counters()
        prefix = "serve/bucket_"
        histogram = {
            k[len(prefix):]: v
            for k, v in counters.items()
            if k.startswith(prefix)
        }
        latency = {}
        for name in _LATENCY_SPANS:
            p = _percentiles_ms(self._tel, name)
            if p:
                latency[name] = p
        out = {
            "ready": self._ready,
            "serve_mode": self.config.serve_mode,
            "tier": self.config.serve_tier,
            "queue_depth": self.batcher.queue_depth(),
            "in_flight": self.in_flight,
            "buckets": list(self.engine.buckets),
            "bucket_histogram": histogram,
            "warm_compiles": self.engine.warm_compiles,
            "compiles_since_ready": counters.get("jax/compiles", 0)
            - self.engine.compiles_at_ready,
            "counters": {
                k: v
                for k, v in counters.items()
                if k.startswith(("serve/", "jax/"))
            },
            "latency_ms": latency,
            "slo": self.slo.snapshot(),
            "profile_captures": self.profiles.captures,
            "lifecycle": self.lifecycle.snapshot(),
        }
        # raw loop-iteration counts, not ms — how many decode steps each
        # request actually ran (continuous mode retires early; batch mode
        # reports the per-batch monolithic step count)
        steps = _percentiles_raw(self._tel, "serve/decode_steps")
        if steps:
            out["decode_steps"] = steps
        # encoder introspection: the active quant mode plus per-lane
        # encode timing (batch mode records per-bucket lanes, continuous
        # mode per admission-lane width; both feed the aggregate span)
        engine_block: Dict[str, Any] = {
            "device": self.engine.device,
            "encoder_quant": self.engine.encoder_quant,
            "quantize_seconds": round(self.engine.quantize_seconds, 3),
        }
        # fused decode window: how many device steps each dispatch
        # actually ran (the K ladder + on-device early exit live;
        # docs/SERVING.md "Fused decode window")
        spd = _percentiles_raw(self._tel, "serve/steps_per_dispatch")
        if spd:
            engine_block["steps_per_dispatch"] = spd
        enc = _percentiles_ms(self._tel, "serve/encode")
        if enc:
            engine_block["encode_ms"] = enc
        lanes = {}
        for lane in self._encode_lanes():
            p = _percentiles_ms(self._tel, f"serve/encode_lane{lane}")
            if p:
                lanes[str(lane)] = p
        if lanes:
            engine_block["encode_lanes_ms"] = lanes
        out["engine"] = engine_block
        if self.pool is not None:
            out["slot_pool"] = {
                "slots": self.pool.slots,
                "pages": self.pool.pages,
                "page_width": self.pool.width,
                "busy": self.pool.occupancy(),
            }
        if getattr(self.engine, "encode_cache", None) is not None:
            # the cache block: host LRU state + lifetime counters, plus
            # the hit path's own device latency (gather) so operators see
            # what a hit actually costs vs the encode it skipped
            cache_block = self.engine.encode_cache.stats()
            gp = _percentiles_ms(self._tel, "serve/cache_gather")
            if gp:
                cache_block["gather_ms"] = gp
            out["encode_cache"] = cache_block
        if self.tenants.multi:
            out["tenants"] = self._tenant_block(counters)
        if self.metering is not None:
            # per-tenant attributed cost (telemetry/metering.py) — the
            # router fans this block in for the fleet-wide view; present
            # with one "default" row on single-tenant servers too
            out["tenants_cost"] = self.metering.snapshot()
        if self.capacity is not None:
            self.capacity.maybe_update()
            out["capacity"] = {
                name.split("/", 1)[1]: value
                for name, value in self._tel.gauges().items()
                if name.startswith("capacity/")
            }
        if self.quality is not None:
            # per-request quality signals + drift vs the frozen
            # reference (telemetry/quality.py); the router fans this
            # block into the fleet view like tenants_cost
            self.quality.maybe_publish(force=True)
            qblock = self.quality.snapshot()
            if self.exemplars is not None:
                qblock["exemplars"] = self.exemplars.stats()
            out["quality"] = qblock
        return out

    def _tenant_block(self, counters: Dict[str, int]) -> Dict[str, Any]:
        """Per-tenant /stats block: static shape (weight/quota/model)
        plus live queue depth, token balance, request/shed/5xx counters
        and latency percentiles.  Refreshes the serve/tenant_* gauges so
        the heartbeat serve block and /metrics carry the same numbers."""
        depths = self.batcher.tenant_depths()
        admitted = self.batcher.tenant_admitted()
        block: Dict[str, Any] = {}
        for name, shape in self.tenants.describe().items():
            entry = dict(shape)
            entry["queue_depth"] = depths.get(name, 0)
            entry["admitted"] = admitted.get(name, 0)
            tokens = self.tenants.tokens(name)
            if tokens is not None and tokens != float("inf"):  # sync-ok: host sentinel
                entry["tokens"] = round(tokens, 2)
                self._tel.gauge(
                    f"serve/tenant_{name}_tokens", round(tokens, 2)
                )
            self._tel.gauge(
                f"serve/tenant_{name}_queue_depth", depths.get(name, 0)
            )
            for short, counter in (
                ("requests", f"serve/tenant_{name}_requests"),
                ("shed", f"serve/tenant_{name}_shed"),
                ("429", f"serve/tenant_{name}_429"),
                ("5xx", f"serve/tenant_{name}_5xx"),
            ):
                entry[short] = counters.get(counter, 0)
            step = (
                self.engine.resident_step(shape["model"])
                if shape["model"]
                else None
            )
            if step is not None:
                entry["model_step"] = step
            p = _percentiles_ms(self._tel, f"serve/tenant_{name}_request")
            if p:
                entry["latency_ms"] = p
            block[name] = entry
        return block

    def _encode_lanes(self):
        """Every encode-lane width this server can have timed: the bucket
        ladder (batch mode) plus the pool's admission lanes (continuous)."""
        lanes = set(self.engine.buckets)
        if self.pool is not None:
            lanes.update(self.pool.lane_widths)
        return sorted(lanes)

    # -- observability endpoints -------------------------------------------

    def metrics_text(self) -> str:
        """The Prometheus exposition body for ``GET /metrics``."""
        # refresh the decode-step distribution gauges at scrape time so
        # both serve modes export them without a per-request hot-path cost
        steps = _percentiles_raw(self._tel, "serve/decode_steps")
        if steps:
            self._tel.gauge("serve/decode_steps_p50", steps["p50"])
            self._tel.gauge("serve/decode_steps_p95", steps["p95"])
        spd = _percentiles_raw(self._tel, "serve/steps_per_dispatch")
        if spd:
            # fused-window amortization: device steps per host dispatch
            # (p50 tracks the chosen K ladder lane, p95 the deep lane)
            self._tel.gauge("serve/steps_per_dispatch", spd["p50"])
            self._tel.gauge("serve/steps_per_dispatch_p95", spd["p95"])
        enc = _percentiles_ms(self._tel, "serve/encode")
        if enc:
            # scrape-time refresh, same discipline as decode_steps: the
            # serve/encode_ms gauge is the p50 device-encode time (p95
            # rides alongside for burn-rate style alerting)
            self._tel.gauge("serve/encode_ms", enc["p50"])
            self._tel.gauge("serve/encode_ms_p95", enc["p95"])
        if self.tenants.multi:
            # refresh the serve/tenant_* queue/token gauges at scrape
            # time (the tenant dimension rides the metric name, so
            # promtext exports them with no label machinery)
            self._tenant_block(self._tel.counters())
        if getattr(self.engine, "encode_cache", None) is not None:
            # scrape-time refresh of the cache residency gauges (the
            # counters tick live; entries/bytes are host-map reads)
            cstats = self.engine.encode_cache.stats()
            self._tel.gauge("serve/cache_entries", cstats["entries"])
            self._tel.gauge("serve/cache_bytes", cstats["bytes"])
            self._tel.gauge("serve/cache_hit_ratio", cstats["hit_ratio"])
            gp = _percentiles_ms(self._tel, "serve/cache_gather")
            if gp:
                self._tel.gauge("serve/cache_gather_ms_p95", gp["p95"])
        if self.capacity is not None:
            # scrape-time refresh of the capacity/* gauges (headroom,
            # ceiling, lane fill, would-hit + actual hit ratios) —
            # rate-limited, so an aggressive scraper costs one clock read
            self.capacity.maybe_update()
        if self.quality is not None:
            # scrape-time refresh of the quality/* gauges (per-signal
            # PSI, psi_max, unk rate) so the drift SLO lanes and the
            # Prometheus series never lag the rate limiter
            self.quality.maybe_publish(force=True)
        extra = self.heartbeat.payload() if self.heartbeat else None
        return promtext.render(self._tel, extra=extra, histograms=_HISTOGRAMS)

    def quality_reference(self) -> Tuple[Dict[str, Any], int]:
        """GET /quality_reference: export the frozen reference so another
        replica (or the next deploy) can pin drift scoring to THIS
        steady state via ``--quality_reference``.  404 with the quality
        plane off, 409 before warmup traffic froze a reference."""
        if self.quality is None:
            return {"error": "quality plane off; boot with --serve_quality on"}, 404
        payload = self.quality.reference_payload()
        if payload is None:
            return {
                "error": (
                    "no reference frozen yet; serve at least "
                    f"{self.quality.window} requests or load one with "
                    "--quality_reference"
                )
            }, 409
        return payload, 200

    def start_profile(self, duration_ms=None) -> Tuple[bool, str]:
        """Begin a bounded live profiler capture (``POST /profile``);
        409-maps when one is already running."""
        ok, info = self.profiles.start(duration_ms)
        if ok:
            self._tel.count("serve/profile_windows")
        return ok, info

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome trace including one lane per retained request
        (tests call this directly; shutdown calls it when
        ``--trace_export`` is set)."""
        from ..telemetry import exporters

        if path is None:
            path = self.config.trace_export
        if not path:
            return None
        return exporters.export_chrome_trace(
            self._tel,
            path,
            extra_events=self.tracer.trace_events(
                getattr(self._tel, "anchor_ns", 0),
                # same lane convention as the host spans (exporters
                # .chrome_trace): pid = process_index, so request lanes
                # land in this host's process group after a fleet merge
                pid=telemetry.process_identity()[0],
            ),
        )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "CaptionServer":
        self.batcher.start()
        self._httpd = ThreadingHTTPServer(
            (self._host, self._requested_port), _Handler
        )
        self._httpd.app = self
        self._httpd.daemon_threads = True
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="sat-serve-http",
            daemon=True,
        )
        self._http_thread.start()
        if self.config.heartbeat_interval > 0:
            hb_dir = self.config.telemetry_dir or os.path.join(
                self.config.summary_dir, "telemetry"
            )
            try:
                os.makedirs(hb_dir, exist_ok=True)
                self.heartbeat = Heartbeat(
                    os.path.join(hb_dir, "heartbeat.json"),
                    self.config.heartbeat_interval,
                    self._tel,
                    static={
                        "phase": "serve",
                        "port": self.port,
                        "buckets": list(self.engine.buckets),
                        "model_step": self.engine.step,
                    },
                ).start()
            except OSError:
                self.heartbeat = None  # health still served from /healthz
        if self.slo.objectives:
            # tick a few times per fast window so a burn is seen promptly
            self.slo.start(
                interval_s=max(0.1, min(5.0, self.config.slo_window_fast_s / 4))
            )
        self.lifecycle.start()
        if self.config.serve_tier == "encode":
            # an encode-tier replica's whole request path is POST /encode:
            # warm its width-1 executable before ready so the first
            # request never compiles, and extend the zero-recompile
            # ledger past it (same bookkeeping as the pool warmup)
            self.engine.warm_encode_one()
            self.engine.compiles_at_ready = max(
                self.engine.compiles_at_ready,
                self._tel.counters().get("jax/compiles", 0),
            )
        self._ready = True
        self._tel.gauge("serve/ready", 1)
        return self

    def request_shutdown(self) -> None:
        """Programmatic twin of SIGTERM (tests, embedding)."""
        self._stop.set()

    def shutdown(self) -> None:
        """Drain sequence: readiness flips first (the balancer stops
        routing), the batcher rejects new work and completes everything
        admitted, then the listener and heartbeat close."""
        if self._httpd is None:
            return
        self._ready = False
        self._tel.gauge("serve/ready", 0)
        # stop the lifecycle plane before draining the batcher: an
        # in-flight canary aborts (candidate cleared, ledger untouched —
        # shutdown is not a verdict) so the drain sees only real work
        self.lifecycle.stop()
        self.batcher.drain()
        self._httpd.shutdown()
        if self._http_thread is not None:
            self._http_thread.join(timeout=10.0)
            self._http_thread = None
        self._httpd.server_close()
        self._httpd = None
        self.slo.stop()
        self.profiles.stop_now()
        if self.metering is not None:
            # final cumulative ledger rows — the shutdown snapshot a
            # billing job replays (torn tails before this lose only
            # recency, never correctness)
            self.metering.maybe_flush(force=True)
        self.export_trace()  # no-op unless --trace_export is set
        if self.heartbeat is not None:
            self.heartbeat.stop()

    def serve_until_shutdown(self, shutdown=None, poll_s: float = 0.1) -> None:
        """Block until SIGTERM/SIGINT or request_shutdown(), then drain.
        ``shutdown`` accepts an externally managed GracefulShutdown (tests
        install one on the main thread); by default one is installed
        here."""
        own = shutdown is None
        sd = GracefulShutdown() if own else shutdown
        try:
            if own:
                sd.__enter__()
            while not sd.stop_requested and not self._stop.is_set():
                time.sleep(poll_s)
        finally:
            if own:
                sd.__exit__(None, None, None)
            self.shutdown()


def serve(config: Config, model_file: Optional[str] = None) -> int:
    """CLI entry point: ``python -m sat_tpu.cli --phase serve``.

    Lineage load → AOT bucket warmup → listen → drain on SIGTERM."""
    tel = telemetry.get()
    if not tel.enabled:
        # /stats and /healthz are part of the serving contract: spans and
        # counters always record in this phase (host-side work only — the
        # tracing layer's measured overhead bar applies, no device syncs)
        tel = telemetry.enable(capacity=config.telemetry_buffer)
    from ..runtime import _install_compile_listener

    _install_compile_listener()

    vocabulary = Vocabulary(config.vocabulary_size, config.vocabulary_file)
    state, source = load_serving_state(config, model_file=model_file)
    engine = ServeEngine(config, state, vocabulary, tel=tel)
    print(
        f"sat_tpu: serving params from {source} (step {engine.step})",
        file=sys.stderr,
        flush=True,
    )
    if config.serve_mode == "batch":
        # continuous mode warms the slot-pool programs instead (in
        # ContinuousBatcher.start, via the server below) — the bucket
        # ladder would be dead weight there
        engine.warmup()
    server = CaptionServer(config, engine)
    # flight recorder (telemetry/blackbox.py): journal serve state so an
    # abnormal exit leaves a postmortem bundle like a training run's
    bb = None
    if config.blackbox:
        from ..telemetry import blackbox as _blackbox

        tdir = config.telemetry_dir or os.path.join(
            config.summary_dir, "telemetry"
        )
        bb = _blackbox.BlackBox(os.path.join(tdir, "blackbox"), tel)
        _blackbox.install(
            bb, telemetry_dir=tdir, config_snapshot=config.to_dict()
        )
        bb.event("serve_start", port=server.port, model_step=engine.step)
    server.start()
    if config.serve_mode == "continuous":
        geometry = (
            f"slot pool {config.serve_slot_pages}x{config.serve_page_width}"
        )
    else:
        geometry = (
            f"buckets {engine.buckets}, max_batch {config.serve_max_batch}, "
            f"max_wait {config.serve_max_wait_ms}ms"
        )
    print(
        f"sat_tpu: captioning server listening on "
        f"http://{config.serve_host}:{server.port}  "
        f"(mode {config.serve_mode}, {geometry})",
        file=sys.stderr,
        flush=True,
    )
    if server.tenants.multi:
        shapes = ", ".join(
            f"{s.name}(w={s.weight:g}"
            + (f", {s.rps:g}rps" if s.limited else "")
            + (f", model={s.model}" if s.model else "")
            + ")"
            for s in server.tenants.specs()
        )
        print(
            f"sat_tpu: multi-tenant plane active — {shapes}; "
            f"default tenant {server.tenants.default!r}",
            file=sys.stderr,
            flush=True,
        )
    try:
        server.serve_until_shutdown()
    except Exception as e:
        if bb is not None:
            from ..telemetry import blackbox as _blackbox

            bb.event("uncaught_exception", error=repr(e))
            _blackbox.dump("uncaught_exception", error=repr(e))
        raise
    if bb is not None:
        bb.event("serve_drained")
    print("sat_tpu: serve drained cleanly", file=sys.stderr, flush=True)
    return 0
