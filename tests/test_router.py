"""Fleet router tests (sat_tpu/serve/router.py).

Three layers, cheapest first:

* pure routing math — weight/effective-load/pick/merge_fleet driven
  directly, no sockets;
* scripted stub replicas — real HTTP upstreams whose /healthz, /stats
  and /caption replies are mutable dicts, so retry/shed/drain paths run
  against real sockets without a jax engine;
* end-to-end — two real CaptionServers behind a real Router HTTP
  process: request-id stitching across the hop (router access.jsonl +
  exactly one replica access.jsonl) and zero steady-state recompiles.
"""

import json
import os
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from sat_tpu import telemetry
from sat_tpu.config import Config
from sat_tpu.serve.replica import Endpoint, parse_endpoints
from sat_tpu.serve.router import (
    Router,
    effective_load,
    merge_fleet,
    pick_replica,
    replica_weight,
)
from sat_tpu.telemetry import tracectx

# ---------------------------------------------------------------------------
# One process for each chip (LocalFleet on a TPU host, stub children)
# ---------------------------------------------------------------------------


@pytest.fixture
def tpu_host(monkeypatch, tmp_path):
    """A host whose PCI bus shows four v5e chips and one other device,
    and a LocalFleet whose children are recorded, not started."""
    from sat_tpu.serve import replica

    bus = tmp_path / "pci"
    for i, (vendor, device) in enumerate(
        [("0x1ae0", "0x0063")] * 4 + [("0x8086", "0x1237")]
    ):
        slot = bus / f"0000:00:0{i}.0"
        slot.mkdir(parents=True)
        (slot / "vendor").write_text(vendor + "\n")
        (slot / "device").write_text(device + "\n")
    real_glob = replica.glob.glob
    monkeypatch.setattr(
        replica.glob, "glob",
        lambda pattern: real_glob(str(bus / "*" / "vendor"))
        if pattern.startswith("/sys/bus/pci") else real_glob(pattern),
    )
    spawned = []

    class StubChild:
        def __init__(self, argv, env=None, **kwargs):
            spawned.append(env)

        def poll(self):
            return 0

    monkeypatch.setattr(replica.subprocess, "Popen", StubChild)
    monkeypatch.delenv(replica.CHIP_ENV, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    return replica, spawned, tmp_path


def test_local_fleet_gives_each_child_its_own_chip(tpu_host, monkeypatch):
    replica, spawned, tmp_path = tpu_host
    assert replica.local_tpu_chips() == ["0", "1", "2", "3"]
    fleet = replica.LocalFleet(Config(), 4, root=str(tmp_path / "fleet"))
    assert [env[replica.CHIP_ENV] for env in spawned] == ["0", "1", "2", "3"]
    for env in spawned:
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    # a respawned replica keeps its index, hence its chip
    fleet.respawn("r2")
    assert spawned[-1][replica.CHIP_ENV] == "2"
    # an outer restriction is what there is to give
    monkeypatch.setenv(replica.CHIP_ENV, "2,3")
    del spawned[:]
    replica.LocalFleet(Config(), 2, root=str(tmp_path / "fleet2"))
    assert [env[replica.CHIP_ENV] for env in spawned] == ["2", "3"]
    # off the TPU nothing changes: children inherit the environment
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv(replica.CHIP_ENV)
    del spawned[:]
    replica.LocalFleet(Config(), 5, root=str(tmp_path / "fleet3"))
    assert len(spawned) == 5
    assert all(replica.CHIP_ENV not in env for env in spawned)


def test_local_fleet_refuses_more_tpu_replicas_than_chips(tpu_host):
    replica, spawned, tmp_path = tpu_host
    with pytest.raises(ValueError, match="5 TPU replicas.*4 chip"):
        replica.LocalFleet(Config(), 5, root=str(tmp_path / "fleet"))
    assert not spawned  # refused before anything started


# ---------------------------------------------------------------------------
# Pure routing math
# ---------------------------------------------------------------------------


def test_replica_weight_multiplies_per_signal():
    assert replica_weight(False, False, 0.25) == 1.0
    assert replica_weight(True, False, 0.25) == 0.25
    assert replica_weight(False, True, 0.25) == 0.25
    # degraded straggler: doubly discounted but never zero
    assert replica_weight(True, True, 0.25) == pytest.approx(0.0625)


def test_effective_load_placement_and_weighting():
    # the +1 is the request being placed: an idle down-weighted replica
    # ranks below an idle healthy one instead of tying at 0
    assert effective_load(0, 0, 1.0) == 1.0
    assert effective_load(0, 0, 0.25) == 4.0
    assert effective_load(3, 2, 1.0) == 6.0
    # negative signals from a confused replica clamp instead of helping
    assert effective_load(-5, -5, 1.0) == 1.0
    assert effective_load(0, 0, 0.0) == float("inf")  # sync-ok: host sentinel


def test_pick_replica_least_load_with_hysteresis():
    loads = {"r0": 2.0, "r1": 1.0}
    assert pick_replica(loads, None, 0.25) == "r1"
    # sticky: last stays while within (1 + hysteresis) of the best
    assert pick_replica({"r0": 1.2, "r1": 1.0}, "r0", 0.25) == "r0"
    # beyond the band the pick flips
    assert pick_replica({"r0": 1.3, "r1": 1.0}, "r0", 0.25) == "r1"
    # a vanished last falls through to the best
    assert pick_replica(loads, "gone", 0.25) == "r1"
    assert pick_replica({}, None, 0.25) is None


def _snap(**kw):
    base = {
        "reachable": True,
        "ready": True,
        "status": "ok",
        "degraded": False,
        "queue_depth": 0,
        "in_flight": 0,
        "p50_ms": None,
        "p99_ms": None,
    }
    base.update(kw)
    return base


def test_merge_fleet_degraded_down_weighted_not_blackholed():
    view = merge_fleet(
        {
            "r0": _snap(status="degraded", degraded=True),
            "r1": _snap(queue_depth=5),
        },
        {"r0": "in", "r1": "in"},
        straggler_factor=2.0,
        down_weight=0.25,
    )
    assert view["routable"] == ["r0", "r1"]
    # idle degraded: 1/0.25 = 4; healthy with 5 queued: 6 — the degraded
    # replica still absorbs load when the healthy one is deeper
    assert view["replicas"]["r0"]["effective_load"] == pytest.approx(4.0)
    assert view["replicas"]["r1"]["effective_load"] == pytest.approx(6.0)
    assert view["queue_depth"] == 5


def test_merge_fleet_straggler_ruling_uses_routable_p99s():
    view = merge_fleet(
        {
            "r0": _snap(p50_ms=100.0, p99_ms=100.0),
            "r1": _snap(p50_ms=110.0, p99_ms=120.0),
            "r2": _snap(p50_ms=150.0, p99_ms=900.0),
        },
        {"r0": "in", "r1": "in", "r2": "in"},
        straggler_factor=2.0,
        down_weight=0.5,
    )
    assert view["straggler"]["verdict"] is True
    assert view["straggler"]["name"] == "r2"
    assert view["replicas"]["r2"]["straggler"] is True
    assert view["replicas"]["r2"]["weight"] == pytest.approx(0.5)
    assert view["replicas"]["r0"]["weight"] == 1.0
    # fleet p50 is the median over routable replicas' request p50s
    assert view["fleet_p50_ms"] == pytest.approx(110.0)


def test_merge_fleet_drain_and_unreachable_leave_rotation():
    view = merge_fleet(
        {
            "r0": _snap(),
            "r1": _snap(reachable=False, ready=False, status="unreachable"),
            "r2": _snap(),
        },
        {"r0": "in", "r1": "in", "r2": "draining"},
        straggler_factor=2.0,
        down_weight=0.25,
    )
    assert view["routable"] == ["r0"]
    assert view["replicas"]["r1"]["routable"] is False
    assert view["replicas"]["r2"]["drain_state"] == "draining"
    assert view["replicas"]["r2"]["effective_load"] is None


def test_config_validates_route_knobs():
    Config(phase="route")  # route is a legal phase
    with pytest.raises(ValueError):
        Config(route_num_replicas=0)
    with pytest.raises(ValueError):
        Config(route_hysteresis=-0.1)
    with pytest.raises(ValueError):
        Config(route_down_weight=0.0)  # zero would blackhole
    with pytest.raises(ValueError):
        Config(route_down_weight=1.5)
    with pytest.raises(ValueError):
        Config(route_poll_interval_s=0.0)
    with pytest.raises(ValueError):
        Config(route_upstream_timeout_s=0.0)


def test_parse_endpoints_names_and_failfast():
    eps = parse_endpoints("127.0.0.1:9000, 127.0.0.1:9001")
    assert [(e.name, e.port) for e in eps] == [("r0", 9000), ("r1", 9001)]
    with pytest.raises(ValueError):
        parse_endpoints("127.0.0.1")  # no port
    with pytest.raises(ValueError):
        parse_endpoints("host:notaport")
    with pytest.raises(ValueError):
        parse_endpoints(",")


def test_cli_route_flags():
    from sat_tpu.cli import build_config

    config, _ = build_config(
        ["--phase=route", "--num_replicas=3", "--port=0"]
    )
    assert config.phase == "route"
    assert config.route_num_replicas == 3
    assert config.route_port == 0  # --port binds the router in route phase

    # naming endpoints implies the route phase
    config, _ = build_config(
        ["--replicas=127.0.0.1:9000,127.0.0.1:9001", "--port=8801"]
    )
    assert config.phase == "route"
    assert config.route_replicas == "127.0.0.1:9000,127.0.0.1:9001"
    assert config.route_port == 8801


# ---------------------------------------------------------------------------
# Scripted stub replicas: retry / shed / drain against real sockets
# ---------------------------------------------------------------------------


class StubReplica:
    """A scripted CaptionServer stand-in: /healthz and /stats serve
    mutable dicts, /caption replies with a scripted status, and every
    X-Request-Id seen is recorded — enough surface for the router's
    poller, proxy and drain machinery without a jax engine."""

    def __init__(self, name):
        self.name = name
        self.health = {
            "ready": True,
            "status": "ok",
            "queue_depth": 0,
            "in_flight": 0,
            "serve_mode": "batch",
        }
        self.stats = {
            "latency_ms": {"serve/request": {"p50": 100.0, "p99": 150.0}},
            "compiles_since_ready": 0,
        }
        self.caption_status = 200
        self.retry_after = "7"  # the per-replica hint the router ignores
        self.seen_rids = []
        self.seen_paths = []
        self.seen_ctypes = []
        stub = self

        class _H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _reply(self, status, payload, headers=None):
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    code = 200 if stub.health.get("ready") else 503
                    self._reply(code, dict(stub.health))
                elif self.path == "/stats":
                    self._reply(200, dict(stub.stats))
                else:
                    self._reply(404, {"error": "nope"})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", "0"))
                self.rfile.read(length)
                rid = self.headers.get(tracectx.TRACE_HEADER)
                stub.seen_rids.append(rid)
                stub.seen_paths.append(self.path)
                stub.seen_ctypes.append(self.headers.get("Content-Type"))
                status = stub.caption_status
                if status == 429:
                    self._reply(
                        status,
                        {"error": "shed", "retry_after_ms": 7000},
                        headers={"Retry-After": stub.retry_after},
                    )
                elif status == 200:
                    self._reply(
                        status,
                        {"caption": f"stub from {stub.name}",
                         "request_id": rid},
                    )
                else:
                    self._reply(status, {"error": f"scripted {status}"})

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        self.port = self._httpd.server_address[1]

    @property
    def endpoint(self):
        return Endpoint(self.name, "127.0.0.1", self.port)

    def stop(self):
        if self._httpd is None:
            return
        self._httpd.shutdown()
        self._thread.join(timeout=5)
        self._httpd.server_close()
        self._httpd = None


def _router_config(tmp_path, **kw):
    return Config(
        phase="route",
        summary_dir=str(tmp_path / "summary"),
        route_poll_interval_s=60.0,  # the tests drive poll_once() by hand
        route_stats_every=1,  # every hand-driven tick folds /stats in
        route_hysteresis=0.25,
        route_down_weight=0.25,
        **kw,
    )


@pytest.fixture()
def stub_pair(tmp_path):
    tel = telemetry.get()
    was_enabled = tel.enabled
    if not was_enabled:
        tel = telemetry.enable(capacity=8192)
    a, b = StubReplica("r0"), StubReplica("r1")
    router = Router(
        _router_config(tmp_path), [a.endpoint, b.endpoint]
    )
    router.poll_once()
    yield {"a": a, "b": b, "router": router, "tel": tel}
    a.stop()
    b.stop()
    router.shutdown()
    if not was_enabled:
        telemetry.disable()


def test_pick_follows_load_and_downweights_degraded(stub_pair):
    a, b, router = stub_pair["a"], stub_pair["b"], stub_pair["router"]
    # healthy idle pair: the pick sticks to one replica (hysteresis),
    # whichever it is
    first = router.pick()
    assert first in ("r0", "r1")
    assert router.pick() == first
    # load the picked one well beyond the band: the pick flips
    (a if first == "r0" else b).health["queue_depth"] = 9
    router.poll_once()
    flipped = router.pick()
    assert flipped != first
    # degrade the new pick with the other still deep: degraded-idle
    # (1/0.25 = 4) still beats healthy-deep (10) — down-weighted, not
    # blackholed
    (a if flipped == "r0" else b).health["status"] = "degraded"
    router.poll_once()
    assert router.pick() == flipped


def test_burst_picks_stay_balanced_despite_hysteresis(stub_pair):
    # a burst between poll ticks is balanced by the router's own
    # outstanding counts: the hysteresis band damps polled-view noise
    # but must never let the sticky replica run ahead on exact local
    # bookkeeping (it would otherwise take (1+hysteresis)x the work)
    router = stub_pair["router"]
    counts = {"r0": 0, "r1": 0}
    for _ in range(16):
        name = router.pick()
        router._note_outstanding(name, +1)
        counts[name] += 1
    assert abs(counts["r0"] - counts["r1"]) <= 1, counts


def test_single_retry_on_refused_lands_on_other_replica(stub_pair):
    a, b, router, tel = (
        stub_pair["a"], stub_pair["b"], stub_pair["router"], stub_pair["tel"]
    )
    # make r1 the clear pick, then kill it without telling the poller —
    # the forward hits a dead socket and must retry on r0 exactly once
    a.health["queue_depth"] = 9
    router.poll_once()
    assert router.pick() == "r1"
    b.stop()
    before = tel.counters().get("route/retries", 0)
    status, data, _, headers = router.proxy_caption(b"img", "rid-retry-1")
    assert status == 200
    assert json.loads(data)["caption"] == "stub from r0"
    assert headers.get("X-Routed-Retry") == "1"
    assert headers.get("X-Routed-Replica") == "r0"
    assert tel.counters().get("route/retries", 0) == before + 1
    assert a.seen_rids == ["rid-retry-1"]  # the SAME rid crossed the hop
    # the failed socket marked r1 unreachable immediately (no poll wait)
    assert router.view()["replicas"]["r1"]["reachable"] is False


def test_both_replicas_refused_is_502_with_hint(stub_pair):
    a, b, router = stub_pair["a"], stub_pair["b"], stub_pair["router"]
    a.stop()
    b.stop()
    status, data, _, headers = router.proxy_caption(b"img", "rid-down-1")
    assert status == 502
    assert int(headers["Retry-After"]) >= 1  # never 0s
    payload = json.loads(data)
    assert payload["request_id"] == "rid-down-1"
    # once the poller catches up, the edge sheds 503 before forwarding
    router.poll_once()
    status, _, _, headers = router.proxy_caption(b"img", "rid-down-2")
    assert status == 503
    assert int(headers["Retry-After"]) >= 1


def test_coherent_shed_uses_fleet_p50_not_replica_hint(stub_pair):
    a, b, router = stub_pair["a"], stub_pair["b"], stub_pair["router"]
    for stub in (a, b):
        stub.caption_status = 429
        stub.retry_after = "19"  # per-replica hint the edge must override
        stub.stats["latency_ms"]["serve/request"] = {
            "p50": 2400.0, "p99": 3000.0,
        }
    router.poll_once()
    status, data, _, headers = router.proxy_caption(b"img", "rid-shed-1")
    assert status == 429
    # ceil(fleet p50 2.4s) = 3s — coherent across whichever replica shed
    assert headers["Retry-After"] == "3"
    payload = json.loads(data)
    assert payload["retry_after_ms"] == 3000
    assert payload["request_id"] == "rid-shed-1"
    # both replicas were tried (the single retry applies to sheds too)
    assert len(a.seen_rids) + len(b.seen_rids) == 2


def test_drain_sequencing_one_at_a_time(stub_pair):
    a, b, router = stub_pair["a"], stub_pair["b"], stub_pair["router"]
    status, payload = router.start_drain("r1")
    assert status == 200
    assert payload["mechanism"] == "hold-out"  # endpoint-mode replica
    # one at a time: a second drain is refused while r1 is in flight
    status, payload = router.start_drain("r0")
    assert status == 409
    assert payload["draining"] == "r1"
    # draining replicas leave rotation immediately
    assert router.view()["routable"] == ["r0"]
    status, _ = router.start_drain("r1")
    assert status == 409  # already draining
    status, _ = router.start_drain("nope")
    assert status == 404
    # observed idle + not ready -> drained; then ready again -> rotation
    b.health.update(ready=False, queue_depth=0, in_flight=0)
    router.poll_once()
    assert router.view()["replicas"]["r1"]["drain_state"] == "drained"
    b.health["ready"] = True
    router.poll_once()
    assert router.view()["replicas"]["r1"]["drain_state"] == "in"
    assert router.view()["routable"] == ["r0", "r1"]
    # undrain is only for held-out replicas
    status, _ = router.undrain("r1")
    assert status == 409


def test_proactive_shed_at_configured_depth(stub_pair, tmp_path):
    a, b = stub_pair["a"], stub_pair["b"]
    router = Router(
        _router_config(tmp_path / "shed", route_shed_depth=4),
        [a.endpoint, b.endpoint],
    )
    a.health["queue_depth"] = 4
    b.health["queue_depth"] = 5
    router.poll_once()
    status, _, _, headers = router.proxy_caption(b"img", "rid-depth-1")
    assert status == 429
    assert int(headers["Retry-After"]) >= 1
    assert a.seen_rids == [] and b.seen_rids == []  # no forwarding
    # one replica with room is enough to route again
    a.health["queue_depth"] = 0
    router.poll_once()
    status, _, _, _ = router.proxy_caption(b"img", "rid-depth-2")
    assert status == 200
    router.shutdown()


def test_tiered_fleet_two_hops_passthrough_and_starved_shed(tmp_path):
    """Disaggregated routing against scripted stubs: an image request
    makes two hops (/encode on the encode tier, then the grid body to
    /caption on the decode tier); a client-supplied grid skips hop one;
    a starved tier sheds 429 (scope=tier), never a 5xx."""
    from sat_tpu.serve.handoff import GRID_CONTENT_TYPE

    enc, dec = StubReplica("r0"), StubReplica("r1")
    enc.health["tier"] = "encode"
    dec.health["tier"] = "decode"
    router = Router(
        _router_config(tmp_path), [enc.endpoint, dec.endpoint]
    )
    try:
        router.poll_once()
        view = router.view()
        assert view["routable_encode"] == ["r0"]
        assert view["routable_decode"] == ["r1"]
        # image in: encode hop mints the grid, decode hop captions it
        status, _body, _ct, headers = router.proxy_caption(
            b"img", "rid-tier-1", content_type="image/jpeg"
        )
        assert status == 200
        assert enc.seen_paths == ["/encode"]
        assert dec.seen_paths == ["/caption"]
        assert dec.seen_ctypes == [GRID_CONTENT_TYPE]
        assert headers.get("X-Routed-Encode-Replica") == "r0"
        assert headers.get("X-Routed-Replica") == "r1"
        # rid propagates across BOTH hops (trace stitching)
        assert enc.seen_rids == ["rid-tier-1"]
        assert dec.seen_rids == ["rid-tier-1"]
        # a client-supplied grid goes straight to the decode tier
        status, _b, _c, _h = router.proxy_caption(
            b"frame", "rid-tier-2", content_type=GRID_CONTENT_TYPE
        )
        assert status == 200
        assert enc.seen_paths == ["/encode"]  # untouched
        assert dec.seen_paths == ["/caption", "/caption"]
        # encode tier gone: image traffic sheds coherently (429, scope
        # tier — capacity returns on respawn), grids still flow
        enc.health["ready"] = False
        router.poll_once()
        status, _b, _c, headers = router.proxy_caption(
            b"img", "rid-tier-3", content_type="image/jpeg"
        )
        assert status == 429
        assert headers["X-Shed-Scope"] == "tier"
        status, _b, _c, _h = router.proxy_caption(
            b"frame", "rid-tier-4", content_type=GRID_CONTENT_TYPE
        )
        assert status == 200
        # healthz/stats carry the tier split for operators
        payload, _code = router.healthz()
        assert payload["replicas_encode"] == 0
        assert payload["replicas_decode"] == 1
        assert router.stats()["routable_decode"] == ["r1"]
    finally:
        router.shutdown()
        enc.stop()
        dec.stop()


# ---------------------------------------------------------------------------
# End-to-end: two real CaptionServers behind a real router
# ---------------------------------------------------------------------------


_SENTENCES = [
    "a man rides a horse .",
    "a dog runs on the grass .",
    "two people walk along the beach .",
    "a plate of food sits on the table .",
]


def _jpeg(size):
    import cv2

    rng = np.random.default_rng(7)
    img = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
    ok, buf = cv2.imencode(".jpg", img)
    assert ok
    return bytes(buf)


@pytest.fixture(scope="module")
def router_fleet(tmp_path_factory):
    """Fresh tiny params saved through checkpoint+lineage, loaded by TWO
    in-process CaptionServers (separate summary dirs -> separate
    access.jsonl), fronted by a real Router HTTP server."""
    import jax

    from sat_tpu import runtime
    from sat_tpu.data.vocabulary import Vocabulary
    from sat_tpu.resilience import lineage
    from sat_tpu.serve.engine import ServeEngine, load_serving_state
    from sat_tpu.serve.server import CaptionServer
    from sat_tpu.train.checkpoint import save_checkpoint
    from sat_tpu.train.step import create_train_state

    root = tmp_path_factory.mktemp("router_e2e")
    vocab_file = str(root / "vocabulary.csv")
    vocabulary = Vocabulary(size=50)
    vocabulary.build(_SENTENCES)
    vocabulary.save(vocab_file)
    config = Config(
        phase="serve",
        image_size=32,
        dim_embedding=16,
        num_lstm_units=16,
        dim_initialize_layer=16,
        dim_attend_layer=16,
        dim_decode_layer=32,
        compute_dtype="float32",
        vocabulary_size=vocabulary.size,
        vocabulary_file=vocab_file,
        beam_size=2,
        save_dir=str(root / "models"),
        summary_dir=str(root / "summary"),
        serve_buckets=(1, 4),
        serve_max_batch=4,
        serve_max_wait_ms=10.0,
        serve_queue_depth=16,
        heartbeat_interval=0.0,
    )
    os.makedirs(config.save_dir, exist_ok=True)
    tel = telemetry.enable(capacity=1 << 16)
    runtime._install_compile_listener()
    state = create_train_state(jax.random.PRNGKey(0), config)
    save_checkpoint(state, config)
    lineage.mark_last_good(config.save_dir, int(np.asarray(state.step)))

    servers = []
    for i in range(2):
        rcfg = config.replace(
            summary_dir=str(root / f"r{i}" / "summary")
        )
        rstate, _ = load_serving_state(rcfg)
        engine = ServeEngine(rcfg, rstate, vocabulary, tel=tel)
        engine.warmup()
        servers.append(CaptionServer(rcfg, engine, port=0).start())
    endpoints = [
        Endpoint(f"r{i}", "127.0.0.1", s.port)
        for i, s in enumerate(servers)
    ]
    route_cfg = config.replace(
        phase="route",
        summary_dir=str(root / "router" / "summary"),
        route_poll_interval_s=0.1,
        route_stats_every=2,
    )
    router = Router(route_cfg, endpoints, port=0).start()
    yield {
        "router": router,
        "servers": servers,
        "tel": tel,
        "root": root,
        "config": config,
    }
    router.shutdown()
    for s in servers:
        s.shutdown()
    telemetry.disable()


def _http(port, method, path, body=None, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=body,
        headers=headers or {},
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def _hop_records(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_e2e_routes_with_rid_stitching_and_zero_recompiles(router_fleet):
    router = router_fleet["router"]
    tel = router_fleet["tel"]
    root = router_fleet["root"]
    jpeg = _jpeg(router_fleet["config"].image_size)
    port = router.port

    status, headers, health = _http(port, "GET", "/healthz")
    assert status == 200
    assert health["role"] == "router"
    assert health["replicas_routable"] == 2
    assert health["serve_mode"] == "batch"
    assert "queue_depth" in health and "in_flight" in health

    # first post pays the host-side first-touch costs
    status, _, _ = _http(
        port, "POST", "/caption", jpeg,
        {"Content-Type": "image/jpeg"},
    )
    assert status == 200

    compiles0 = tel.counters().get("jax/compiles", 0)
    rids = [f"rid-e2e-{i}" for i in range(4)]
    for rid in rids:
        status, headers, payload = _http(
            port, "POST", "/caption", jpeg,
            {"Content-Type": "image/jpeg", tracectx.TRACE_HEADER: rid},
        )
        assert status == 200
        assert headers[tracectx.TRACE_HEADER] == rid
        assert headers["X-Routed-Replica"] in ("r0", "r1")
        assert payload["request_id"] == rid  # replica echoed OUR id
        assert payload["captions"][0]["caption"]
    # steady state: the warmed buckets absorb every shape
    assert tel.counters().get("jax/compiles", 0) == compiles0

    # the hop stitches: each rid appears in the router's own access log
    # AND in exactly one replica's access log
    router_log = _hop_records(
        str(root / "router" / "summary" / "telemetry" / "access.jsonl")
    )
    replica_logs = {
        f"r{i}": _hop_records(
            str(root / f"r{i}" / "summary" / "telemetry" / "access.jsonl")
        )
        for i in range(2)
    }
    for rid in rids:
        hops = [r for r in router_log if r["trace_id"] == rid]
        assert len(hops) == 1 and hops[0]["hop"] == "route"
        assert hops[0]["status"] == 200
        served_by = [
            name
            for name, records in replica_logs.items()
            if any(r.get("trace_id") == rid for r in records)
        ]
        assert len(served_by) == 1
        # the router recorded the same replica the trace landed on
        assert hops[0]["replica"] == served_by[0]


def test_e2e_stats_and_metrics_surfaces(router_fleet):
    router = router_fleet["router"]
    port = router.port
    status, _, stats = _http(port, "GET", "/stats")
    assert status == 200
    assert stats["role"] == "router"
    assert set(stats["replicas"]) == {"r0", "r1"}
    assert stats["counters"].get("route/requests", 0) > 0
    assert "route/request" in stats["latency_ms"]
    assert "route/overhead" in stats["latency_ms"]
    req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
    with urllib.request.urlopen(req, timeout=30) as r:
        text = r.read().decode()
    assert 'sat_gauge{name="route/replicas_routable"} 2' in text
    assert 'name="route/requests"' in text
