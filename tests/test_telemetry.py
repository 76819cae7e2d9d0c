"""Telemetry subsystem tests: spans/counters/gauges, exporters, heartbeat,
ProfilerWindow coverage, crc32c vectorization parity, and the end-to-end
`--telemetry` train run (docs/OBSERVABILITY.md)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from sat_tpu import telemetry
from sat_tpu.telemetry import exporters
from sat_tpu.telemetry.heartbeat import Heartbeat
from sat_tpu.telemetry.spans import NullTelemetry, Telemetry

from tests.fixtures import LEDGER_TRAIN_STEP_MS


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    """Every test leaves the process-global dispatch in the off state —
    the same invariant production code relies on (telemetry-off runs are
    bitwise-unchanged)."""
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# spans core
# ---------------------------------------------------------------------------


def test_span_records_into_aggregates_and_window():
    tel = Telemetry(capacity=1024)
    with tel.span("a"):
        time.sleep(0.001)
    tel.record("b", 100, 500)
    agg = tel.aggregates()
    assert agg["a"][0] == 1 and agg["a"][1] >= 1_000_000  # >= 1 ms
    assert agg["b"] == (1, 500, 500)
    assert list(tel.durations_ns("b")) == [500]
    names, ids, t0s, durs, tids = tel.spans_snapshot()
    assert len(ids) == 2
    assert [names[i] for i in ids] == ["a", "b"]
    assert durs[0] >= 1_000_000 and durs[1] == 500


def test_capacity_rounds_to_power_of_two_min_256():
    assert Telemetry(capacity=1)._capacity == 256
    assert Telemetry(capacity=257)._capacity == 512
    assert Telemetry(capacity=1024)._capacity == 1024


def test_ring_overwrites_but_aggregates_stay_exact():
    tel = Telemetry(capacity=256)
    for i in range(1000):
        tel.record("x", i, i)
    count, total, mx = tel.aggregates()["x"]
    assert count == 1000
    assert total == sum(range(1000))
    assert mx == 999
    # window keeps only the newest `capacity` samples, oldest first
    win = tel.durations_ns("x")
    assert len(win) == 256
    assert list(win) == list(range(744, 1000))


def test_percentiles_come_from_window_not_all_time():
    tel = Telemetry(capacity=256)
    for i in range(300):
        tel.record("x", 0, 1_000_000 if i < 200 else 9_000_000)
    # the first 44 cheap samples fell off the ring; stats still count them
    assert tel.aggregates()["x"][0] == 300
    st = exporters._stats(*tel.aggregates()["x"], tel.durations_ns("x"))
    assert st["count"] == 300
    assert st["p95_ms"] == 9.0


def test_interning_grows_past_name_block():
    tel = Telemetry(capacity=256)
    for i in range(300):  # > _NAME_BLOCK distinct names
        tel.record(f"n{i}", 0, i + 1)
    agg = tel.aggregates()
    assert len(agg) == 300
    assert agg["n299"] == (1, 300, 300)


def test_counters_and_gauges():
    tel = Telemetry()
    tel.count("retries")
    tel.count("retries", 4)
    tel.gauge("step", 7)
    tel.gauge("step", 9)
    assert tel.counters() == {"retries": 5}
    assert tel.gauges() == {"step": 9}


def test_threaded_recording_smoke():
    tel = Telemetry(capacity=4096)
    n_threads, per_thread = 8, 500

    def work(k):
        for i in range(per_thread):
            tel.record(f"t{k}", i, 1)
            tel.count("events")

    threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # counters are lock-protected: exact.  Ring rows are slot-exclusive:
    # every record landed (4000 < capacity: nothing overwritten) and the
    # retained window holds only valid rows (no torn ids).
    assert tel.counters()["events"] == n_threads * per_thread
    names, ids, _, durs, _ = tel.spans_snapshot()
    assert len(ids) == n_threads * per_thread
    assert all(0 <= i < len(names) for i in ids)
    assert all(d == 1 for d in durs)
    assert sum(c for c, _, _ in tel.aggregates().values()) == n_threads * per_thread


def test_global_dispatch_enable_disable():
    assert isinstance(telemetry.get(), NullTelemetry)
    assert not telemetry.enabled()
    tel = telemetry.enable(512)
    assert telemetry.get() is tel and telemetry.enabled()
    with telemetry.span("x"):
        pass
    telemetry.count("c")
    telemetry.gauge("g", 1.5)
    assert "x" in tel.aggregates()
    assert tel.counters() == {"c": 1} and tel.gauges() == {"g": 1.5}
    # enable() again = fresh buffers (one recorder per run)
    tel2 = telemetry.enable(512)
    assert tel2 is not tel and tel2.aggregates() == {}
    telemetry.disable()
    assert isinstance(telemetry.get(), NullTelemetry)


def test_null_telemetry_is_inert():
    null = telemetry.get()
    assert isinstance(null, NullTelemetry)
    with null.span("x"):
        pass
    null.record("x", 0, 1)
    null.count("c")
    null.gauge("g", 1)
    assert null.counters() == {} and null.gauges() == {}
    assert null.aggregates() == {}
    assert null.durations_ns("x").size == 0
    names, ids, *_ = null.spans_snapshot()
    assert names == [] and ids.size == 0


def test_run_id_is_stable_within_process():
    assert telemetry.run_id() == telemetry.run_id()
    assert str(os.getpid()) in telemetry.run_id()


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_export_is_loadable(tmp_path):
    tel = Telemetry(capacity=256)
    with tel.span("phase/one"):
        time.sleep(0.001)
    tel.count("c", 2)
    path = str(tmp_path / "trace.json")
    assert exporters.export_chrome_trace(tel, path) == path
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert meta and meta[0]["args"]["name"] == "sat_tpu host"
    assert len(xs) == 1
    e = xs[0]
    assert e["name"] == "phase/one"
    assert set(e) >= {"name", "cat", "ph", "pid", "tid", "ts", "dur"}
    assert e["dur"] >= 1000.0  # microseconds
    assert doc["otherData"]["run_id"] == telemetry.run_id()
    assert doc["otherData"]["counters"] == {"c": 2}


def test_export_failure_degrades_not_raises(tmp_path):
    tel = Telemetry(capacity=256)
    tel.record("x", 0, 1)
    bad = str(tmp_path / "f.txt" / "trace.json")
    (tmp_path / "f.txt").write_text("a file, not a dir")
    assert exporters.export_chrome_trace(tel, bad) is None


def test_telemetry_jsonl_rows(tmp_path):
    tel = Telemetry(capacity=256)
    tel.record("x", 0, 2_000_000)
    tel.gauge("g", 3)
    # target a not-yet-created subdir: the first heartbeat normally creates
    # the telemetry dir, but heartbeat_interval=0 runs must not depend on it
    path = str(tmp_path / "telemetry" / "telemetry.jsonl")
    exporters.append_jsonl(tel, path, step=5)
    exporters.append_jsonl(tel, path, step=10)
    rows = [json.loads(l) for l in open(path)]
    assert [r["step"] for r in rows] == [5, 10]
    for r in rows:
        assert r["run_id"] == telemetry.run_id()
        assert isinstance(r["wall_time"], float)
        assert isinstance(r["mono_ns"], int)
        assert r["gauges"] == {"g": 3}
        assert r["spans"]["x"]["count"] == 1
        assert r["spans"]["x"]["total_ms"] == 2.0


def test_step_breakdown_phase_sum_reconstructs_wall():
    tel = Telemetry(capacity=1024)
    # 10 steps of 10 ms: 4 ms data_wait + 5 ms dispatch + 1 ms untracked
    for i in range(10):
        tel.record("train/data_wait", 0, 4_000_000)
        tel.record("train/dispatch", 0, 5_000_000)
        tel.record("feed/device_put", 0, 1_000_000)  # nested inside data_wait
        tel.record("train/step", 0, 10_000_000)
    rep = exporters.step_breakdown(
        tel, "train/step",
        ("train/data_wait", "train/dispatch"),
        nested=("feed/device_put",),
    )
    assert rep["steps"] == 10
    assert rep["wall_s"] == pytest.approx(0.1)
    phases = rep["phases"]
    assert phases["train/data_wait"]["total_s"] == pytest.approx(0.04)
    assert phases["train/dispatch"]["total_s"] == pytest.approx(0.05)
    assert phases["other"]["total_s"] == pytest.approx(0.01)
    # the invariant the acceptance bar rides on: phase sum == wall
    assert rep["phase_total_s"] == pytest.approx(rep["wall_s"])
    # nested spans are visible but NOT part of the sum
    assert rep["nested"]["feed/device_put"]["total_s"] == pytest.approx(0.01)
    text = exporters.format_breakdown(rep)
    assert "train/dispatch" in text and "other" in text
    assert "feed/device_put" in text


def test_step_breakdown_none_when_no_steps():
    tel = Telemetry(capacity=256)
    assert exporters.step_breakdown(tel, "train/step", ()) is None


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------


def test_heartbeat_updates_at_interval(tmp_path):
    tel = Telemetry(capacity=256)
    tel.gauge("train/step", 0)
    path = str(tmp_path / "heartbeat.json")
    hb = Heartbeat(path, interval_s=0.05, tel=tel, static={"phase": "train"})
    with hb:
        deadline = time.time() + 5.0
        # first beat is immediate; wait for at least two more ticks
        while time.time() < deadline:
            if os.path.exists(path) and json.load(open(path))["seq"] >= 2:
                break
            time.sleep(0.02)
        tel.gauge("train/step", 42)
    final = json.load(open(path))
    assert final["seq"] >= 3  # stop() writes a final beat
    assert final["step"] == 42  # the final beat sees the last gauge
    assert final["phase"] == "train"
    assert final["pid"] == os.getpid()
    assert final["run_id"] == telemetry.run_id()
    assert final["rss_mb"] > 0
    # atomic writes: the file is always complete, valid JSON (checked by
    # every json.load above)


def test_heartbeat_throughput_between_ticks(tmp_path):
    tel = Telemetry(capacity=256)
    hb = Heartbeat(str(tmp_path / "hb.json"), 10.0, tel)
    tel.gauge("train/step", 100)
    hb.write_now()
    time.sleep(0.05)
    tel.gauge("train/step", 110)
    hb.write_now()
    d = json.load(open(hb.path))
    assert d["steps_per_s"] is not None and d["steps_per_s"] > 0


def test_heartbeat_write_failure_never_raises(tmp_path):
    tel = Telemetry(capacity=256)
    blocker = tmp_path / "f"
    blocker.write_text("not a dir")
    hb = Heartbeat(str(blocker / "hb.json"), 0.05, tel)
    hb.write_now()  # must warn, not raise
    hb.write_now()


# ---------------------------------------------------------------------------
# ProfilerWindow (satellite: previously zero tests referenced it)
# ---------------------------------------------------------------------------


@pytest.fixture
def fake_profiler(monkeypatch):
    """Replace jax.profiler start/stop and block_until_ready with a call
    recorder, so window logic is testable without a real trace backend."""
    import jax

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    monkeypatch.setattr(
        jax, "block_until_ready", lambda x: calls.append(("sync", x))
    )
    return calls


def _window_config(**kw):
    from sat_tpu.config import Config

    return Config(**{"profile_dir": "/tmp/prof", "profile_start_step": 5,
                     "profile_num_steps": 3, **kw})


def test_profiler_window_resume_aware_start(fake_profiler):
    from sat_tpu.runtime import ProfilerWindow

    with ProfilerWindow(_window_config()) as prof:
        # resumed run: first loop step is 100, already past start=5 —
        # the window must still open (">= start, once" semantics)
        for i in range(100, 110):
            prof.before_step(i)
            prof.after_step(i, f"sync{i}")
    starts = [c for c in fake_profiler if c[0] == "start"]
    stops = [c for c in fake_profiler if c[0] == "stop"]
    assert len(starts) == 1 and len(stops) == 1
    # window covered exactly profile_num_steps steps: opened at 100,
    # closed after 102 with a sync on 102's target
    stop_idx = fake_profiler.index(("stop",))
    assert fake_profiler[stop_idx - 1] == ("sync", "sync102")


def test_profiler_window_max_start_clamps_short_loops(fake_profiler):
    from sat_tpu.runtime import ProfilerWindow

    # 3-batch decode with default start=5: without clamping the window
    # would never open
    with ProfilerWindow(_window_config(), max_start=2) as prof:
        for i in range(3):
            prof.before_step(i)
            prof.after_step(i, i)
    assert ("start", "/tmp/prof") in fake_profiler
    assert ("stop",) in fake_profiler


def test_profiler_window_exit_closes_early_loop_exit(fake_profiler):
    from sat_tpu.runtime import ProfilerWindow

    with ProfilerWindow(_window_config(profile_start_step=0)) as prof:
        prof.before_step(0)
        prof.after_step(0, "s0")  # loop dies inside the window
    # __exit__ must stop the trace, syncing on the last after_step target
    assert fake_profiler[-1] == ("stop",)
    assert ("sync", "s0") in fake_profiler


def test_profiler_window_sweep_reentry_never_double_opens(fake_profiler):
    from sat_tpu.runtime import ProfilerWindow

    # evaluate_sweep re-enters decode per checkpoint: each decode gets a
    # FRESH window; starts/stops must stay strictly paired
    for _ in range(3):
        with ProfilerWindow(_window_config(), max_start=1) as prof:
            for i in range(2):
                prof.before_step(i)
                prof.after_step(i, i)
    seq = [c[0] for c in fake_profiler if c[0] in ("start", "stop")]
    assert seq == ["start", "stop"] * 3


def test_profiler_window_off_when_no_dir(fake_profiler):
    from sat_tpu.runtime import ProfilerWindow

    with ProfilerWindow(_window_config(profile_dir="")) as prof:
        for i in range(10):
            prof.before_step(i)
            prof.after_step(i, i)
    assert fake_profiler == []


def test_profiler_window_exit_idempotent(fake_profiler):
    from sat_tpu.runtime import ProfilerWindow

    w = ProfilerWindow(_window_config(profile_start_step=0))
    w.before_step(0)
    w.after_step(0, "s")
    w.__exit__()
    w.__exit__()  # second close is a no-op, not a double stop_trace
    assert [c[0] for c in fake_profiler].count("stop") == 1


def test_profiler_window_start_collision_degrades_and_never_stops(
    fake_profiler, monkeypatch, capsys
):
    """--profile_dir alongside an already-live trace (e.g. an outer
    jax.profiler session next to --trace_export): start_trace raises.
    The window must (a) not take the run down, (b) not retry the open on
    every later step, and (c) never issue the stop_trace that would
    close the OUTER trace."""
    import jax

    from sat_tpu.runtime import ProfilerWindow

    calls = []

    def boom(d):
        calls.append(("start", d))
        raise RuntimeError("Only one profile may be run at a time.")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with ProfilerWindow(_window_config(profile_start_step=0)) as prof:
        for i in range(10):
            prof.before_step(i)
            prof.after_step(i, i)
    assert calls == [("start", "/tmp/prof")]       # opened once, not per step
    assert ("stop",) not in fake_profiler          # outer trace left alone
    assert ("sync", 0) not in fake_profiler        # no close sync either
    assert "start_trace failed" in capsys.readouterr().err


def test_profiler_window_stop_failure_degrades_and_stays_closed(
    fake_profiler, monkeypatch, capsys
):
    """stop_trace raising (the trace was stopped under us) must not
    propagate into the train loop, and __exit__ must not try a second
    stop afterwards."""
    import jax

    from sat_tpu.runtime import ProfilerWindow

    stops = []

    def boom():
        stops.append("stop")
        raise RuntimeError("No profile started")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    with ProfilerWindow(_window_config(profile_start_step=0)) as prof:
        for i in range(5):
            prof.before_step(i)
            prof.after_step(i, i)   # window closes (and fails) at step 2
    assert stops == ["stop"]        # __exit__ saw a closed window: no retry
    assert "stop_trace failed" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# crc32c vectorization (satellite: bitwise parity with the scalar oracle)
# ---------------------------------------------------------------------------


def test_crc32c_vector_matches_scalar_oracle():
    from sat_tpu.utils.summary import _crc32c_scalar, crc32c

    rng = np.random.default_rng(0)
    for n in (0, 1, 7, 255, 256, 4095, 4096, 4097, 8192, 65536, 65537, 200001):
        data = rng.integers(0, 256, n, np.uint8).tobytes()
        assert crc32c(data) == _crc32c_scalar(data) ^ 0xFFFFFFFF, n


def test_crc32c_known_vectors():
    from sat_tpu.utils.summary import crc32c

    # RFC 3720 appendix B.4 test vectors (Castagnoli)
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c(b"\xff" * 32) == 0x62A8AB43
    assert crc32c(bytes(range(32))) == 0x46DD794E
    # and above the vector threshold: all-zero / patterned payloads
    assert crc32c(b"\x00" * 8192) == (
        __import__("sat_tpu.utils.summary", fromlist=["_crc32c_scalar"])
        ._crc32c_scalar(b"\x00" * 8192)
        ^ 0xFFFFFFFF
    )


def test_masked_crc_framing_unchanged():
    from sat_tpu.utils.summary import _masked_crc

    # the TFRecord mask of a known crc must be stable across the
    # vectorization (an 8-byte length header exercises the scalar path)
    import struct

    header = struct.pack("<Q", 24)
    assert _masked_crc(header) == _masked_crc(header)


# ---------------------------------------------------------------------------
# config / CLI wiring
# ---------------------------------------------------------------------------


def test_cli_telemetry_flags():
    from sat_tpu.cli import build_config

    c, _ = build_config(["--phase", "train"])
    assert c.telemetry is False  # off by default
    c, _ = build_config([
        "--phase", "train", "--telemetry",
        "--heartbeat_interval", "2.5", "--trace_export", "/tmp/t.json",
    ])
    assert c.telemetry is True
    assert c.heartbeat_interval == 2.5
    assert c.trace_export == "/tmp/t.json"


def test_config_validates_telemetry_knobs():
    from sat_tpu.config import Config

    with pytest.raises(ValueError, match="heartbeat_interval"):
        Config(heartbeat_interval=-1)
    with pytest.raises(ValueError, match="telemetry_buffer"):
        Config(telemetry_buffer=0)


def test_step_loop_instrumentation_under_half_percent_of_a_step():
    """The telemetry calls runtime.train makes per step (data_wait, place
    and dispatch spans, the step gauge and record, each with the step as
    its arg; log_sync and log_io spans every log_every steps; the device's
    occupancy asked at each boundary, at its dearest: a device that has
    run dry at EVERY step, so a stretch a step) against a live recorder:
    <= 0.5% of the train cell's device step."""
    from sat_tpu.runtime import DeviceOccupancy

    class Done:
        @staticmethod
        def is_ready():
            return True

    def per_step_s(tel, iters, log_every=10):
        occupancy = DeviceOccupancy(tel, "train")
        t0 = time.perf_counter()
        step_t0 = time.perf_counter_ns()
        for step in range(iters):
            for name in ("train/data_wait", "train/place"):
                with tel.span(name, step):
                    pass
                occupancy.observe()
            with tel.span("train/dispatch", step):
                pass
            occupancy.enqueued(Done, step)
            tel.gauge("train/step", step)
            if step % log_every == 0:
                with tel.span("train/log_sync", step):
                    pass
                occupancy.observe()
                with tel.span("train/log_io", step):
                    occupancy.publish()
                occupancy.observe()
            now = time.perf_counter_ns()
            tel.record("train/step", step_t0, now - step_t0, step)
            step_t0 = now
        return (time.perf_counter() - t0) / iters

    per_step_s(telemetry.enable(capacity=65536), 1000)  # warm
    tel = telemetry.enable(capacity=65536)
    on = per_step_s(tel, 5000)
    assert 1e3 * on <= 0.005 * LEDGER_TRAIN_STEP_MS
    assert tel.aggregates()["train/device_empty"][0] == 4999


# ---------------------------------------------------------------------------
# end-to-end: tier-1 CPU train run with --telemetry (acceptance criteria)
# ---------------------------------------------------------------------------

SMALL_MODEL = dict(
    image_size=32,
    dim_embedding=16,
    num_lstm_units=16,
    dim_initialize_layer=16,
    dim_attend_layer=16,
    dim_decode_layer=32,
    compute_dtype="float32",
    save_period=3,
    log_every=2,
    num_epochs=1,
    num_data_workers=2,
)


@pytest.fixture(scope="module")
def telemetry_run(coco_fixture, tmp_path_factory):
    """One telemetry-on train run shared by the artifact assertions."""
    from sat_tpu import runtime

    tmp = tmp_path_factory.mktemp("telemetry_run")
    config = coco_fixture["config"].replace(
        **SMALL_MODEL,
        save_dir=str(tmp / "models"),
        summary_dir=str(tmp / "summary"),
        telemetry=True,
        heartbeat_interval=0.1,
        telemetry_buffer=4096,
    )
    t0 = time.perf_counter()
    state = runtime.train(config)
    wall_s = time.perf_counter() - t0
    telemetry.disable()
    return config, state, wall_s


def test_e2e_trace_json_is_perfetto_loadable(telemetry_run):
    config, state, _ = telemetry_run
    trace = os.path.join(config.summary_dir, "telemetry", "trace.json")
    doc = json.load(open(trace))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs, "no span events in the trace"
    by_name = {e["name"] for e in xs}
    assert {"train/step", "train/data_wait", "train/dispatch",
            "train/log_sync"} <= by_name
    for e in xs:
        assert e["dur"] >= 0 and isinstance(e["ts"], float)
    assert doc["otherData"]["anchor_unix"] > 0


def test_e2e_heartbeat_is_valid_and_final(telemetry_run):
    config, state, _ = telemetry_run
    hb = json.load(
        open(os.path.join(config.summary_dir, "telemetry", "heartbeat.json"))
    )
    assert hb["step"] == int(state.step) == 6
    assert hb["phase"] == "train"
    assert hb["backend"] == "cpu"
    assert hb["interval_s"] == pytest.approx(0.1)
    assert hb["seq"] >= 1
    assert hb["last_checkpoint_step"] == 6
    assert hb["last_checkpoint_age_s"] is not None
    assert hb["rss_mb"] > 0


def test_e2e_breakdown_phase_sum_within_5pct_of_wall(telemetry_run):
    config, state, _ = telemetry_run
    report = json.load(
        open(os.path.join(config.summary_dir, "telemetry", "breakdown.json"))
    )
    assert report["steps"] == 6
    # phase sum reconstructs the measured step wall time (acceptance bar:
    # within 5%; the residual "other" phase makes it exact by construction)
    assert report["phase_total_s"] == pytest.approx(
        report["wall_s"], rel=0.05
    )
    assert "train/dispatch" in report["phases"]
    assert report["phases"]["train/dispatch"]["count"] == 6


def test_e2e_telemetry_jsonl_rows_at_log_boundaries(telemetry_run):
    config, state, _ = telemetry_run
    path = os.path.join(config.summary_dir, "telemetry", "telemetry.jsonl")
    rows = [json.loads(l) for l in open(path)]
    # log_every=2 over 6 steps -> boundaries at 2, 4, 6
    assert [r["step"] for r in rows] == [2, 4, 6]
    for r in rows:
        assert r["run_id"] == telemetry.run_id()
        assert "train/step" in r["spans"] or r["step"] == 2


def test_e2e_metrics_jsonl_stamps_join_with_telemetry(telemetry_run):
    config, state, _ = telemetry_run
    rows = [
        json.loads(l)
        for l in open(os.path.join(config.summary_dir, "metrics.jsonl"))
    ]
    assert all(r["run_id"] == telemetry.run_id() for r in rows)
    mono = [r["mono_ns"] for r in rows]
    assert mono == sorted(mono)


def test_e2e_compile_accounting_counted(telemetry_run):
    """jax.monitoring feeds compile events into the heartbeat/trace."""
    config, state, _ = telemetry_run
    hb = json.load(
        open(os.path.join(config.summary_dir, "telemetry", "heartbeat.json"))
    )
    # the tiny model still compiles at least the train step
    assert hb["compile_count"] >= 1
    assert hb["compile_seconds"] > 0


def test_telemetry_off_leaves_no_artifacts(coco_fixture, tmp_path):
    """Default (off) runs must neither record spans nor write telemetry
    artifacts — the bitwise-unchanged guarantee rides on this."""
    from sat_tpu import runtime

    config = coco_fixture["config"].replace(
        **SMALL_MODEL,
        save_dir=str(tmp_path / "models"),
        summary_dir=str(tmp_path / "summary"),
        max_steps=2,
    )
    runtime.train(config)
    assert not os.path.exists(os.path.join(config.summary_dir, "telemetry"))
    assert isinstance(telemetry.get(), NullTelemetry)
