"""Execution supervisor: hang/wedge watchdog, crash-only auto-restart,
and topology-elastic checkpoint resume (docs/RESILIENCE.md).

Unit layer pins the contracts in isolation — the watchdog escalation
ladder (gauges → stack dump → abort 86) with an injected abort, the
supervisor restart policy through its ``runner`` hook, fault-plan knob
parsing, the force-kill defer window, the topology sidecar, and the
armed watchdog's cost on the step loop.

The chaos layer drives the whole stack end-to-end through real
subprocesses on the 8-virtual-device CPU backend:
``--supervise`` + ``SAT_FI_WEDGE_AT_STEP`` → watchdog abort (exit 86,
stack-dump artifact) → auto-restart from LAST_GOOD → a final state
bitwise-identical to an uninterrupted control run.  Elastic resume is
pinned in-process: an 8-chip checkpoint re-placed onto 4- and 1-chip
meshes bitwise-exactly, then trained further on the smaller mesh.
"""

import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from sat_tpu import runtime
from sat_tpu.parallel.mesh import mesh_from_devices
from sat_tpu.parallel.sharding import reshard_train_state
from sat_tpu.resilience import lineage
from sat_tpu.resilience.faultinject import FaultPlan
from sat_tpu.resilience.preempt import GracefulShutdown
from sat_tpu.resilience.supervisor import (
    RESTARTS_ENV,
    _strip_supervise,
    supervise,
)
from sat_tpu.resilience.watchdog import (
    ABORTING,
    DUMPED,
    OK,
    STALLED,
    WATCHDOG_EXIT_CODE,
    Watchdog,
    deadlines_from_config,
)
from sat_tpu.train import checkpoint as ckpt_mod
from sat_tpu.train.checkpoint import latest_checkpoint, state_to_flat

from tests.fixtures import LEDGER_TRAIN_STEP_MS
from tests.test_resilience import _cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# watchdog: escalation ladder with an injected abort
# ---------------------------------------------------------------------------


def _make_wd(tmp_path, deadlines, **kw):
    aborts = []
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("grace_s", 0.0)
    kw.setdefault("dump_path", str(tmp_path / "watchdog_stacks.txt"))
    wd = Watchdog(deadlines, abort=aborts.append, **kw)
    return wd, aborts


def test_watchdog_ladder_escalates_to_abort(tmp_path):
    flushed = []
    wd, aborts = _make_wd(
        tmp_path, {"step": 0.01}, pre_abort=lambda: flushed.append(1)
    )
    with wd.phase("step"):  # first completion arms enforcement
        pass
    assert wd.state == OK
    wd._enter("step")
    time.sleep(0.03)

    wd.check()  # rung 1: gauges
    assert wd.state == STALLED and wd.stalled_phase == "step"
    wd.check()  # rung 2: stack dump
    assert wd.state == DUMPED
    dump = open(str(tmp_path / "watchdog_stacks.txt")).read()
    assert dump.startswith("sat_tpu watchdog stack dump: phase=step")
    assert f"pid={os.getpid()}" in dump
    wd.check()  # rung 3 (grace 0): pre-abort hook, then abort
    assert wd.state == ABORTING
    assert flushed == [1]
    assert aborts == [WATCHDOG_EXIT_CODE] == [86]


def test_watchdog_cold_start_never_false_trips(tmp_path):
    """A phase that has NEVER completed (first step compiling for minutes)
    is tracked but not enforced."""
    wd, aborts = _make_wd(tmp_path, {"step": 0.01})
    wd._enter("step")
    time.sleep(0.03)
    wd.check()
    assert wd.state == OK and aborts == []
    wd._exit("step")
    # ...but the second entry IS enforced
    wd._enter("step")
    time.sleep(0.03)
    wd.check()
    assert wd.state == STALLED


def test_watchdog_stands_down_when_phase_completes(tmp_path):
    wd, aborts = _make_wd(tmp_path, {"dispatch": 0.01})
    with wd.phase("dispatch"):
        pass
    wd._enter("dispatch")
    time.sleep(0.03)
    wd.check()
    assert wd.state == STALLED
    wd._exit("dispatch")  # the stall resolved after all
    assert wd.state == OK and wd.stalled_phase is None
    wd.check()
    assert wd.state == OK and aborts == []


def test_watchdog_untracked_phase_never_enforced(tmp_path):
    wd, aborts = _make_wd(tmp_path, {"step": 0.01})
    with wd.phase("warmup"):  # no deadline entry
        pass
    wd._enter("warmup")
    time.sleep(0.03)
    for _ in range(4):
        wd.check()
    assert wd.state == OK and aborts == []


def test_slow_but_alive_steps_keep_watchdog_quiet(tmp_path):
    """SAT_FI_SLOW_STEP_MS semantics: a degraded-but-progressing loop
    completes its phases and must never climb the ladder.  Driven on a
    fake clock (``use_clock``) so "slow but under deadline" is exact —
    the wall-clock version raced suite CPU contention and flaked when a
    5 ms stall ran past the 50 ms deadline on a loaded host."""
    plan = FaultPlan(slow_step_ms=5)
    now = [0.0]
    wd, aborts = _make_wd(tmp_path, {"step": 0.05})
    wd.use_clock(lambda: now[0])
    for step in range(5):
        with wd.phase("step"):
            plan.maybe_slow(step)  # real stall; watchdog time is frozen
            now[0] += 0.04  # each step runs 40 ms on the fake clock
        wd.check()
    assert wd.state == OK and aborts == []
    # same cadence past the deadline DOES climb: proves the fake-clock
    # harness still exercises enforcement, not a disconnected timer
    wd._enter("step")
    now[0] += 0.06
    wd.check()
    assert wd.state == STALLED


def test_watchdog_threaded_smoke(tmp_path):
    """The real observer thread drives the same ladder: a parked phase
    reaches the injected abort without any manual check() calls."""
    wd, aborts = _make_wd(tmp_path, {"step": 0.05}, poll_s=0.05)
    wd.start()
    try:
        with wd.phase("step"):
            pass
        wd._enter("step")
        deadline = time.time() + 10.0
        while not aborts and time.time() < deadline:
            time.sleep(0.02)
    finally:
        wd._exit("step")
        wd.stop()
    assert aborts == [WATCHDOG_EXIT_CODE]


def test_deadlines_from_config_drops_disabled_phases():
    from sat_tpu.config import Config

    config = Config(
        watchdog_step_s=10.0,
        watchdog_data_wait_s=0.0,  # 0 disables this phase
        watchdog_dispatch_s=5.0,
        watchdog_checkpoint_s=7.0,
    )
    d = deadlines_from_config(config)
    assert d["step"] == 10.0 and d["dispatch"] == 5.0 and d["checkpoint"] == 7.0
    wd = Watchdog(d, abort=lambda rc: None)
    assert "data_wait" not in wd.deadlines


# ---------------------------------------------------------------------------
# fault-plan knobs added for the supervisor PR
# ---------------------------------------------------------------------------


def test_fault_plan_parses_wedge_and_slow_knobs():
    assert FaultPlan.from_env({}).inert
    plan = FaultPlan.from_env(
        {
            "SAT_FI_WEDGE_AT_STEP": "5",
            "SAT_FI_SLOW_STEP_MS": "20",
            "SAT_FI_WEDGE_SERVE_BATCH": "2",
        }
    )
    assert not plan.inert
    assert plan.wedge_at_step == 5
    assert plan.slow_step_ms == 20
    assert plan.wedge_serve_batch == 2
    with pytest.raises(ValueError, match="expected an integer"):
        FaultPlan.from_env({"SAT_FI_WEDGE_AT_STEP": "later"})


def test_fault_plan_serve_wedge_fires_exactly_once():
    plan = FaultPlan(wedge_serve_batch=2)
    assert not plan.maybe_wedge_serve(1)
    assert plan.maybe_wedge_serve(2)
    assert not plan.maybe_wedge_serve(2)  # fired already
    assert not plan.maybe_wedge_serve(3)


def test_fault_plan_slow_step_stalls_host_time():
    plan = FaultPlan(slow_step_ms=30)
    t0 = time.monotonic()
    plan.maybe_slow(1)
    plan.maybe_slow(2)  # slow is per-step, not fire-once
    assert time.monotonic() - t0 >= 0.05


# ---------------------------------------------------------------------------
# supervisor: restart policy through the runner hook
# ---------------------------------------------------------------------------


def test_strip_supervise_variants():
    argv = [
        "--config", "c.json", "--supervise", "--max_restarts", "4",
        "--watchdog", "1.0",
    ]
    assert _strip_supervise(argv) == ["--config", "c.json", "--watchdog", "1.0"]
    assert _strip_supervise(["--supervise", "--max_restarts=4"]) == []
    assert _strip_supervise(["--load"]) == ["--load"]


def test_supervisor_restarts_with_load_and_disarmed_faults(monkeypatch):
    """Child failures burn the budget; every restarted child resumes with
    --load, a bumped SAT_SUPERVISOR_RESTARTS, and NO SAT_FI_* vars (an
    injected deterministic fault must not live-lock the restart loop)."""
    monkeypatch.setenv("SAT_FI_WEDGE_AT_STEP", "5")
    calls = []
    rcs = iter([WATCHDOG_EXIT_CODE, 1, 0])

    def runner(cmd, env):
        calls.append((list(cmd), dict(env)))
        return next(rcs)

    sleeps = []
    rc = supervise(
        ["--config", "c.json", "--supervise", "--max_restarts", "5"],
        max_restarts=5,
        backoff_base_s=0.01,
        runner=runner,
        sleep=sleeps.append,
    )
    assert rc == 0
    assert len(calls) == 3
    assert len(sleeps) == 2 and all(s > 0 for s in sleeps)

    cmd0, env0 = calls[0]
    assert cmd0[:3] == [sys.executable, "-m", "sat_tpu.cli"]
    assert "--supervise" not in cmd0 and "--max_restarts" not in cmd0
    assert "--load" not in cmd0  # first launch: fresh run as asked
    assert env0[RESTARTS_ENV] == "0"
    assert env0.get("SAT_FI_WEDGE_AT_STEP") == "5"  # first child keeps it

    for n, (cmd, env) in enumerate(calls[1:], start=1):
        assert cmd.count("--load") == 1  # appended once, never duplicated
        assert env[RESTARTS_ENV] == str(n)
        assert not any(k.startswith("SAT_FI_") for k in env)


def test_supervisor_budget_spent_returns_last_rc():
    calls = []

    def runner(cmd, env):
        calls.append(cmd)
        return WATCHDOG_EXIT_CODE

    rc = supervise(
        ["--config", "c.json"],
        max_restarts=2,
        backoff_base_s=0.0,
        runner=runner,
        sleep=lambda s: None,
    )
    assert rc == WATCHDOG_EXIT_CODE
    assert len(calls) == 3  # 1 launch + 2 restarts


def test_supervisor_clean_child_never_restarts():
    calls = []
    rc = supervise(
        ["--config", "c.json"],
        max_restarts=3,
        runner=lambda cmd, env: (calls.append(cmd), 0)[1],
        sleep=lambda s: None,
    )
    assert rc == 0 and len(calls) == 1


def test_supervisor_signal_stops_restart_loop():
    """A SIGTERM delivered to the supervisor while a child is failing
    stops the restart loop (the pair is being preempted, not wedged)."""
    calls = []

    def runner(cmd, env):
        calls.append(cmd)
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)  # let the handler observe the signal
        return WATCHDOG_EXIT_CODE

    before = signal.getsignal(signal.SIGTERM)
    rc = supervise(
        ["--config", "c.json"],
        max_restarts=5,
        runner=runner,
        sleep=lambda s: None,
    )
    assert rc == WATCHDOG_EXIT_CODE
    assert len(calls) == 1  # no restart after the signal
    assert signal.getsignal(signal.SIGTERM) is before  # handler restored


# ---------------------------------------------------------------------------
# graceful-shutdown defer window (force-kill held mid-checkpoint-flush)
# ---------------------------------------------------------------------------


def test_defer_holds_force_kill_until_window_closes(capsys):
    fired = []
    with GracefulShutdown() as s:
        s._handler(signal.SIGTERM, None)  # first signal: graceful stop
        assert s.stop_requested
        # observable stand-in for the original disposition
        s._previous[signal.SIGTERM] = lambda signum, frame: fired.append(signum)
        with s.defer():
            s._handler(signal.SIGTERM, None)  # force-kill mid-flush
            assert fired == []  # held, not dropped
            err = capsys.readouterr().err
            assert "held until the in-flight checkpoint" in err
        assert fired == [signal.SIGTERM]  # released when the window closed


def test_defer_is_reentrant_releases_at_outermost_exit():
    fired = []
    with GracefulShutdown() as s:
        s._handler(signal.SIGTERM, None)
        s._previous[signal.SIGTERM] = lambda signum, frame: fired.append(signum)
        with s.defer():
            with s.defer():
                s._handler(signal.SIGTERM, None)
            assert fired == []  # inner exit: still one window deep
        assert fired == [signal.SIGTERM]


def test_defer_without_pending_force_is_inert():
    with GracefulShutdown() as s:
        with s.defer():
            pass
        assert not s.stop_requested


# ---------------------------------------------------------------------------
# topology sidecar + elastic-restore note
# ---------------------------------------------------------------------------


def _write_npz(path, **arrays):
    if not arrays:
        arrays = {"w": np.arange(8, dtype=np.float32)}
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    return path


def test_topology_sidecar_round_trip_and_verify_compat(tmp_path):
    path = _write_npz(str(tmp_path / "3.npz"))
    topo = {
        "device_count": 8,
        "platform": "cpu",
        "process_count": 1,
        "mesh_shape": [8, 1],
        "mesh_axes": ["data", "model"],
    }
    lineage.write_sidecar(path, topology=topo)
    # the digest contract is untouched by the extension
    assert lineage.verify_checkpoint(path) == (True, "sha256 ok")
    assert lineage.read_sidecar_topology(path) == topo
    # sidecars without the extension read as None, not an error
    legacy = _write_npz(str(tmp_path / "6.npz"))
    lineage.write_sidecar(legacy)
    assert lineage.read_sidecar_topology(legacy) is None


def test_elastic_restore_note_fires_only_on_topology_change(tmp_path, capsys):
    path = _write_npz(str(tmp_path / "3.npz"))
    lineage.write_sidecar(
        path, topology={"device_count": 2, "mesh_shape": [2, 1]}
    )
    ckpt_mod._note_elastic_restore(path)
    err = capsys.readouterr().err
    assert "elastic resume" in err and "2 device(s)" in err
    # matching topology: silent
    same = _write_npz(str(tmp_path / "6.npz"))
    lineage.write_sidecar(
        same,
        topology={"device_count": len(jax.devices()), "mesh_shape": [8, 1]},
    )
    ckpt_mod._note_elastic_restore(same)
    assert "elastic resume" not in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the armed watchdog's cost on the step loop
# ---------------------------------------------------------------------------


def test_watchdog_guards_cost_under_half_percent_of_a_step(tmp_path):
    """runtime.train's per-step guard sequence (data_wait, then step
    around dispatch) under a started watchdog whose observer polls,
    minus the bare loop: <= 0.5% of the train cell's device step."""

    def loop_s(iters, wd=None):
        t0 = time.perf_counter()
        for _ in range(iters):
            if wd is not None:
                with wd.phase("data_wait"):
                    pass
                with wd.phase("step"), wd.phase("dispatch"):
                    pass
        return (time.perf_counter() - t0) / iters

    wd = Watchdog(
        {"step": 3600.0, "data_wait": 3600.0, "dispatch": 3600.0},
        poll_s=0.05, dump_path=str(tmp_path / "watchdog_stacks.txt"),
    )
    wd.start()
    try:
        loop_s(1000, wd)  # warm
        armed = loop_s(20000, wd)
    finally:
        wd.stop()
    assert wd.state == OK and wd.aborted_rc is None  # never tripped
    assert 1e3 * (armed - loop_s(20000)) <= 0.005 * LEDGER_TRAIN_STEP_MS


# ---------------------------------------------------------------------------
# elastic resume: 8-chip checkpoint onto 4- and 1-chip meshes (in-process)
# ---------------------------------------------------------------------------


def test_elastic_resume_8_to_4_to_1_bitwise(coco_fixture, tmp_path, capsys):
    """Train on an (8,1) mesh, then restore+re-place the checkpoint onto
    4- and 1-device meshes: state must be bitwise identical, and training
    must continue on the smaller mesh."""
    cfg8 = _cfg(
        coco_fixture, tmp_path, "elastic", mesh_shape=(8, 1), batch_size=8
    )
    state = runtime.train(cfg8)
    ref = state_to_flat(state)
    path = latest_checkpoint(cfg8.save_dir)
    topo = lineage.read_sidecar_topology(path)
    assert topo is not None
    assert topo["device_count"] == 8
    assert topo["mesh_shape"] == [8, 1]
    assert topo["platform"] == "cpu"

    for n in (4, 1):
        cfg_n = cfg8.replace(mesh_shape=(n, 1))
        restored = runtime.setup_state(cfg_n, load=True)
        mesh = mesh_from_devices(jax.devices()[:n], (n, 1), ("data", "model"))
        placed = reshard_train_state(restored, cfg_n, mesh)
        got = state_to_flat(placed)
        assert set(got) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(got[k], ref[k], err_msg=f"n={n}: {k}")

    # the resumed run actually trains on the smaller mesh
    cfg4 = cfg8.replace(mesh_shape=(4, 1), num_epochs=2)
    resumed = runtime.setup_state(cfg4, load=True)
    start = int(resumed.step)
    cont = runtime.train(cfg4, state=resumed)
    assert int(cont.step) > start


# ---------------------------------------------------------------------------
# chaos e2e: wedge → watchdog abort 86 → supervised restart → bitwise resume
# ---------------------------------------------------------------------------


def _subprocess_env(extra=None):
    """Child env: the test env minus any SAT_FI_* leakage, with the
    suite's per-machine XLA compile cache so children skip recompiles."""
    env = {
        k: v for k, v in os.environ.items() if not k.startswith("SAT_FI_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    env.update(extra or {})
    return env


def _run_cli(args, env_extra=None, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "sat_tpu.cli", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=_subprocess_env(env_extra),
        timeout=timeout,
    )


def _flat_npz(path):
    with np.load(path, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def test_chaos_wedge_abort_restart_bitwise(coco_fixture, tmp_path):
    """The acceptance run: under --supervise with SAT_FI_WEDGE_AT_STEP=5,
    the wedged child is aborted by the watchdog with exit code 86 (stack
    dump artifact on disk), the supervisor restarts it from LAST_GOOD with
    faults disarmed, and the relaunched run finishes with a final
    checkpoint bitwise-identical to an uninterrupted control run."""
    chaos = dict(
        watchdog_interval=0.2,
        watchdog_step_s=5.0,
        watchdog_data_wait_s=120.0,
        watchdog_dispatch_s=120.0,
        watchdog_checkpoint_s=120.0,
        watchdog_grace_s=0.3,
        supervise_backoff_s=0.1,
    )
    control_cfg = _cfg(coco_fixture, tmp_path, "chaos_control", **chaos)
    control_cfg.save(str(tmp_path / "control.json"))
    chaos_cfg = _cfg(coco_fixture, tmp_path, "chaos_wedged", **chaos)
    chaos_cfg.save(str(tmp_path / "chaos.json"))

    control = _run_cli(["--config", str(tmp_path / "control.json")])
    assert control.returncode == 0, control.stdout + control.stderr
    control_final = latest_checkpoint(control_cfg.save_dir)
    assert control_final.endswith("6.npz")

    proc = _run_cli(
        ["--config", str(tmp_path / "chaos.json"), "--supervise"],
        env_extra={"SAT_FI_WEDGE_AT_STEP": "5"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # the first child wedged at step 5 and the watchdog climbed the ladder
    assert "sat_tpu watchdog: phase 'step' exceeded" in proc.stderr
    assert "aborting with exit code 86" in proc.stderr
    # the supervisor recognized 86 and restarted from LAST_GOOD
    assert "watchdog abort (wedged run; LAST_GOOD landed)" in proc.stderr
    assert "restarting from LAST_GOOD" in proc.stderr
    assert "run completed after 1 restart(s)" in proc.stderr
    # stack-dump artifact landed next to the telemetry outputs
    dump_path = os.path.join(
        chaos_cfg.summary_dir, "telemetry", "watchdog_stacks.txt"
    )
    assert os.path.isfile(dump_path)
    assert "phase=step" in open(dump_path).read()

    # LAST_GOOD advanced to the final step on the restarted incarnation
    assert lineage.last_good_step(chaos_cfg.save_dir) == 6
    chaos_final = latest_checkpoint(chaos_cfg.save_dir)
    assert chaos_final.endswith("6.npz")

    # bitwise-identical continuation: wedge + abort + resume changed nothing
    want = _flat_npz(control_final)
    got = _flat_npz(chaos_final)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
