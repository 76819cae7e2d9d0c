"""chip_smoke.py, rehearsed on the CPU (the guide's first two rehearsals).

The script's real run needs a TPU; here its control flow runs at a tiny
size with ``--cpu-rehearsal``.  What these cases pin: the rehearsal
passes and never prints the ``ok`` line a chip run ends with; without the
switch, or with a phase that raises, or alone in a directory, the script
exits non-zero and prints no result; ``--chips 4`` runs the mesh and
fleet phases and nothing else.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, cwd=REPO, script=SMOKE, timeout=420, **env):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, timeout=timeout,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
    )


def _facts(stdout):
    """The JSON lines of a run, in order."""
    return [json.loads(l) for l in stdout.splitlines() if l.startswith("{")]


def test_cpu_rehearsal_passes_and_prints_no_ok_line():
    proc = _run("--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    facts = _facts(proc.stdout)
    assert [f["phase"] for f in facts[:-1]] == [
        "device", "setup", "train", "eval", "kernel", "serve",
    ]
    assert facts[-1] == json.loads(proc.stdout.strip().splitlines()[-1])
    assert facts[-1]["rehearsal"] == "passed"
    assert facts[-1]["device"]["platform"] == "cpu"
    assert '"ok"' not in proc.stdout
    by = {f["phase"]: f for f in facts[:-1]}
    assert by["train"]["steps"] >= 3 and by["train"]["loss_falling"]
    assert by["serve"]["compiles_since_ready"] == 0
    assert by["serve"]["requests"] >= 4
    assert by["serve"]["encode_cache"]["hits"] >= 1


def test_without_an_accelerator_it_fails_before_any_phase():
    proc = _run()
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not _facts(proc.stdout)


def test_a_failing_phase_exits_nonzero(monkeypatch, capsys):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    def broken(*args, **kwargs):
        raise RuntimeError("phase made to raise")

    monkeypatch.setattr(chip_smoke, "phase_train", broken)
    assert chip_smoke.main(["--cpu-rehearsal"]) == 1
    out = capsys.readouterr()
    assert "phase made to raise" in out.err
    assert '"ok"' not in out.out and '"rehearsal"' not in out.out


def test_alone_in_a_directory_it_fails(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path)
    proc = _run("--cpu-rehearsal", cwd=str(tmp_path), script=alone)
    assert proc.returncode != 0
    assert "No module named" in proc.stderr
    assert '"ok"' not in proc.stdout and '"rehearsal"' not in proc.stdout


def test_chips4_rehearsal_runs_only_mesh_and_fleet():
    proc = _run(
        "--cpu-rehearsal", "--chips", "4",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    facts = _facts(proc.stdout)
    assert {f["phase"] for f in facts[:-1]} == {
        "setup", "device", "mesh", "fleet",
    }
    assert facts[-1]["rehearsal"] == "passed"
    assert facts[-1]["device"]["count"] == 4
    assert '"ok"' not in proc.stdout
    arms = [f["arm"] for f in facts[:-1] if f["phase"] == "mesh"]
    assert arms[:4] == [
        "one_device", "mesh(4, 1)", "mesh(2, 2)", "context_parallel=2",
    ]
    fleet = facts[-2]
    assert fleet["replicas"] == 4 and fleet["routable"] == 4
    assert not any(fleet["router_failures"].values())
    assert all(d["requests"] >= 2 for d in fleet["replica_devices"].values())
