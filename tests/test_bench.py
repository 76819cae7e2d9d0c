"""bench.py contract tests.

bench.py is one process that runs the bench and exits with its own
status: whatever raises ends it non-zero with no metric line, and a
device whose peak FLOP/s is not recorded is an error, not a row with a
silently missing ``mfu``.  (It cannot complete on the CPU by design — a
measurement path that finds no accelerator fails.)
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def test_bench_exits_nonzero_when_run_raises():
    # a knob the run rejects (Config validation) must end the process
    # with its own failure status and no JSON line on stdout
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_CNN="bogus_cnn")
    proc = subprocess.run(
        [sys.executable, BENCH],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode not in (0, None), proc.stderr[-2000:]
    assert "bogus_cnn" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.strip().startswith("{")]


def test_unknown_device_kind_is_an_error():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert bench._peak_flops(v5e) == 197e12
    cpu = types.SimpleNamespace(device_kind="cpu", platform="cpu")
    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        bench._peak_flops(cpu)


def test_eval_ab_emits_summary_contract(tmp_path):
    """bench_eval_ab's parent: interleaved fresh/resident subprocess arms,
    one summary JSON line with the per-arm means and the clean-process
    number as `value` (the PERF.md 802-vs-620 discrepancy protocol)."""
    out = tmp_path / "ab.json"
    proc = subprocess.run(
        [sys.executable, "scripts/bench_eval_ab.py", "--cpu",
         "--image-size", "32", "--batch", "2", "--beam", "2",
         "--iters", "1", "--windows", "2", "--steps", "1",
         "--repeats", "1", "--budget-s", "300", "--out", str(out)],
        # outer > sum of child budgets (2 arms x 300s), repo convention
        capture_output=True, text=True, timeout=700,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    summary = json.loads(out.read_text())
    assert summary["metric"] == "eval_images_per_sec"
    assert summary["value"] == summary["fresh_mean"] > 0
    assert summary["resident_mean"] > 0
    assert summary["resident_over_fresh"] > 0
    arms = sorted(r["arm"] for r in summary["rows"])
    assert arms == ["fresh", "resident"]
    for r in summary["rows"]:
        assert len(r["windows_batch_ms"]) == 2
