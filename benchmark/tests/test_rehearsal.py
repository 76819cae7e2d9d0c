"""Each driver's control flow, tiny, on the CPU: it must end without a
result line that names a TPU; and with the timed path broken underneath,
``correct`` must come out false."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT, bench_with_serve

CELLS = ["vgg16-train-b256", "vgg16-serve-poisson", "vgg16-eval-beam3-b512"]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_passes_and_prints_no_result_line(cell, tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", cell, "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "0", "--cpu-rehearsal", "--bench-json", bench_with_serve(tmp_path)],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["device"]["platform"] == "cpu"
    assert "metrics" not in last and "correct" not in last


def test_a_seed_s_data_is_kept_and_its_next_run_starts_from_step_0_again():
    """The second run of a seed finds JPEGs, shards and the step-0 checkpoint
    in the checkout, and resumes from step 0 although the first run left its
    final checkpoint behind: the same first losses come out."""
    import glob
    import shutil

    for old in glob.glob(os.path.join(BENCH_DIR, ".work", CELLS[0], "77-*")):
        shutil.rmtree(old)
    notes = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELLS[0], "--seed", "77",
             "--seconds", "3", "--trace", "0", "--cpu-rehearsal"],
            cwd=ROOT, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
        assert lines[-1]["rehearsal"] == "passed"
        notes.append(next(ln["notes"] for ln in lines if "notes" in ln))
    assert [n["setup_phases_s"]["reused"] for n in notes] == [False, True]
    assert notes[0]["losses_program"] == notes[1]["losses_program"]


def test_the_program_s_own_int8_encoder_moves_the_served_scores(tmp_path):
    """The serve cell's control at a size a test can hold: the program with
    its lower-precision path switched on (``encoder_quant=int8``) in the
    program's place, same seed, same replies checked.  Its gap to the
    reference has to stand well clear of the sound program's."""
    gaps = []
    for switch in ([], ["--program", "encoder_quant=int8"]):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", "vgg16-serve-poisson",
             "--seed", "5", "--seconds", "3", "--trace", "0", "--cpu-rehearsal",
             "--bench-json", bench_with_serve(tmp_path)] + switch,
            cwd=ROOT, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
        gaps.append(next(ln["notes"]["score_gap_mean"] for ln in lines if "notes" in ln))
    assert gaps[1] > 3 * gaps[0], gaps


def test_off_the_chip_a_real_run_fails_before_any_work():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and "no accelerator" in proc.stderr
    assert not any(line.startswith('{"correct"') for line in proc.stdout.splitlines())


@pytest.mark.parametrize("cell,sabotage,number", [
    ("vgg16-train-b256", "identity", "update_norm_gap"),
    ("vgg16-eval-beam3-b512", "token", "rank_gap"),
])
def test_a_broken_timed_path_is_not_correct(cell, sabotage, number):
    """Skips the harness's look for a chip (rehearsal sizes on the CPU) and
    drives the rest of a run with the step returning its state unchanged,
    or one served token altered where it is produced."""
    code = (
        "import sys, json, types; sys.argv=['run.py']; import run, harness;"
        f"a=types.SimpleNamespace(workload={cell!r}, seed=9, seconds=3.0, trace=0, cpu_rehearsal=True, rates=None);"
        f"cell, facts, out = run.run_cell(a, sabotage={sabotage!r});"
        "print(json.dumps({c['name']: c['value'] for c in out.checks}))"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=BENCH_DIR, capture_output=True, text=True,
                          timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    numbers = json.loads(proc.stdout.strip().splitlines()[-1])
    # a state that never changes leaves the update's norm at 0 against the
    # reference's: the gap is the whole norm; an altered token falls out of
    # the reference's best beam+1 words
    assert numbers[number] > (0.5 if sabotage == "identity" else 0.05), numbers
