"""The trace reduction, on hand-made planes and on the small extract of a
real v5e trace kept in data/ (written by ``xtrace.record`` on the chip)."""

import os

import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


def _plane(name, lines):
    return xtrace._Recorded({"name": name, "lines": [
        xtrace._Recorded({"name": ln, "events": [
            xtrace._Recorded({"name": n, "start_ns": s, "duration_ns": d}) for n, s, d in evs]})
        for ln, evs in lines.items()]})


def test_busy_is_the_union_of_op_intervals_and_gaps_are_found():
    ops = [("%fusion.1", 1000, 400), ("%while.2", 1200, 800),      # overlap: union 1000..2000
           ("%fusion.1", 3000, 500), ("%fused_attend.7", 3600, 100)]
    modules = [("jit_train_step(123)", 1000, 1000), ("jit_train_step(123)", 3000, 700)]
    host = _plane("/host:CPU", {"main": [("x", 0, 10 ** 9)]})
    dev = _plane("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": modules, "Steps": []})
    r = xtrace.reduce_planes([host, dev])
    assert abs(r["busy_s"] - 1.6e-6) < 1e-12            # 1000 + 500 + 100 ns
    assert abs(r["window_s"] - 2.7e-6) < 1e-12          # 1000 .. 3700
    assert r["modules"] == {"jit_train_step": [1e-6, 7e-7]}
    assert r["ops"][0][0] == "%fusion.1" and abs(r["ops"][0][1] - 9e-7) < 1e-15
    assert r["gaps"][0] == (1e-6, 1e-6)                 # idle 2000..3000, 1000 ns after the start
    assert xtrace.reduce_planes([host]) is None          # no device plane: nothing to read


def test_reduction_of_a_recorded_v5e_trace():
    planes = xtrace.load_recorded(os.path.join(HERE, "data", "trace_small.json"))
    r = xtrace.reduce_planes(planes)
    assert r is not None and r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any(name.startswith("jit_") for name in r["modules"])
    assert all(name.startswith("%") for name, _ in r["ops"])
