"""The per-scope device metrics: ``reducers/trace_scope_ms`` on a recorded
``op_totals`` + ``op_scopes`` pair, ``tools/gapcause``'s containment on
hand-made planes, and the new entries against their files."""

import json
import os
import sys
import types

import pytest

from conftest import BENCH_DIR, ROOT

from reducers import trace_scope_ms

COLUMNS = ["name", "shape", "container", "op_name"]
# what xla.analyze keeps of a (made-up) train step and of a second program of the same run
TRAIN = {"columns": COLUMNS, "rows": [
    ["%while.14", "(s32[],f32[4,8])", True, "jit(train_step)/jvp(loss)/while"],
    ["%fusion.7", "f32[4,8]", False, "jit(train_step)/jvp(loss)/while/body/closed_call/decoder/lstm/dot_general"],
    ["%fusion.8", "f32[20,4,8]", False, "jit(train_step)/jvp(loss)/while/body/dynamic_update_slice"],
    ["%fusion.9", "f32[4,8]", False, "jit(train_step)/transpose(jvp(loss))/while/body/closed_call/decoder/lstm/dot_general"],
    ["%convolution.3", "bf16[4,14,14,8]", False, "jit(train_step)/jvp(loss)/encoder/VGG16/conv5_3/conv/conv_general_dilated"],
    ["%fusion.30", "f32[8]", False, "jit(train_step)/optimizer/mul"],
    ["%copy.5", "f32[4,8]", False, ""],
    ["%fusion.4", "f32[2]", False, "jit(train_step)/jvp(loss)/decoder/init/tanh"],
]}
OTHER = {"columns": COLUMNS, "rows": [["%fusion.4", "f32[2]", False, "jit(other)/decoder/init/tanh"]]}
# the trace names an op by its whole HLO line, layouts and operands in it
OP_TOTALS = {
    "%while.14 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}) while((s32[]{:T(128)}, f32[4,8]{1,0}) %tuple.1), condition=%c, body=%b": 0.060,
    "%fusion.7 = f32[4,8]{1,0:T(4,128)S(1)} fusion(f32[4,8]{1,0} %p.1), kind=kOutput": 0.030,
    "%fusion.8 = f32[20,4,8]{2,1,0:T(4,128)} fusion(f32[20,4,8]{2,1,0} %p.2)": 0.020,
    "%fusion.9 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %p.3)": 0.040,
    "%convolution.3 = bf16[4,14,14,8]{3,2,1,0:T(8,128)(2,1)} convolution(bf16[4,14,14,8] %p.4)": 0.100,
    "%fusion.30 = f32[8]{0} fusion(f32[8]{0} %p.5)": 0.002,
    "%copy.5 = f32[4,8]{1,0} copy(f32[4,8]{0,1} %p.6)": 0.006,
    "%fusion.4 = f32[2]{0} fusion(f32[2]{0} %p.7)": 0.002,
    "%fusion.99 = f32[3]{0} fusion(f32[3]{0} %p.8)": 0.500,           # no instruction of this program
}


def _run(monkeypatch, entries, modules=(("jit_train_step", [0.1, 0.1]),)):
    from sat_tpu.telemetry import xla

    monkeypatch.setattr(xla, "entries", lambda: entries)
    return types.SimpleNamespace(trace={"op_totals": OP_TOTALS, "modules": dict(modules)})


def _read(run, pick):
    return trace_scope_ms.read(run, program="train_step", module="^jit_train_step", rules="train_step", pick=pick)


def test_leaf_sum_per_run_container_skipped_first_rule_wins(monkeypatch):
    run = _run(monkeypatch, {"train_step": {"op_scopes": TRAIN}})
    # two runs of the module in the trace: every bucket is halved, in ms
    assert _read(run, "encoder") == pytest.approx(50.0)
    assert _read(run, "decoder_fwd") == pytest.approx(16.0)       # %fusion.7 + %fusion.4; the while is not counted
    assert _read(run, "decoder_bwd") == pytest.approx(20.0)       # transpose( wins over decoder/
    assert _read(run, "scan_plumbing") == pytest.approx(10.0)     # while/body under no scope, though under jvp(loss)
    assert _read(run, "other") == pytest.approx(1.0)
    # leaf time 0.200 s; only the copy the compiler made carries no scope
    assert _read(run, "unscoped") == pytest.approx(100.0 * 0.006 / 0.200)


def test_a_run_the_trace_cut_counts_as_the_part_it_lasted(monkeypatch):
    # the trace began inside a run: its module event lasted half of a whole one's time
    run = _run(monkeypatch, {"train_step": {"op_scopes": TRAIN}},
               modules=(("jit_train_step", [0.05, 0.1, 0.1]), ("jit_reshape", [1e-5] * 9)))
    assert trace_scope_ms.runs_of(run.trace["modules"], "^jit_train_step") == pytest.approx(2.5)
    assert _read(run, "encoder") == pytest.approx(100.0 / 2.5)
    assert _read(run, "unscoped") == pytest.approx(100.0 * 0.006 / 0.200)     # a share: no run count in it


def test_a_key_two_programs_share_is_unscoped_never_in_a_bucket(monkeypatch):
    run = _run(monkeypatch, {"train_step": {"op_scopes": TRAIN}, "decode/encode": {"op_scopes": OTHER}})
    assert _read(run, "decoder_fwd") == pytest.approx(15.0)       # %fusion.4 left the bucket
    assert _read(run, "unscoped") == pytest.approx(100.0 * 0.008 / 0.200)


def test_nothing_to_read_is_none_not_an_error(monkeypatch):
    # a program from before it kept the map (the parent commit), no trace, no run of the module
    assert _read(_run(monkeypatch, {"train_step": {"memory": {}}}), "encoder") is None
    assert _read(_run(monkeypatch, {}), "encoder") is None
    run = _run(monkeypatch, {"train_step": {"op_scopes": TRAIN}})
    run.trace = None
    assert _read(run, "encoder") is None
    assert _read(_run(monkeypatch, {"train_step": {"op_scopes": TRAIN}}, modules=()), "encoder") is None


def test_head_drops_layouts_and_index_comments():
    assert trace_scope_ms.head("%a.1 = f32[2,3]{1,0:T(2,128)S(1)} fusion(f32[2]{0} %b)") == ("%a.1", "f32[2,3]")
    line = "%w.2 = (s32[]{:T(128)}, /*index=1*/f32[2]{0}) while((s32[], f32[2]) %t), body=%b"
    assert trace_scope_ms.head(line) == ("%w.2", "(s32[],f32[2])")
    assert trace_scope_ms.head("jit_train_step(123)") is None


def test_rule_files_and_metric_files_agree():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    declared = {m["name"] for m in bench["per_layer"]}
    for name in sorted(os.listdir(os.path.join(BENCH_DIR, "metrics"))):
        spec = json.load(open(os.path.join(BENCH_DIR, "metrics", name)))
        if spec["reducer"] != "trace_scope_ms":
            continue
        assert name[:-5] in declared
        rules = json.load(open(os.path.join(BENCH_DIR, "scopes", spec["args"]["rules"] + ".json")))
        assert rules["program"] == spec["args"]["program"]
        assert spec["args"]["pick"] in {b for b, _ in rules["rules"]} | {"unscoped"}
        trace_scope_ms.load_rules(spec["args"]["rules"])            # every pattern compiles


# ---- tools/gapcause.py: containment on one clock


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=float(start), duration_ns=float(dur), stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[types.SimpleNamespace(name=n, events=e) for n, e in lines])


def test_gapcause_names_the_innermost_span_that_contains_a_gap():
    sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
    import gapcause

    ms = 1_000_000
    device = _plane("/device:TPU:0", [("XLA Modules", [
        _ev("jit_encode_fn(1)", 0, 100 * ms), _ev("jit_beam_search(2)", 100 * ms + 50_000, 300 * ms),
        _ev("jit_encode_fn(1)", 421 * ms, 100 * ms), _ev("jit_beam_search(2)", 540 * ms, 300 * ms)])])
    host = _plane("/host:CPU", [
        ("python", [_ev("decode/dispatch", 410 * ms, 5 * ms, i=4), _ev("decode/drain", 390 * ms, 32 * ms, i=3),
                    _ev("decode/drain/detok", 399 * ms, 23 * ms, i=3), _ev("decode/data_wait", 525 * ms, 10 * ms, i=5),
                    _ev("PjitFunction(f)", 0, 900 * ms)]),
        ("python", [_ev("data/decode_batch", 300 * ms, 230 * ms, i=7)]),
    ])
    report = gapcause.gap_report(iter([host, device]), min_gap_ns=200_000)
    assert report["modules"] == 4 and report["loop_thread"] == "python.0" and report["annotated_spans"] == 5
    first, second = report["gaps"]                                # the 50-us gap is under the threshold
    assert first["ms"] == pytest.approx(20.95) and first["after"] == "jit_beam_search"
    assert first["inside"] == ["decode/drain/detok#3", "decode/drain#3"]      # innermost first, with the batch
    assert first["other_threads"] == ["data/decode_batch"]
    # no span holds all of the second gap (521..540 ms): the overlap is listed, not a nearest match
    assert second["inside"] == [] and second["overlaps"] == [["decode/data_wait#5", 10.0]]
    rows = gapcause.summary(report["gaps"])
    assert rows[0]["cause"] == "decode/drain/detok" and rows[1]["cause"] == "overlaps decode/data_wait"
