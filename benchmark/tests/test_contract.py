"""BENCHMARK.json against the contract's limits, and against the files
the harness finds by the names in it."""

import json
import os
import re

from conftest import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_shape_of_the_file():
    import harness

    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"] and all(_line(w) for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert (BENCH["run_seconds"] + 60) * (2 + 14 * 24) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24 and os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic")) and _line(w["why"])
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    cell_names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        spec = json.load(open(harness.metric_file(m["name"])))
        assert all(spec[k] == m[k] for k in ("unit", "moves", "layer", "source") if k in spec)
        assert os.path.exists(os.path.join(BENCH_DIR, "reducers", spec["reducer"] + ".py"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cell_names


def test_every_cell_reports_setup_one_more_end_to_end_and_a_per_layer_metric():
    import harness

    for w in BENCH["workloads"]:
        cell = harness.Cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end()}
        # what the mix says it reports is what BENCHMARK.json's lists say of the cell
        listed = {m["name"] for m in BENCH["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])}
        assert e2e == listed == {"setup_s"} | set(cell.mix["end_to_end"])
        assert "setup_s" in e2e and len(e2e) >= 2 and len(cell.per_layer()) >= 1
        for m in cell.per_layer():          # a metric is read only where its end-to-end metric is
            assert m["moves"] in e2e
        assert cell.config["reduced"] == [] and cell.config["assumed"]
        assert os.path.exists(os.path.join(BENCH_DIR, "drivers", cell.mix["driver"] + ".py"))


def harness_metric_file(name):
    import harness

    return harness.metric_file(name)


def test_a_cell_added_as_entries_alone_reports_its_mix_s_metrics(tmp_path, monkeypatch):
    """The README's promise: `resnet50-eval-beam3-b512` (the eval mix on the
    other configuration, whose file is kept) is a `configs` entry, a
    `workloads` entry and its name on the lists of the metrics its mix
    reports; no file under benchmark/ changes."""
    import harness

    bench = json.loads(json.dumps(BENCH))
    new = "resnet50-eval-beam3-b512"
    bench["configs"].append({"name": "sat-resnet50", "source": "He et al. 2015, arXiv:1512.03385",
                             "file": "benchmark/configs/sat-resnet50.json", "reduced": [], "why": "the other tiling"})
    bench["workloads"].append({"name": new, "config": "sat-resnet50", "traffic": "decode-offline-b512",
                               "chips": 1, "why": "the eval mix on the other tiling"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "vgg16-eval-beam3-b512" in m.get("workloads", []):
            m["workloads"].append(new)
    root = tmp_path / "repo"
    root.mkdir()
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(BENCH_DIR, root / "benchmark")
    monkeypatch.setattr(harness, "ROOT", str(root))
    old, cell = harness.Cell("vgg16-eval-beam3-b512"), harness.Cell(new)
    assert [m["name"] for m in cell.end_to_end()] == [m["name"] for m in old.end_to_end()]
    assert [m["name"] for m in cell.per_layer()] == [m["name"] for m in old.per_layer()]
    assert cell.model["cnn"] == "resnet50"
    run = harness.RunData(cell, (0, 1), None)
    run.measured.update(captions_per_s=100.0, setup_s=30.0)
    assert run.e2e == {"setup_s": 30.0, "decode_captions_per_s": 100.0}


def test_file_names_under_paths_use_the_characters_of_a_name():
    serve = json.load(open(os.path.join(BENCH_DIR, "tests", "data", "serve_cell.json")))
    used = {os.path.basename(harness_metric_file(m["name"])) for m in BENCH["per_layer"] + serve["per_layer"]}
    assert used == set(os.listdir(os.path.join(BENCH_DIR, "metrics")))      # no file read by nothing
    for base, _dirs, files in os.walk(BENCH_DIR):
        if ".work" in base or "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", rel), rel
