"""``reducers/span_overlap_ms`` on a hand-made ring, and the files of the
metrics that read the loops' ``device_empty`` spans."""

import importlib
import inspect
import json
import os

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
MS = 1_000_000


def _run(spans, window=(0, 1000 * MS)):
    """A RunData over ``spans`` = [(name, start_ms, duration_ms)], as the
    program's ring would hand them over."""
    import harness

    run = harness.RunData(harness.Cell("vgg16-train-b256"), window, None)
    names = sorted({n for n, _, _ in spans})
    run._spans = (names, np.array([names.index(n) for n, _, _ in spans], np.int32),
                  np.array([s * MS for _, s, _ in spans], np.int64),
                  np.array([d * MS for _, _, d in spans], np.int64), np.zeros(len(spans), np.int64))
    return run


STEPS = [("train/step", 0, 100), ("train/step", 100, 100)]
ARGS = dict(span="train/device_empty", within="train/data_wait", per="train/step")


@pytest.mark.parametrize("spans,expect", [
    # a stretch of 30..70 over a wait of 50..90: 20 ms shared, two steps
    (STEPS + [("train/device_empty", 30, 40), ("train/data_wait", 50, 40)], 10.0),
    # the wait inside the stretch counts whole, a second wait outside it not at all
    (STEPS + [("train/device_empty", 10, 80), ("train/data_wait", 20, 30), ("train/data_wait", 120, 30)], 15.0),
    # two stretches, one wait across both
    (STEPS + [("train/device_empty", 0, 10), ("train/device_empty", 40, 10), ("train/data_wait", 5, 40)], 5.0),
    # stretches and waits that never meet (touching ends share nothing)
    (STEPS + [("train/device_empty", 0, 10), ("train/data_wait", 10, 10)], 0.0),
    # no phase span at all in the window: the stretch shares nothing with it
    (STEPS + [("train/device_empty", 0, 10)], 0.0),
    # a program from before it recorded stretches: nothing to read
    (STEPS + [("train/data_wait", 10, 10)], None),
    # no ``per`` in the window
    ([("train/device_empty", 0, 10), ("train/data_wait", 5, 10)], None),
])
def test_span_overlap_ms(spans, expect):
    from reducers import span_overlap_ms

    got = span_overlap_ms.read(_run(spans), **ARGS)
    assert got == (None if expect is None else pytest.approx(expect))


def test_span_overlap_ms_reads_spans_that_start_inside_the_window_only():
    from reducers import span_overlap_ms

    spans = [("train/step", 100, 100), ("train/step", 900, 100),             # the second starts outside
             ("train/device_empty", 50, 40), ("train/data_wait", 50, 40),    # before the window
             ("train/device_empty", 150, 40), ("train/data_wait", 160, 40)]
    assert span_overlap_ms.read(_run(spans, window=(100 * MS, 800 * MS)), **ARGS) == pytest.approx(30.0)


NEW = ["train_device_empty_share", "decode_device_empty_share", "train_empty_data_wait_ms",
       "train_empty_log_io_ms", "train_empty_place_ms", "train_empty_dispatch_ms",
       "decode_empty_detok_ms", "decode_empty_data_wait_ms", "decode_empty_dispatch_ms"]


@pytest.mark.parametrize("name", NEW)
def test_each_device_empty_metric_names_a_reducer_that_takes_its_arguments(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    spec = json.load(open(os.path.join(BENCH_DIR, "metrics", name + ".json")))
    assert all(spec[k] == entry[k] for k in ("unit", "moves", "layer", "source"))
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    reader = importlib.import_module("reducers." + spec["reducer"])
    inspect.signature(reader.read).bind(None, **spec["args"])
    family = name.split("_")[0]
    assert all(v.startswith(family + "/") for v in spec["args"].values())
    train = entry["workloads"] == ["vgg16-train-b256"]
    assert train == (family == "train") and (train or len(entry["workloads"]) == 4)


def test_a_program_without_the_spans_leaves_the_metrics_out():
    """The parent under this PR's benchmark files: no ``device_empty`` span in
    the ring, so every new reader returns None and the line leaves them out."""
    import harness

    cell = harness.Cell("vgg16-train-b256")
    run = _run(STEPS + [("train/data_wait", 10, 10), ("train/log_io", 30, 2)])
    got = harness.read_per_layer(cell, run)
    assert not set(NEW) & set(got) and "train_data_wait_share" in got
