"""The seam between the program and the files that drive it on the chip.

The benchmark (``benchmark/``, run by the driver and by nobody in tier-1)
builds the program's ``Config`` from the ``model`` and ``program`` blocks
of a configuration file and the ``program`` block of a traffic mix
(``benchmark/harness.py::program_config``), and its drivers take a handful
of names from the package.  A ``Config`` field or a function can so be
load-bearing for a cell without any test here knowing: these fail on the
CPU, the day a change renames or deletes what a cell passes, before the
chip says so.  They read ``benchmark/`` and ``BENCHMARK.json`` and edit
nothing.
"""

import dataclasses
import glob
import importlib
import json
import os

import pytest

from sat_tpu.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(
    os.path.relpath(path, REPO)
    for kind in ("configs", "traffic")
    for path in glob.glob(os.path.join(REPO, "benchmark", kind, "*.json"))
)
FIELDS = {f.name for f in dataclasses.fields(Config)}


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as f:
        return json.load(f)


def _validate(*blocks):
    """``Config`` from the blocks laid over each other, as
    ``program_config`` lays them (lists arrive as tuples)."""
    settings = {}
    for block in blocks:
        settings.update(block)
    return Config(**{
        k: tuple(v) if isinstance(v, list) else v for k, v in settings.items()
    })


@pytest.mark.parametrize("relpath", FILES)
def test_blocks_are_config_fields_and_validate(relpath):
    doc = _load(relpath)
    rehearsal = doc.get("rehearsal", {}).get("program", {})
    blocks = {
        "model": doc.get("model", {}),
        "program": doc.get("program", {}),
        "rehearsal.program": rehearsal,
    }
    assert blocks["model"] or blocks["program"], f"{relpath} passes nothing"
    for name, block in blocks.items():
        assert set(block) <= FIELDS, (
            f"{relpath} {name}: not Config fields: {sorted(set(block) - FIELDS)}"
        )
    _validate(blocks["model"], blocks["program"])
    _validate(blocks["model"], blocks["program"], rehearsal)

    # a traffic mix also validates over every configuration a cell pairs it with
    if not relpath.startswith("benchmark/traffic"):
        return
    bench = _load("BENCHMARK.json")
    config_file = {c["name"]: c["file"] for c in bench["configs"]}
    mix = os.path.splitext(os.path.basename(relpath))[0]
    for cell in bench["workloads"]:
        if cell["traffic"] == mix:
            config = _load(config_file[cell["config"]])
            _validate(config["model"], config.get("program", {}), blocks["program"])


def test_names_the_drivers_take_from_the_program_are_callable():
    taken = {
        "sat_tpu.cli": ["main"],
        "sat_tpu.runtime": ["beam_search_jit", "make_jit_train_step"],
        "sat_tpu.telemetry": ["get"],
        "sat_tpu.telemetry.xla": ["entries"],
        "sat_tpu.train.checkpoint": ["save_checkpoint"],
        "sat_tpu.train.optimizer": ["make_optimizer"],
        "sat_tpu.train.step": ["TrainState", "create_train_state", "split_trainable"],
        "sat_tpu.utils.compile_cache": ["enable"],
    }
    for module, names in taken.items():
        mod = importlib.import_module(module)
        for name in names:
            assert callable(getattr(mod, name, None)), f"{module}.{name}"
