"""What every driver shares: the cell's files, the device check, the
program's Config and checkpoint from the benchmark's own weights, the
compile meter, the profiler window, the per-layer readers and the result
line.  Imports jax and the program lazily, after the device check.
"""

from __future__ import annotations

import gc
import hashlib
import importlib
import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# what --cpu-rehearsal shrinks: control flow only, never a result
REHEARSAL_MODEL = dict(
    image_size=32, dim_embedding=16, num_lstm_units=16, dim_initialize_layer=16,
    dim_attend_layer=16, dim_decode_layer=32, vocabulary_size=128,
)


# data of a seed (JPEGs, COCO files, shard cache, step-0 checkpoint) is kept
# in the checkout for the next run of that seed; this many seeds to a cell
KEEP_SEEDS = 6


class BenchError(RuntimeError):
    """A run that must end non-zero with no result line."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's workloads with its files resolved."""

    def __init__(self, name: str, rehearsal: bool = False, switches: Optional[dict] = None,
                 bench_json: Optional[str] = None) -> None:
        # bench_json: builder's option (the tests keep a serve cell in a file of their own)
        self.bench = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(os.path.join(ROOT, configs[self.entry["config"]]["file"]))
        self.mix = load_json(os.path.join(BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.rehearsal = rehearsal
        self.model = dict(self.config["model"])
        if rehearsal:
            self.model.update(REHEARSAL_MODEL)
            small = self.mix.get("rehearsal", {})
            program = {**self.mix.get("program", {}), **small.get("program", {})}
            self.mix = {**self.mix, **small, "program": program}
        if switches:            # builder's option --program: never the driver's command
            self.mix = {**self.mix, "program": {**self.mix.get("program", {}), **switches}}

    def end_to_end(self) -> List[dict]:
        """BENCHMARK.json's entries of ``setup_s`` and of the metrics that
        the mix's ``end_to_end`` block names (metric -> the driver's
        quantity that fills it).  The cell's side decides; BENCHMARK.json's
        ``workloads`` lists have to agree, since the driver's check reads
        those."""
        wanted = ["setup_s"] + list(self.mix.get("end_to_end", {}))
        known = {m["name"]: m for m in self.bench["end_to_end"]}
        for name in wanted:
            if name not in known:
                raise BenchError(f"mix {self.entry['traffic']!r} reports {name!r}, which is no "
                                 "end_to_end metric of BENCHMARK.json")
            if self.name not in known[name].get("workloads", [self.name]):
                raise BenchError(f"BENCHMARK.json's {name}.workloads does not list {self.name!r}")
        return [known[name] for name in wanted]

    def per_layer(self) -> List[dict]:
        """By the contract's rule: a metric whose ``workloads`` lists the
        cell, or one without the key that moves a metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m else m["moves"] in reported)]

    def workdir(self, seed: int, *sized_by) -> Tuple[str, str, bool]:
        """(kept, run, reused): ``.work/<cell>/<seed>-<key>/`` holds what is
        made from the seed alone and is kept for the next run of that seed
        (``reused`` says it is there and complete); ``<kept>/run/`` holds
        what one run writes and is wiped now.  The key is a hash of the
        configuration, the mix and ``sized_by``, so a changed file never
        meets stale data.  Fixed paths inside the checkout."""
        key = hashlib.sha1(json.dumps([self.config, self.mix, self.rehearsal, sized_by],
                                      sort_keys=True).encode()).hexdigest()[:10]
        base = os.path.join(BENCH_DIR, ".work", self.name)
        kept = os.path.join(base, f"{int(seed)}-{key}")
        reused = os.path.exists(os.path.join(kept, ".complete"))
        if not reused:
            shutil.rmtree(kept, ignore_errors=True)
        os.makedirs(kept, exist_ok=True)
        os.utime(kept)
        others = sorted((d for d in os.listdir(base) if os.path.join(base, d) != kept),
                        key=lambda d: os.path.getmtime(os.path.join(base, d)), reverse=True)
        for d in others[KEEP_SEEDS - 1:]:
            shutil.rmtree(os.path.join(base, d), ignore_errors=True)
        run = os.path.join(kept, "run")
        shutil.rmtree(run, ignore_errors=True)
        os.makedirs(run)
        return kept, run, reused


def mark_complete(kept: str) -> None:
    with open(os.path.join(kept, ".complete"), "w") as f:
        f.write("the seed's data and step-0 checkpoint are whole\n")


def device_facts() -> Dict[str, Any]:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def require_device(chips: int, rehearsal: bool) -> Tuple[Dict[str, Any], Optional[dict]]:
    """(device facts, the device's row of peaks.json).  Raises unless jax's
    first device is a TPU the table knows and there are enough of them;
    the rehearsal instead refuses anything but the CPU."""
    facts = device_facts()
    if rehearsal:
        if facts["platform"] != "cpu":
            raise BenchError("--cpu-rehearsal is for the CPU; run without it on a chip")
        return facts, None
    if facts["platform"] != "tpu":
        raise BenchError(f"no accelerator: jax's first device is {facts}")
    if facts["count"] < chips:
        raise BenchError(f"the cell needs {chips} chip(s), jax reports {facts['count']}")
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if facts["kind"] not in peaks:
        raise BenchError(f"device_kind {facts['kind']!r} is not in benchmark/peaks.json")
    return facts, peaks[facts["kind"]]


def memory_stats() -> Dict[str, int]:
    """The runtime's counters of the fullest chip: ``bytes_in_use`` (live
    buffers now) and ``peak_bytes_in_use`` (their high-water mark)."""
    import jax

    best: Dict[str, int] = {"bytes_in_use": 0, "peak_bytes_in_use": 0}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if int(stats.get("peak_bytes_in_use", 0)) >= best["peak_bytes_in_use"]:
            best = {k: int(stats.get(k, 0)) for k in best}
    return best


def program_temps(*names: str) -> Dict[str, int]:
    """Temporaries of the named programs as the compiler laid them out
    (``memory_analysis().temp_size_in_bytes``), from the program's own
    compile accounting (``sat_tpu.telemetry.xla``, on with telemetry)."""
    from sat_tpu.telemetry import xla

    entries = xla.entries()
    return {n: int(entries[n]["memory"]["temp_bytes"]) for n in names
            if "temp_bytes" in entries.get(n, {}).get("memory", {})}


def memory_peak(live_in_window: Sequence[int] = (), temps: Optional[Dict[str, int]] = None) -> dict:
    """The peak on the fullest chip, and what it is made of.  On this
    runtime ``peak_bytes_in_use`` counts live buffers and leaves out what
    a program takes while it runs (tools/memprobe.py; PERF.md section 2),
    so the peak is the larger of that counter and: the most live bytes
    sampled while the window's programs were in flight + the temporaries
    of the largest of them.  Where the counter does hold the temporaries
    it is the larger of the two and nothing is added."""
    counter = memory_stats()["peak_bytes_in_use"]
    live = max(live_in_window, default=0)
    temp = max((temps or {}).values(), default=0)
    return {"peak": max(counter, live + temp), "counter_peak": counter,
            "live_in_window": live, "program_temps": temps or {}}


class CompileMeter:
    """Backend compile seconds and cache hits/misses, each stamped with the
    host clock so that compiles INSIDE the window can be counted (there
    must be none).  jax.monitoring listeners cannot be removed: one per
    process."""

    def __init__(self) -> None:
        from jax import monitoring

        self.events: List[Tuple[int, float]] = []     # (perf_counter_ns at end, seconds)
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter_ns(), float(seconds)))

    def seconds_before(self, t_ns: int) -> float:
        return sum(s for t, s in self.events if t <= t_ns)

    def count_between(self, t0_ns: int, t1_ns: int) -> int:
        return sum(1 for t, _ in self.events if t0_ns < t <= t1_ns)


# ---------------------------------------------------------------------------
# the program's Config and checkpoint, from the benchmark's own files
# ---------------------------------------------------------------------------


def program_config(cell: Cell, kept: str, run: str, seed: int, **extra):
    """The program's Config for this cell: the configuration file's model
    block (every width), its ``program`` block, the traffic mix's
    ``program`` block, inputs under ``kept`` and outputs under ``run``."""
    from sat_tpu.config import Config

    k = lambda *p: os.path.join(kept, *p)  # noqa: E731
    r = lambda *p: os.path.join(run, *p)  # noqa: E731
    settings = dict(cell.model)
    settings.update(cell.config.get("program", {}))
    settings.update(cell.mix.get("program", {}))
    settings.update(
        seed=int(seed % (2 ** 31 - 1)),      # the program folds seed+1 into an int32 key
        train_image_dir=k("train", "images"), train_caption_file=k("train", "captions.json"),
        temp_annotation_file=k("train", "anns.csv"), temp_data_file=k("train", "data.npy"),
        eval_image_dir=k("val", "images"), eval_caption_file=k("val", "captions.json"),
        eval_result_dir=r("val_results"), eval_result_file=r("val_results.json"),
        test_image_dir=k("val", "images"), test_result_dir=r("test"),
        test_result_file=r("test", "results.csv"),
        vocabulary_file=k("vocabulary.csv"), save_dir=r("models"),
        summary_dir=r("summary"), shard_cache_dir=k("shards"),
        max_train_ann_num=None, max_eval_ann_num=None,
    )
    settings.update(extra)
    for key, v in list(settings.items()):
        if isinstance(v, list):
            settings[key] = tuple(v)
    return Config(**settings)


def write_checkpoint(config, weights: Dict[str, Any], save_dir: str) -> str:
    """The benchmark's seeded weights, written to ``save_dir`` through the
    program's own checkpoint and lineage path as a step-0 state (zero
    optimizer slots).  Fails if the program's tree and
    ``reference.params.param_spec`` differ."""
    import jax
    import jax.numpy as jnp

    from sat_tpu.train.checkpoint import save_checkpoint
    from sat_tpu.train.step import TrainState, create_train_state, split_trainable
    from sat_tpu.train.optimizer import make_optimizer

    shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))

    def fill(tree, prefix):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        leaves = []
        for path, leaf in flat:
            name = prefix + "/" + "/".join(str(p.key) for p in path)
            if name not in weights or tuple(weights[name].shape) != tuple(leaf.shape):
                raise BenchError(f"the program's leaf {name} {leaf.shape} is not in the benchmark's weights")
            leaves.append(weights[name])
        return jax.tree_util.tree_unflatten(treedef, leaves), len(leaves)

    params, n_p = fill(shapes.params, "params")
    stats, n_s = fill(shapes.batch_stats, "batch_stats") if shapes.batch_stats else ({}, 0)
    if n_p + n_s != len(weights):
        raise BenchError(f"the benchmark made {len(weights)} leaves, the program holds {n_p + n_s}")
    trainable, _ = split_trainable(params, config)
    state = TrainState(params=params, batch_stats=stats,
                       opt_state=make_optimizer(config).init(trainable),
                       step=jnp.zeros((), jnp.int32))
    path = save_checkpoint(state, config, save_dir=save_dir)
    del state, params, stats
    gc.collect()
    return path


# ---------------------------------------------------------------------------
# the traced sub-window
# ---------------------------------------------------------------------------


class TraceWindow:
    """jax.profiler over [start, start + seconds) of the measured window,
    started from a side thread of the process that holds the chip."""

    def __init__(self, directory: str, seconds: float) -> None:
        self.directory, self.seconds = directory, float(seconds)
        self.t0_ns = self.t1_ns = 0
        self.error: Optional[str] = None
        self.timing: List[int] = []

    def run(self) -> None:
        import jax

        try:
            shutil.rmtree(self.directory, ignore_errors=True)
            # device planes only: the host and python tracers write millions
            # of events a second at these step rates and make stop_trace
            # take minutes
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 0
            options.python_tracer_level = 0
            self.timing = [time.perf_counter_ns()]
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self.t0_ns = time.perf_counter_ns()
            time.sleep(self.seconds)
            self.t1_ns = time.perf_counter_ns()
            jax.profiler.stop_trace()
            self.timing += [self.t0_ns, self.t1_ns, time.perf_counter_ns()]
        except Exception as e:  # the run reports it and fails
            self.error = repr(e)

    def reduced(self) -> Optional[dict]:
        from xtrace import reduce_trace

        if self.error or not self.t1_ns:
            return None
        return reduce_trace(self.directory)


# ---------------------------------------------------------------------------
# what a per-layer reader sees
# ---------------------------------------------------------------------------


class RunData:
    """The facts of one run that the readers under ``reducers/`` may use."""

    def __init__(self, cell: Cell, window_ns: Tuple[int, int], peaks: Optional[dict]) -> None:
        self.cell, self.window_ns, self.peaks = cell, window_ns, peaks
        self.model = cell.model
        self.measured: Dict[str, float] = {}      # the driver's quantities, under its own names
        self.extras: Dict[str, Any] = {}
        self.trace: Optional[dict] = None
        self.trace_ns: Optional[Tuple[int, int]] = None
        self._spans: Optional[tuple] = None

    @property
    def e2e(self) -> Dict[str, float]:
        """End-to-end metrics by the names the mix gives them: its
        ``end_to_end`` block maps a metric of BENCHMARK.json to the
        driver's quantity that fills it (``setup_s`` is always itself)."""
        named = {"setup_s": "setup_s", **self.cell.mix.get("end_to_end", {})}
        return {metric: self.measured[q] for metric, q in named.items() if q in self.measured}

    def take_spans(self, tel) -> None:
        """Snapshot the program's host spans (its telemetry ring)."""
        if tel is not None and getattr(tel, "enabled", False):
            self._spans = tel.spans_snapshot()
            self.extras.setdefault("counters", tel.counters())

    def spans(self, name: str, within: Optional[Tuple[int, int]] = None):
        """(start_ns, duration_ns) arrays of the spans called ``name`` that
        START inside ``within`` (default: the measured window; "all": no filter)."""
        if self._spans is None:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        names, ids, t0s, durs, _tids = self._spans
        if name not in names:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        keep = ids == names.index(name)
        if within != "all":
            lo, hi = within or self.window_ns
            keep &= (t0s >= lo) & (t0s < hi)
        return t0s[keep], durs[keep]

    def host_activity_at(self, t_ns: int) -> str:
        """Name of the innermost program span open at ``t_ns`` (wall-clock
        match; exact attribution waits for TraceAnnotation in the program)."""
        if self._spans is None:
            return "no-span"
        names, ids, t0s, durs, _tids = self._spans
        open_ = np.nonzero((t0s <= t_ns) & (t0s + durs > t_ns))[0]
        if len(open_) == 0:
            return "between-spans"
        return names[int(ids[open_[np.argmin(durs[open_])]])]


def metric_file(name: str) -> str:
    """metrics/<name>.json, or for a quantity split by cell family
    (``device_idle.train``) the family's one file metrics/<stem>.json."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".json")
        if os.path.exists(path):
            return path
    raise BenchError(f"no file under benchmark/metrics/ reads {name!r}")


def read_per_layer(cell: Cell, run: RunData) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric the cell declares: its file under metrics/
    names a reader under reducers/ and the reader's arguments.  A reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        spec = load_json(metric_file(m["name"]))
        reader = importlib.import_module("reducers." + spec["reducer"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None and np.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(run: RunData) -> Optional[dict]:
    if not run.trace:
        return None
    # device timestamps count from the start of the profiling session,
    # which is the host's clock at the call of start_trace (to ~40 ms)
    gaps = []
    t0 = (run.trace_ns[0] if run.trace_ns else 0) + run.trace["first_ns"]
    for start_s, seconds in run.trace["gaps"]:
        gaps.append([run.host_activity_at(int(t0 + start_s * 1e9 + seconds * 5e8)), seconds])
    # an op's name is its whole HLO line: a while loop's runs to 4,000 characters
    ops = [[name[:200], seconds] for name, seconds in run.trace["ops"][:10]]
    return {"device_ops": ops, "idle_gaps": gaps[:10]}


def print_checks(checks: List[dict]) -> bool:
    """Each number compared beside its limit; True when all hold."""
    ok = True
    for c in checks:
        good = bool(c["value"] <= c["limit"]) if c.get("limit") is not None else bool(c["value"])
        ok &= good
        print(json.dumps({"check": c["name"], "value": c["value"], "limit": c.get("limit"),
                          "ok": good}), flush=True)
    return ok


def result_line(cell: Cell, trace: bool, correct: bool, attempted: int, failed: int,
                facts: dict, run: RunData, mem_peak: int) -> str:
    device = {"platform": facts["platform"], "kind": facts["kind"], "count": facts["count"],
              "memory_peak_bytes": int(mem_peak)}
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted),
                           "failed": int(failed)}
    if trace:
        out["metrics"] = read_per_layer(cell, run)
        if run.trace:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            bd = breakdown(run)
            if bd:
                out["breakdown"] = bd
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        out["metrics"] = {k: {"value": float(run.e2e[k]), "unit": units[k]}
                          for k in units if k in run.e2e}
        missing = [k for k in units if k not in run.e2e]
        if missing:
            raise BenchError(f"the driver did not measure {missing}")
    out["device"] = device
    return json.dumps(out)


def self_sigterm_after(fn: Callable[[], None]) -> threading.Thread:
    """Run ``fn`` on a side thread, then send this process the SIGTERM an
    operator would send (the program drains and its main() returns)."""
    import signal

    def body() -> None:
        try:
            fn()
        finally:
            os.kill(os.getpid(), signal.SIGTERM)

    t = threading.Thread(target=body, name="bench-controller", daemon=True)
    t.start()
    return t
