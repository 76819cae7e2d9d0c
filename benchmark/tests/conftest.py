"""The benchmark's own tests: CPU only, tiny widths.  Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

They are not under tests/ and are no part of the repo's tier-1 run."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(
    max_caption_length=8, dim_embedding=16, num_lstm_units=16, num_initialize_layers=2,
    dim_initialize_layer=16, num_attend_layers=2, dim_attend_layer=16, num_decode_layers=2,
    dim_decode_layer=32, vocabulary_size=64, image_size=32, compute_dtype="bfloat16",
    param_dtype="float32", beam_size=3,
)


def bench_with_serve(directory) -> str:
    """BENCHMARK.json plus the serve cell's entries (tests/data/serve_cell.json:
    the cell is proven on the chip but has no place in BENCHMARK.json while it
    fills 3% of a chip, PERF.md section 7), written to ``directory`` for
    ``run.py --bench-json``."""
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH_DIR, "tests", "data", "serve_cell.json")) as f:
        for key, entries in json.load(f).items():
            bench[key] += entries
    path = os.path.join(str(directory), "bench_with_serve.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
