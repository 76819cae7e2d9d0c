"""Time candidate tiles of the lfm2 experts' grouped products on the chip,
INSIDE their consumers: the sweep that filled ``lm_common._GMM_TILES``'s
rows for 2,048 x 1,792 and 1,792 x 2,048 (PR 44; PERF.md section 6).

    chiprun -- python scripts/gmm_tile_sweep.py [--stack lfm2|qwen3_next] [--only step|prefill]
        [--skew 0.04] [--same-word] [--w13 64,2048,1792 ...] [--w2 ...]
    JAX_PLATFORMS=cpu python scripts/gmm_tile_sweep.py --rehearse   # toy widths: the control flow only

The consumers, at the published widths with random weights: ``lfm2.step``
whole (9 layers, 768 rows: 3,072 pairs over 32 experts through the real
router; ``--skew`` draws each ``expert_bias`` with that deviation to crowd
some experts, ``--same-word`` gives every row one word as at step 0) and
``lfm2.sequence_forward`` over the first three layers at [256, 196, 2048]
(200,704 pairs an expert layer).  A program is ONE jit of a consumer under
one tile for w1 / w3 and one for w2 (separate kernels: a program times one
candidate of each); the kernel alone is compiled first and a tile its 16 MB
refuse is said so.  ms a call come from ONE device trace over all programs
(the ``gmm`` ops inside each program's module interval, told apart by their
output's shape), each program run twice, in opposite orders; the wall time
a run is printed beside them.  The result goes to ``--out`` (under
``chiprun_out/``).

``--stack qwen3_next`` (PR 47: the rows for 2,048 x 512 and 512 x 2,048,
256 experts held of 512): its consumers are the four layers' expert halves
of ``qwen3_next.step`` over 384 rows (norm, the softmax router, the
dispatch, the three grouped products, the combine, the gated shared expert,
the residual add; the DeltaNet state, 2.4 GB a program, is left out: forty
programs would not compile inside a call's time with it) and three layers'
over one prefill pass of 32 x 196 positions.  Another stack's widths: give
its two keys of ``_GMM_TILES``, its candidates and its consumers in
``STACKS`` below; the rest reads shapes.
"""

import argparse
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import xtrace  # noqa: E402
from sat_tpu.config import Config  # noqa: E402
from sat_tpu.models import lfm2, lm_common, qwen3_next  # noqa: E402

W13, W2 = (2048, 1792), (1792, 2048)
CANDIDATES = {   # regime -> (w1 / w3 tiles, w2 tiles)
    "step": (
        [(tm, 2048, tn) for tm in (32, 64, 128, 256) for tn in (1792, 896, 1024, 512, 256)]
        + [(80, 2048, 1792), (96, 2048, 1792), (112, 2048, 1792)]
        + [(128, 1024, 896), (128, 1024, 1792), (64, 1024, 1792), (512, 2048, 896)],
        [(tm, tk, tn) for tm in (32, 64, 128, 256) for tk in (1792, 896) for tn in (2048, 1024, 512)]
        + [(80, 1792, 2048), (96, 1792, 2048), (128, 2048, 1024), (512, 1792, 1024)],
    ),
    "prefill": (
        [(256, 2048, 1024), (256, 1024, 1024), (512, 1024, 1024), (512, 2048, 512), (256, 2048, 896),
         (256, 2048, 1792), (512, 2048, 896), (512, 1024, 896), (512, 1024, 1792), (256, 1024, 1792),
         (512, 512, 1792), (1024, 1024, 896), (1024, 512, 896), (512, 2048, 256)],
        [(256, 2048, 1024), (256, 1024, 1024), (512, 1024, 1024), (512, 2048, 512), (512, 1792, 1024),
         (256, 1792, 1024), (512, 1792, 512), (512, 896, 1024), (256, 1792, 2048), (512, 896, 2048),
         (256, 896, 2048), (1024, 896, 1024), (1024, 896, 512), (1024, 1792, 512)],
    ),
}
TOY = dict(
    decoder="lfm2_moe", image_size=32, hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
    num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2, num_experts=8,
    num_experts_per_tok=2, layer_types=("conv", "full_attention", "conv", "conv", "full_attention"),
    vocabulary_size=96,
)


def published(layers: int = 9) -> Config:
    with open(os.path.join(ROOT, "benchmark/configs/sat-lfm2-8b-a1b.json")) as f:
        model = json.load(f)["model"]
    model = {k: tuple(v) if isinstance(v, list) else v for k, v in model.items()}
    return Config(**{**model, "layer_types": model["layer_types"][:layers], "num_hidden_layers": layers})


def kernel_takes(pairs: int, kn, tiles, experts: int = 32) -> bool:
    """Whether the kernel alone compiles under ``tiles`` (its 16 MB)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    k, n = kn
    sd = jax.ShapeDtypeStruct
    try:
        jax.jit(lambda r, w, s: gmm(r, w, s, preferred_element_type=jnp.bfloat16, tiling=tiles)).lower(
            sd((pairs + -pairs % tiles[0], k), jnp.bfloat16), sd((experts, k, n), jnp.bfloat16),
            sd((experts,), jnp.int32)
        ).compile()
        return True
    except Exception as e:  # noqa: BLE001  the compiler's refusal, whatever its class
        print(f"  {kn} {tiles}: refused ({str(e)[:90]!r})", flush=True)
        return False


def named_jit(fn, name: str):
    def program(*operands):
        return fn(*operands)

    program.__name__ = name
    return jax.jit(program)


def set_tiles(prefill: bool, w13, w2) -> None:
    for key, tiles in ((W13, w13), (W2, w2)):
        entry = list(lm_common._GMM_TILES[key])
        entry[prefill] = tuple(tiles)
        lm_common._GMM_TILES[key] = tuple(entry)


def gmm_ns_by_module(planes) -> dict:
    """{module: {"runs": [ns a run], "calls": {the output's width: [ns a ``gmm`` call]}}} of the first TPU plane
    (``planes``: ``jax.profiler.ProfileData``'s, or stand-ins with .name and .lines[].events[])."""
    planes = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not planes:
        return {}
    calls = []                           # (start, ns, the output's width) of every ``gmm`` op
    for op, start, dur in xtrace.plane_events(planes[0], "XLA Ops"):
        shape = re.search(r"bf16\[\d+,(\d+)\]", op)
        if shape and "gmm" in op.split("=")[0]:
            calls.append((start, dur, int(shape.group(1))))
    out = {}
    for name, start, dur in xtrace.plane_events(planes[0], "XLA Modules"):
        bucket = out.setdefault(re.sub(r"\(\d+\)$", "", name), {"runs": [], "calls": {}})
        bucket["runs"].append(dur)
        for s, d, width in calls:
            if start <= s < start + dur:
                bucket["calls"].setdefault(width, []).append(d)
    return out


def consumers(rehearse: bool, skew: float, same_word: bool):
    """{regime: (function, operands, timed runs)} and {regime: routed pairs}."""
    config, c3 = (Config(**TOY), Config(**{**TOY, "num_hidden_layers": 3, "layer_types": TOY["layer_types"][:3]})) \
        if rehearse else (published(), published(3))
    B, N, T = (4 if rehearse else 256), config.num_ctx, config.max_caption_length
    R, H = B * 3, config.hidden_size
    rng = np.random.default_rng(44)
    normal = lambda scale, *shape: jnp.asarray(scale * rng.standard_normal(shape, np.float32), jnp.bfloat16)  # noqa: E731

    params = jax.jit(lambda: lfm2.init_params(jax.random.PRNGKey(44), config))()
    if skew:
        for p in params["lm"]["layers"].values():
            if "expert_bias" in p["feed_forward"]:
                p["feed_forward"]["expert_bias"] = jnp.asarray(
                    skew * rng.standard_normal((config.num_experts,)), jnp.float32)
    kinds = config.layer_types
    width = config.num_key_value_heads * (H // config.num_attention_heads)
    n_attn = sum(kind == "full_attention" for kind in kinds)
    prefix = lfm2.BeamCache(
        conv=(), keys=tuple(normal(0.5, B, N, width) for _ in range(n_attn)),
        values=tuple(normal(0.5, B, N, width) for _ in range(n_attn)))
    cache = lfm2.init_cache(
        config, tuple(normal(0.1, R, config.conv_L_cache, H) for _ in range(len(kinds) - n_attn)), R, T)
    counters = lm_common.init_counters(
        jnp.zeros((config.num_hidden_layers - config.num_dense_layers, config.num_experts), jnp.int32), T
    )._replace(t=jnp.int32(7))
    words = jnp.zeros((R,), jnp.int32) if same_word else jnp.asarray(
        rng.integers(0, config.vocabulary_size, (R,)), jnp.int32)
    x = normal(1.0, B, N, H)

    def step(params, prefix, cache, counters, words):
        return lfm2.step(params, config, prefix, cache, counters, words)

    def prefill(lm, x):
        hidden, _, counts, _ = lfm2.sequence_forward(lm, c3, x)
        return hidden, counts

    programs = {"step": (step, (params, prefix, cache, counters, words), 8),
                "prefill": (prefill, (params["lm"], x), 3)}
    pairs = {"step": R * config.num_experts_per_tok, "prefill": B * N * config.num_experts_per_tok}
    return programs, pairs


QWEN3_NEXT_CANDIDATES = {
    "step": (
        [(tm, 2048, 512) for tm in (16, 32, 64, 128, 256)] + [(32, 2048, 256), (128, 2048, 256), (32, 1024, 512),
                                                                 (128, 1024, 512)],
        [(tm, 512, 2048) for tm in (16, 32, 64, 128, 256)] + [(32, 512, 1024), (128, 512, 1024), (128, 512, 512),
                                                                 (32, 256, 2048)],
    ),
    "prefill": (
        [(64, 2048, 512), (128, 2048, 512), (256, 2048, 512), (512, 2048, 512), (128, 1024, 512), (256, 1024, 512),
         (256, 2048, 256)],
        [(64, 512, 2048), (128, 512, 2048), (256, 512, 2048), (512, 512, 2048), (128, 512, 1024), (256, 512, 1024),
         (512, 512, 1024)],
    ),
}
QWEN3_NEXT_TOY = dict(
    decoder="qwen3_next", image_size=32, hidden_size=64, moe_intermediate_size=24, num_hidden_layers=4,
    num_dense_layers=0, num_attention_heads=4, num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.5,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=3, experts_held=8, n_shared_experts=1,
    shared_expert_intermediate_size=20, shared_expert_gate=True, scoring_func="softmax", use_expert_bias=False,
    tie_word_embeddings=False, layer_types=("linear_attention",) * 3 + ("full_attention",), vocabulary_size=96,
)


def consumers_qwen3_next(rehearse: bool, skew: float, same_word: bool):
    """``consumers`` for the qwen3_next stack: the layers' expert halves
    (``qwen3_next._experts``) over a step's rows and over one prefill pass."""
    if rehearse:
        config = Config(**QWEN3_NEXT_TOY)
    else:
        with open(os.path.join(ROOT, "benchmark/configs/sat-qwen3-next-80b-a3b.json")) as f:
            model = json.load(f)["model"]
        config = Config(**{k: tuple(v) if isinstance(v, list) else v for k, v in model.items()})
    B = 4 if rehearse else 128
    R, H, N = B * 3, config.hidden_size, config.num_ctx
    block = min(B, qwen3_next.SEQUENCE_BLOCK)
    rng = np.random.default_rng(47)
    normal = lambda scale, *shape: jnp.asarray(scale * rng.standard_normal(shape, np.float32), jnp.bfloat16)  # noqa: E731
    layers = jax.jit(lambda: qwen3_next.init_params(jax.random.PRNGKey(47), config))()["lm"]["layers"]
    layers = {name: {k: v for k, v in p.items() if k in ("post_attention_layernorm", "feed_forward")}
              for name, p in layers.items()}

    def halves(n_layers):
        def run(layers, x):
            counts = []
            for i in range(n_layers):
                x, sizes, _, _ = qwen3_next._experts(layers[lm_common.layer_name(i)], config, x)
                counts.append(sizes)
            return x, lm_common.StepCounters(jnp.int32(0), jnp.stack(counts), jnp.zeros((n_layers, 1), jnp.int32))
        return run

    rows = jnp.tile(normal(1.0, 1, H), (R, 1)) if same_word else normal(1.0, R, H)
    programs = {"step": (halves(config.num_hidden_layers), (layers, rows), 8),
                "prefill": (halves(3), (layers, normal(1.0, block * N, H)), 3)}
    pairs = {"step": lm_common.held_pair_rows(config, R), "prefill": lm_common.held_pair_rows(config, block * N)}
    return programs, pairs


def main(argv=None) -> int:
    global W13, W2, CANDIDATES
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stack", choices=("lfm2", "qwen3_next"), default="lfm2")
    ap.add_argument("--only", choices=sorted(CANDIDATES))
    ap.add_argument("--w13", nargs="*", help="tiles as m,k,n in place of the regime's list (with --only)")
    ap.add_argument("--w2", nargs="*")
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--same-word", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "gmm_tile_sweep.json"))
    args = ap.parse_args(argv)
    if (args.w13 or args.w2) and not args.only:
        ap.error("--w13 / --w2 name one regime's tiles: give --only")
    if not args.rehearse and jax.default_backend() != "tpu":
        raise SystemExit("a time comes from the chip: run under chiprun, or --rehearse")
    experts = 32
    if args.stack == "qwen3_next":
        W13, W2, CANDIDATES, experts = (2048, 512), (512, 2048), QWEN3_NEXT_CANDIDATES, 256
        if args.rehearse:
            W13, W2, experts = (64, 24), (24, 64), 8
        for key in (W13, W2):       # a width without a row starts from what ``_gmm_tiling`` falls back to
            lm_common._GMM_TILES.setdefault(key, (lm_common._gmm_tiling(0, *key), lm_common._gmm_tiling(8192, *key)))
        programs, pairs = consumers_qwen3_next(args.rehearse, args.skew, args.same_word)
    else:
        programs, pairs = consumers(args.rehearse, args.skew, args.same_word)
    table = {key: lm_common._GMM_TILES[key] for key in (W13, W2)}

    step, operands, _ = programs["step"]
    sizes = np.asarray(jax.jit(step)(*operands)[1].moe_counts)
    print("a step's rows an expert, by layer (sorted) | fullest / mean:", flush=True)
    for row in sizes:
        print(" ", sorted(row.tolist()), "|", round(float(row.max() / row.mean()), 3), flush=True)

    plans = []
    for regime in ([args.only] if args.only else sorted(CANDIDATES)):
        standing = (table[W13][regime == "prefill"], table[W2][regime == "prefill"])
        lists = CANDIDATES[regime]
        if args.w13 or args.w2:
            lists = [[tuple(map(int, t.split(","))) for t in given or []] for given in (args.w13, args.w2)]
        if args.rehearse:                # one candidate of each beside the standing pair
            lists = [tiles[:1] for tiles in lists]
        w13s, w2s = ([t for t in dict.fromkeys(tiles)
                      if args.rehearse or kernel_takes(pairs[regime], kn, t, experts)] or [own]
                     for tiles, kn, own in zip(lists, (W13, W2), standing))
        plans += [(regime,) + standing] + [
            (regime, w13s[i % len(w13s)], w2s[i % len(w2s)]) for i in range(max(len(w13s), len(w2s)))]

    results, compiled = [], []
    for i, (regime, w13, w2) in enumerate(dict.fromkeys(plans)):
        name = f"{regime}_{i:02d}_" + "x".join(map(str, w13)) + "__" + "x".join(map(str, w2))
        fn, operands, reps = programs[regime]
        set_tiles(regime == "prefill", w13, w2)
        t0 = time.perf_counter()
        run = named_jit(fn, name).lower(*operands).compile()      # the module's name in the trace
        jax.block_until_ready(run(*operands))
        t1 = time.perf_counter()
        for _ in range(reps):
            out = run(*operands)
        jax.block_until_ready(out)
        wall_ms = (time.perf_counter() - t1) / reps * 1e3
        del out
        print(f"{name}: compiled in {t1 - t0:.1f} s, {wall_ms:.3f} ms a run", flush=True)
        compiled.append((name, run, operands, max(reps // 2, 2)))
        results.append(dict(name=name, regime=regime, w13=w13, w2=w2, wall_ms=wall_ms))
    for key, entry in table.items():
        lm_common._GMM_TILES[key] = entry

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = options.python_tracer_level = 0    # device planes only
    with tempfile.TemporaryDirectory(prefix="gmm_tile_sweep_") as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for order in (compiled, compiled[::-1]):
            for _, run, operands, reps in order:
                for _ in range(reps):
                    out = run(*operands)
                jax.block_until_ready(out)
                del out
        jax.profiler.stop_trace()
        path = xtrace.find_xplane(trace_dir)
        by_module = gmm_ns_by_module(jax.profiler.ProfileData.from_file(path).planes) if path else {}
    print("\nregime | w1 / w3 tiles: ms a call | w2 tiles: ms a call | the program's ms on the device, by the host")
    for r in results:
        got = by_module.get("jit_" + r["name"])
        if got:
            r["module_ms"] = float(np.median(got["runs"])) / 1e6
            r["ms_a_call"] = {name: round(float(np.mean(got["calls"][kn[1]])) / 1e6, 4)
                              for name, kn in (("w13", W13), ("w2", W2)) if kn[1] in got["calls"]}
        ms = r.get("ms_a_call", {})
        print(r["regime"], "|", r["w13"], ms.get("w13"), "|", r["w2"], ms.get("w2"), "|",
              round(r.get("module_ms", float("nan")), 3), round(r["wall_ms"], 3), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(dict(device=jax.devices()[0].device_kind, step_sizes=sizes.tolist(), results=results), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
