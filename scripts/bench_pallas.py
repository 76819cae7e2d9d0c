"""Measure the fused Pallas attention kernel against XLA on the real chip.

Decides the fate of ``use_pallas_attention`` (VERDICT r1 item 6): flagship
decode shapes, both implementations timed over identical inputs.  Round 5
extends the single-B=48 measurement to a batch sweep (VERDICT r4
next-round #8): default B ∈ {32, 48, 64, 128}, one correctness check and
one speedup per size, and the ENABLE verdict requires the kernel to hold
>= 1.0x at EVERY size — a knob that wins at one operating point and
loses at another must not be default-on.  Measurements run on TPU
(no platform override); ``--cpu`` exists only as a plumbing smoke.

Usage: python scripts/bench_pallas.py [--batch 48] [--iters 200] [--cpu]
  (--batch 0 = the default sweep; --cpu pins the host backend and runs
  the kernel in Pallas interpret mode — a smoke of the sweep/correctness
  plumbing, NOT a performance measurement)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timeit(fn, args, iters: int) -> float:
    """On-device loop timing: ONE dispatched program runs ``iters``
    serially-dependent kernel invocations under lax.fori_loop (each
    iteration's query vector depends on the previous output context), and
    one device_get closes the window.  Per-call host timing is not used:
    dispatch latency swamps µs-scale kernels."""
    import jax

    t1, t2, w2, ctx = args

    @jax.jit
    def loop(t2c, t1, w2, ctx):
        def body(_, c):
            out_ctx, _alpha = fn(t1, c, w2, ctx)
            return c + out_ctx * 1e-6  # serializing dep, ~no perturbation
        return jax.lax.fori_loop(0, iters, body, t2c)

    jax.device_get(loop(t2, t1, w2, ctx)[0, 0])  # compile + warm
    t0 = time.perf_counter()
    out = loop(t2, t1, w2, ctx)
    jax.device_get(out[0, 0])
    return (time.perf_counter() - t0) / iters


def bench_one(B: int, iters: int, block_arg: int, interpret: bool = False):
    """Time XLA vs the kernel at one batch size; returns a result row or
    None when the kernel fails to lower at every tiling."""
    import jax
    import jax.numpy as jnp

    from sat_tpu.ops.pallas_attention import fused_attend, fused_attend_reference

    # flagship decode shapes: VGG16 grid N=196, da=D=512
    N, da, D = 196, 512, 512
    rng = np.random.default_rng(0)
    t1 = jnp.asarray(rng.normal(size=(B, N, da)).astype(np.float32))
    t2 = jnp.asarray(rng.normal(size=(B, da)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(da, 1)).astype(np.float32))
    ctx = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))

    xla = jax.jit(fused_attend_reference, static_argnames=("compute_dtype",))
    t_xla = timeit(xla, (t1, t2, w2, ctx), iters)
    traffic_mb = (t1.nbytes + ctx.nbytes) / 1e6
    print(
        f"[B={B:3d}] XLA fused:    {t_xla*1e6:8.1f} us   "
        f"(~{traffic_mb / t_xla / 1e3:.0f} GB/s effective)", flush=True,
    )

    # no divisibility guard: fused_attend pads the batch axis up to a
    # multiple of block_b, so every tiling is valid at every B
    blocks = [block_arg] if block_arg else [4, 8, 16]
    best = (None, float("inf"))
    for bb in blocks:
        try:
            t_pal = timeit(
                lambda *a: fused_attend(*a, block_b=bb, interpret=interpret),
                (t1, t2, w2, ctx), iters,
            )
        except Exception as e:  # mosaic lowering failure at this tiling
            print(f"[B={B:3d}] pallas bb={bb}: FAILED ({type(e).__name__}: {e})",
                  flush=True)
            continue
        print(
            f"[B={B:3d}] pallas bb={bb:2d}: {t_pal*1e6:8.1f} us   "
            f"(~{traffic_mb / t_pal / 1e3:.0f} GB/s effective)", flush=True,
        )
        if t_pal < best[1]:
            best = (bb, t_pal)

    if best[0] is None:
        return None

    # correctness BEFORE the verdict: a fast-but-wrong kernel must never
    # emit the ENABLE line.  Both impls are compared against a
    # highest-precision ground truth rather than against each other: on
    # TPU the XLA twin's fp32 einsum runs at default matmul precision
    # (bf16 MXU passes), while the kernel's weighted-sum reduction is full
    # fp32 on the VPU — the kernel is *more* accurate, so an
    # impl-vs-impl allclose at tight tolerance fails for the wrong reason.
    with jax.default_matmul_precision("highest"):
        truth = jax.jit(
            lambda *a: fused_attend_reference(*a, compute_dtype="float32")
        )(t1, t2, w2, ctx)
    want = fused_attend_reference(t1, t2, w2, ctx)
    got = fused_attend(t1, t2, w2, ctx, block_b=best[0], interpret=interpret)

    def max_err(a, b):
        return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))

    err_alpha = (max_err(got[1], truth[1]), max_err(want[1], truth[1]))
    err_ctx = (max_err(got[0], truth[0]), max_err(want[0], truth[0]))
    print(f"[B={B:3d}] max |err| vs fp32 ground truth — alpha: pallas "
          f"{err_alpha[0]:.2e} xla {err_alpha[1]:.2e}; context: pallas "
          f"{err_ctx[0]:.2e} xla {err_ctx[1]:.2e}", flush=True)
    assert err_alpha[0] <= max(err_alpha[1] * 1.5, 1e-5), (B, err_alpha)
    assert err_ctx[0] <= max(err_ctx[1] * 1.5, 1e-4), (B, err_ctx)

    speedup = t_xla / best[1]
    print(f"[B={B:3d}] best pallas: block_b={best[0]}  "
          f"speedup vs XLA: {speedup:.2f}x  correctness OK", flush=True)
    return {
        "batch": B,
        "xla_us": round(t_xla * 1e6, 1),
        "pallas_us": round(best[1] * 1e6, 1),
        "block_b": best[0],
        "speedup": round(speedup, 3),
        "err_ctx_pallas": err_ctx[0],
        "err_ctx_xla": err_ctx[1],
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=0,
                    help="B (images × beams); 0 = sweep 32,48,64,128")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--block-b", type=int, default=0, help="0 = sweep tilings")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (interpret-mode smoke runs)")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})", flush=True)

    batches = [args.batch] if args.batch else [32, 48, 64, 128]
    rows = []
    for B in batches:
        row = bench_one(B, args.iters, args.block_b, interpret=args.cpu)
        if row is None:
            print(f"verdict: pallas kernel failed at B={B} — keep XLA path")
            return 1
        rows.append(row)

    min_speedup = min(r["speedup"] for r in rows)
    from sat_tpu.telemetry import bench_stamp

    print(
        json.dumps(
            {"sweep": rows, "min_speedup": min_speedup, **bench_stamp()}
        ),
        flush=True,
    )
    if args.cpu:
        # interpret-mode timings are meaningless; the smoke's value is
        # that the sweep + correctness plumbing ran — no verdict off-TPU
        print("smoke complete (interpret mode): no enable/keep verdict")
        return 0
    # default-on requires holding the win at EVERY measured operating
    # point (VERDICT r4 next-round #8); 1.0 exactly is a wash, keep it —
    # the 1.02 margin keeps run-to-run timing noise from flipping the
    # default on a result indistinguishable from a wash (ADVICE r5 #1)
    print(
        "verdict: ENABLE use_pallas_attention"
        if min_speedup >= 1.02
        else "verdict: keep XLA path (wash or loses at some batch size)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
