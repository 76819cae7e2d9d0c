"""Fused soft-attention step as a batched Pallas TPU kernel.

At decode time the attention step is (reference attend,
/root/reference/model.py:395-436, 2-layer variant):

    temp   = t1 + t2[:, None, :]     # [B, N, da]  (t1 hoisted, loop-invariant)
    logits = temp @ w2               # [B, N]
    alpha  = softmax(logits)         # [B, N]
    ctx    = alpha @ contexts        # [B, D]

The op is bandwidth-bound: the matvec against w2 gives it an arithmetic
intensity of ~1 flop/byte, so the win is HBM traffic, not MXU time.  XLA
materializes intermediates between fusions; this kernel streams one batch
tile's t1/contexts through VMEM exactly once — add, scoring reduction,
softmax, and the weighted context sum all happen in a single residency and
only alpha [B,N] and the context vector [B,D] go back to HBM.

Layout: the grid tiles the *batch* axis (``block_b`` rows per program, 8 by
default) so one program covers a [block_b·N, da] volume rather than the
per-image slivers of the round-1 kernel.  N stays the sublane axis, da/D
the lane axis; reductions are lane-axis (scoring, context sum) or
sublane-axis (softmax) — both Mosaic-native.  The context-grid axis is
padded to a multiple of 8 with a -inf logit bias masking the pad rows out
of the softmax; the batch axis is padded to a multiple of ``block_b``.

Used at inference (beam search / greedy); training keeps the XLA path
(per-step dropout on contexts invalidates the t1 hoist there).
``interpret=True`` runs the same kernel on CPU for tests.

VMEM budget per program at flagship shapes (N=196→200, da=D=512, block_b=8,
fp32): t1 3.3 MB + contexts 3.3 MB + outputs ≈ 6.8 MB — comfortably inside
the ~16 MB/core budget (see /opt/skills/guides/pallas_guide.md).

Speed against XLA's fusion: not measured on the current machine
(scripts/bench_pallas.py is the vehicle; PERF.md keeps what an earlier
machine showed).  The kernel's softmax and weighted sum run in full fp32
on the VPU, whereas the XLA path's fp32 einsum lowers to
default-precision bf16 MXU passes; on the chip the two agree to ~2e-3 in
the context vector at flagship shapes (chip_smoke.py prints the figure).
block_b=4 fails Mosaic's sublane-divisibility rule.  Enabled by default
via config.use_pallas_attention.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30

# Test hook: route attend_with_precomputed through the kernel in interpret
# mode even off-TPU (production non-TPU uses the XLA fallback instead).
FORCE_INTERPRET = False

# Batch rows per program.  8 keeps the VMEM residency ~7 MB at flagship
# shapes while giving Mosaic full-width vector work on every axis.
DEFAULT_BLOCK_B = 8

_PAD_SCOPE = "decoder/attend/pad"


def _make_kernel(compute_dtype):
    dt = jnp.dtype(compute_dtype)

    def _kernel(t1_ref, t2_ref, w2_ref, bias_ref, ctx_ref,
                out_ctx_ref, out_alpha_ref):
        # blocks: t1 [Bt,Np,da], t2 [Bt,1,da], w2 [1,da], bias [1,Np],
        #         ctx [Bt,Np,D], out_ctx [Bt,D], out_alpha [Bt,Np]
        temp = t1_ref[...] + t2_ref[...]                           # [Bt,Np,da]
        # scoring: temp·w2 contracted over the lane axis.  A [.,da]@[da,1]
        # matvec cannot fill the MXU; an elementwise-mul + lane reduction
        # is the same flops on the VPU without the degenerate-matmul
        # layout.  Mirror _dense's dtype story: bf16 multiply, fp32
        # accumulate, round through dt like XLA's bf16 matmul output.
        prod = temp.astype(dt).astype(jnp.float32) * w2_ref[0].astype(
            dt
        ).astype(jnp.float32)
        logits = jnp.sum(prod, axis=-1).astype(dt).astype(jnp.float32)
        logits = logits + bias_ref[...]                            # [Bt,Np]
        m = jnp.max(logits, axis=1, keepdims=True)                 # [Bt,1]
        e = jnp.exp(logits - m)
        alpha = e / jnp.sum(e, axis=1, keepdims=True)              # [Bt,Np]
        out_alpha_ref[...] = alpha
        # weighted context sum: lane-preserving sublane reduction
        out_ctx_ref[...] = jnp.sum(
            alpha[:, :, None] * ctx_ref[...], axis=1
        )                                                          # [Bt,D]

    return _kernel


def _make_masked_kernel(compute_dtype):
    """Row-masked variant for slot-pool geometry (stepped decode).

    Dead pool rows carry whatever the retired slot last held — possibly
    non-finite after many steps of garbage arithmetic — so the mask must
    neutralize them INSIDE the kernel: scores are zeroed before the
    softmax (no exp of garbage) and alpha/context are zeroed after, so a
    dead row can never emit or propagate a NaN.  Live rows take the
    ``where`` true-branch everywhere and stay bitwise identical to the
    unmasked kernel.
    """
    dt = jnp.dtype(compute_dtype)

    def _kernel(t1_ref, t2_ref, w2_ref, bias_ref, ctx_ref, mask_ref,
                out_ctx_ref, out_alpha_ref):
        # blocks: as the unmasked kernel, plus mask [Bt,1] fp32 (>0 ⇒ live)
        valid = mask_ref[...] > 0.0                                # [Bt,1]
        temp = t1_ref[...] + t2_ref[...]                           # [Bt,Np,da]
        prod = temp.astype(dt).astype(jnp.float32) * w2_ref[0].astype(
            dt
        ).astype(jnp.float32)
        logits = jnp.sum(prod, axis=-1).astype(dt).astype(jnp.float32)
        logits = jnp.where(valid, logits, 0.0) + bias_ref[...]     # [Bt,Np]
        m = jnp.max(logits, axis=1, keepdims=True)                 # [Bt,1]
        e = jnp.exp(logits - m)
        alpha = e / jnp.sum(e, axis=1, keepdims=True)              # [Bt,Np]
        alpha = jnp.where(valid, alpha, 0.0)
        out_alpha_ref[...] = alpha
        ctxsum = jnp.sum(alpha[:, :, None] * ctx_ref[...], axis=1)  # [Bt,D]
        out_ctx_ref[...] = jnp.where(valid, ctxsum, 0.0)

    return _kernel


@partial(
    jax.jit, static_argnames=("compute_dtype", "interpret", "block_b")
)
def fused_attend(
    t1: jnp.ndarray,
    t2: jnp.ndarray,
    w2: jnp.ndarray,
    contexts: jnp.ndarray,
    row_mask: "jnp.ndarray | None" = None,
    compute_dtype: str = "float32",
    interpret: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(context [B,D], alpha [B,N]) from hoisted attention inputs.

    t1: [B, N, da] fp32 — tanh(fc_1a(contexts)), loop-invariant.
    t2: [B, da]    fp32 — tanh(fc_1b(output)) for the current step.
    w2: [da, 1]    fp32 — second-layer projection.
    contexts: [B, N, D] fp32.
    row_mask: optional [B] bool — slot-pool geometry (stepped decode):
        False rows are dead slots whose inputs may be stale garbage; the
        masked kernel zeroes their scores/alpha/context so nothing
        non-finite propagates, while True rows stay bitwise identical to
        the unmasked call.  ``None`` keeps the original kernel program
        (the monolithic serve path) byte-for-byte.
    compute_dtype: the scoring multiply dtype (the model's MXU dtype).
    """
    B, N, da = t1.shape
    D = contexts.shape[-1]
    n_pad = (-N) % 8
    Np = N + n_pad
    bt = max(1, min(block_b, B))
    b_pad = (-B) % bt
    Bp = B + b_pad

    # the data movement round the kernel carries a scope of its own, so
    # that a device trace tells it from the kernel (docs/OBSERVABILITY.md)
    with jax.named_scope(_PAD_SCOPE):
        t1 = jnp.pad(t1.astype(jnp.float32), ((0, b_pad), (0, n_pad), (0, 0)))
        contexts_p = jnp.pad(
            contexts.astype(jnp.float32), ((0, b_pad), (0, n_pad), (0, 0))
        )
        t2 = jnp.pad(t2.astype(jnp.float32), ((0, b_pad), (0, 0))).reshape(
            Bp, 1, da
        )
        w2_row = w2.astype(jnp.float32).reshape(1, da)
        # padding grid rows get -inf logits so they vanish from the softmax
        bias = jnp.where(
            (jnp.arange(Np) < N)[None, :], 0.0, _NEG_INF
        ).astype(jnp.float32)                                      # [1, Np]

    if row_mask is not None:
        # batch-pad rows are dead by construction (pad with 0 = masked)
        with jax.named_scope(_PAD_SCOPE):
            mask_col = jnp.pad(
                row_mask.astype(jnp.float32), ((0, b_pad),)
            ).reshape(Bp, 1)
        out_ctx, out_alpha = pl.pallas_call(
            _make_masked_kernel(compute_dtype),
            name="fused_attend_masked",
            grid=(Bp // bt,),
            in_specs=[
                pl.BlockSpec((bt, Np, da), lambda b: (b, 0, 0)),
                pl.BlockSpec((bt, 1, da), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, da), lambda b: (0, 0)),
                pl.BlockSpec((1, Np), lambda b: (0, 0)),
                pl.BlockSpec((bt, Np, D), lambda b: (b, 0, 0)),
                pl.BlockSpec((bt, 1), lambda b: (b, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bt, D), lambda b: (b, 0)),
                pl.BlockSpec((bt, Np), lambda b: (b, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((Bp, D), jnp.float32),
                jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
            ],
            interpret=interpret,
        )(t1, t2, w2_row, bias, contexts_p, mask_col)
        with jax.named_scope(_PAD_SCOPE):
            return out_ctx[:B], out_alpha[:B, :N]

    out_ctx, out_alpha = pl.pallas_call(
        _make_kernel(compute_dtype),
        name="fused_attend",
        grid=(Bp // bt,),
        in_specs=[
            pl.BlockSpec((bt, Np, da), lambda b: (b, 0, 0)),
            pl.BlockSpec((bt, 1, da), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, da), lambda b: (0, 0)),
            pl.BlockSpec((1, Np), lambda b: (0, 0)),
            pl.BlockSpec((bt, Np, D), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, D), lambda b: (b, 0)),
            pl.BlockSpec((bt, Np), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, D), jnp.float32),
            jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        ],
        interpret=interpret,
    )(t1, t2, w2_row, bias, contexts_p)
    with jax.named_scope(_PAD_SCOPE):
        return out_ctx[:B], out_alpha[:B, :N]


def fused_attend_reference(
    t1: jnp.ndarray,
    t2: jnp.ndarray,
    w2: jnp.ndarray,
    contexts: jnp.ndarray,
    row_mask: "jnp.ndarray | None" = None,
    compute_dtype: str = "float32",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Plain-XLA twin of :func:`fused_attend` (correctness oracle)."""
    dt = jnp.dtype(compute_dtype)
    temp = t1.astype(jnp.float32) + t2.astype(jnp.float32)[:, None, :]
    logits = (
        temp.astype(dt) @ w2.astype(dt)
    ).astype(jnp.float32)[..., 0]
    if row_mask is not None:
        valid = row_mask.reshape(-1, 1)
        logits = jnp.where(valid, logits, 0.0)
    alpha = jax.nn.softmax(logits, axis=-1)
    if row_mask is not None:
        alpha = jnp.where(valid, alpha, 0.0)
    ctx = jnp.einsum("bn,bnd->bd", alpha, contexts.astype(jnp.float32))
    if row_mask is not None:
        ctx = jnp.where(valid, ctx, 0.0)
    return ctx, alpha
