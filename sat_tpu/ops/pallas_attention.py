"""Fused soft-attention step as a batched Pallas TPU kernel.

At decode time the attention step is (reference attend,
/root/reference/model.py:395-436, 2-layer variant):

    temp   = t1[:, None] + t2[:, :, None]  # [B, K, N, da]  (t1 [B,N,da] hoisted,
                                           #  per image; t2 [B,K,da] per beam row)
    logits = temp @ w2                     # [B, K, N]
    alpha  = softmax(logits)               # [B, K, N]
    ctx    = alpha @ contexts              # [B, K, D]      (contexts [B,N,D])

The op is bandwidth-bound: the matvec against w2 gives it an arithmetic
intensity of ~1 flop/byte, so the win is HBM traffic, not MXU time.  XLA
materializes intermediates between fusions; this kernel streams one block
of images' t1/contexts through VMEM exactly once — add, scoring reduction,
softmax, and the weighted context sum all happen in a single residency and
only alpha [B*K,N] and the context vector [B*K,D] go back to HBM.

Layout: the grid runs over IMAGES (``block_b`` per program, 8 by default).
A program loads its images' ``t1 [Bi,N,da]`` and ``contexts [Bi,N,D]`` once
and loops the K beams of those images over that one residency (K static,
unrolled): every beam of an image attends over the same grid and the same
projection, and only ``t2 [B*K, da]`` differs per beam, so no copy per beam
exists anywhere, in HBM or in VMEM.  K is read from the shapes; K = 1
(greedy, the slot pool, any caller whose rows equal its grids) is the
kernel as it was before the grid ran over images.  N stays the sublane
axis, da/D the lane axis; reductions are lane-axis (scoring, context sum)
or sublane-axis (softmax), both Mosaic-native.  The context-grid axis is
padded to a multiple of 8 with a -inf logit bias masking the pad rows out
of the softmax; the image axis is padded to a multiple of ``block_b``.
(Mosaic also takes the 196-row block as it is, a block equal to the full
dimension, with no pad and no bias, and the chip runs it faster: PERF.md
sections 6 and 7 say why the pad is still here.)

Used at inference (beam search / greedy); training keeps the XLA path
(per-step dropout on contexts invalidates the t1 hoist there).
``interpret=True`` runs the same kernel on CPU for tests.

VMEM budget per program at flagship shapes (N=196→200, da=D=512, block_b=8,
fp32): t1 3.3 MB + contexts 3.3 MB, double buffered = 13.1 MB, plus
t2 [8,K,512], the outputs [8,K,512] and [8,K,200] and the beam loop's
temporaries: under the compiler's 16 MB default for the unmasked body,
17.7 MB for the masked body at K = 3, hence ``_VMEM_LIMIT`` (the core has
128 MiB; /opt/skills/guides/pallas_guide.md).  The second encoder family
(N=49→56, D=2048): 3.7 MB of grid a block.

Speed on the chip, beam 3 over 512 images (v5e; PERF.md section 6): one
call reads 0.42 GB (a third of what K copies of the grid took) in 855 us
where the kernel over 1536 tiled rows took 1,665 us; the wrapper's pad of
the two per-image arrays costs another 1.27 ms a call.  The kernel's
softmax and weighted sum run in full fp32 on the VPU, whereas the XLA
path's fp32 einsum lowers to default-precision bf16 MXU passes; on the
chip the two agree to ~2e-3 in the context vector at flagship shapes
(chip_smoke.py prints the figure).  block_b=4 fails Mosaic's
sublane-divisibility rule at K = 1.  Enabled by default via
config.use_pallas_attention.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Test hook: route attend_with_precomputed through the kernel in interpret
# mode even off-TPU (production non-TPU uses the XLA fallback instead).
FORCE_INTERPRET = False

# Images per program.  8 keeps the VMEM residency ~7 MB (13 MB double
# buffered) at flagship shapes while giving Mosaic full-width vector work
# on every axis.
DEFAULT_BLOCK_B = 8

# Scoped VMEM a program may take.  The two per-image blocks are double
# buffered (13.1 MB at the flagship shapes) and the unrolled beam loop's
# temporaries come on top: the masked body at K = 3 needs 17.7 MB, over
# the compiler's 16 MB default and far under the core's 128 MiB.
_VMEM_LIMIT = 32 * 1024 * 1024

_PAD_SCOPE = "decoder/attend/pad"


def _make_kernel(compute_dtype, beams: int, masked: bool):
    """The kernel body over a block of ``Bi`` images with ``beams`` rows
    each.  One body for every caller: a row's arithmetic does not depend
    on ``beams`` or on ``masked`` (live rows take the ``where`` true-branch
    everywhere), so per-image and tiled calls agree bitwise.

    Masked (slot-pool geometry, stepped decode): dead pool rows carry
    whatever the retired slot last held — possibly non-finite after many
    steps of garbage arithmetic — so the mask must neutralize them INSIDE
    the kernel: scores are zeroed before the softmax (no exp of garbage)
    and alpha/context are zeroed after, so a dead row can never emit or
    propagate a NaN.
    """
    dt = jnp.dtype(compute_dtype)

    def _kernel(t1_ref, t2_ref, w2_ref, bias_ref, ctx_ref, *refs):
        # blocks: t1 [Bi,Np,da], t2 [Bi,K,da], w2 [1,da], bias [1,Np],
        #         ctx [Bi,Np,D], mask [Bi,K] fp32 (>0 ⇒ live; masked only);
        #         out_ctx [Bi,D], out_alpha [Bi,Np] at K = 1, else
        #         out_ctx [Bi,K,D], out_alpha [Bi,K,Np]
        if masked:
            mask_ref, out_ctx_ref, out_alpha_ref = refs
        else:
            out_ctx_ref, out_alpha_ref = refs
        w2 = w2_ref[0].astype(dt).astype(jnp.float32)
        # the K beams of an image against ONE residency of its grid; K is
        # static and small, and each pass holds [Bi,N,da] at most (never a
        # [Bi,K,N,da] temporary)
        for k in range(beams):
            temp = t1_ref[...] + t2_ref[:, k:k + 1, :]                # [Bi,Np,da]
            # scoring: temp·w2 contracted over the lane axis.  A [.,da]@[da,1]
            # matvec cannot fill the MXU; an elementwise-mul + lane reduction
            # is the same flops on the VPU without the degenerate-matmul
            # layout.  Mirror _dense's dtype story: bf16 multiply, fp32
            # accumulate, round through dt like XLA's bf16 matmul output.
            prod = temp.astype(dt).astype(jnp.float32) * w2
            logits = jnp.sum(prod, axis=-1).astype(dt).astype(jnp.float32)
            if masked:
                valid = mask_ref[:, k:k + 1] > 0.0                    # [Bi,1]
                logits = jnp.where(valid, logits, 0.0)
            logits = logits + bias_ref[...]                           # [Bi,Np]
            m = jnp.max(logits, axis=1, keepdims=True)                # [Bi,1]
            e = jnp.exp(logits - m)
            alpha = e / jnp.sum(e, axis=1, keepdims=True)             # [Bi,Np]
            if masked:
                alpha = jnp.where(valid, alpha, 0.0)
            # weighted context sum: lane-preserving sublane reduction
            ctxsum = jnp.sum(alpha[:, :, None] * ctx_ref[...], axis=1)  # [Bi,D]
            if masked:
                ctxsum = jnp.where(valid, ctxsum, 0.0)
            if beams == 1:
                out_alpha_ref[...] = alpha
                out_ctx_ref[...] = ctxsum
            else:
                out_alpha_ref[:, k:k + 1, :] = alpha[:, None, :]
                out_ctx_ref[:, k:k + 1, :] = ctxsum[:, None, :]

    return _kernel


def _beams_per_grid(rows: int, grids: int) -> int:
    """K of ``rows`` = grids x K step rows, beams of an image adjacent."""
    beams, rest = divmod(rows, grids)
    if rest or not beams:
        raise ValueError(
            f"{rows} attention rows over {grids} context grids: the rows "
            "must be a whole number of beams per grid"
        )
    return beams


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
    """(context [B*K,D], alpha [B*K,N]) from hoisted attention inputs.

    t1: [B, N, da] fp32 — tanh(fc_1a(contexts)), loop-invariant, per IMAGE.
    t2: [B*K, da]  fp32 — tanh(fc_1b(output)) for the current step, per
        beam row, the K beams of an image adjacent (``tile_beams`` order).
        K is read from the shapes; K = 1 where every row has a grid of its
        own (greedy, the slot pool's carry).
    w2: [da, 1]    fp32 — second-layer projection.
    contexts: [B, N, D] fp32, per image.
    row_mask: optional [B*K] bool — slot-pool geometry (stepped decode):
        False rows are dead slots whose inputs may be stale garbage; the
        masked kernel zeroes their scores/alpha/context so nothing
        non-finite propagates, while True rows stay bitwise identical to
        the unmasked call.
    compute_dtype: the scoring multiply dtype (the model's MXU dtype).
    """
    B, N, da = t1.shape
    D = contexts.shape[-1]
    K = _beams_per_grid(t2.shape[0], B)
    n_pad = (-N) % 8
    Np = N + n_pad
    bi = max(1, min(block_b, B))
    b_pad = (-B) % bi
    Bp = B + b_pad
    masked = row_mask is not None

    # the data movement round the kernel carries a scope of its own, so
    # that a device trace tells it from the kernel (docs/OBSERVABILITY.md)
    with jax.named_scope(_PAD_SCOPE):
        operands = [
            jnp.pad(t1.astype(jnp.float32), ((0, b_pad), (0, n_pad), (0, 0))),
            jnp.pad(
                t2.astype(jnp.float32).reshape(B, K, da),
                ((0, b_pad), (0, 0), (0, 0)),
            ),
            w2.astype(jnp.float32).reshape(1, da),
            # padding grid rows get -inf logits so they vanish from the softmax
            jnp.where(
                (jnp.arange(Np) < N)[None, :], 0.0, _NEG_INF
            ).astype(jnp.float32),                                 # [1, Np]
            jnp.pad(
                contexts.astype(jnp.float32), ((0, b_pad), (0, n_pad), (0, 0))
            ),
        ]
        if masked:
            # image-pad rows are dead by construction (pad with 0 = masked)
            operands.append(jnp.pad(
                row_mask.astype(jnp.float32).reshape(B, K), ((0, b_pad), (0, 0))
            ))

    in_specs = [
        pl.BlockSpec((bi, Np, da), lambda b: (b, 0, 0)),
        pl.BlockSpec((bi, K, da), lambda b: (b, 0, 0)),
        pl.BlockSpec((1, da), lambda b: (0, 0)),
        pl.BlockSpec((1, Np), lambda b: (0, 0)),
        pl.BlockSpec((bi, Np, D), lambda b: (b, 0, 0)),
    ]
    if masked:
        in_specs.append(pl.BlockSpec((bi, K), lambda b: (b, 0)))
    # K = 1 keeps the rows on the sublane axis of a 2-D output, as every
    # caller before the per-image layout had it; K beams an image go out
    # as [B, K, ·] (a strided store into [B*K, ·] is not Mosaic's to make)
    # and are flattened below
    block, whole = ((bi,), (Bp,)) if K == 1 else ((bi, K), (Bp, K))

    def out_index(b):
        return (b,) + (0,) * len(block)

    out_ctx, out_alpha = pl.pallas_call(
        _make_kernel(compute_dtype, K, masked),
        name="fused_attend_masked" if masked else "fused_attend",
        grid=(Bp // bi,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(block + (D,), out_index),
            pl.BlockSpec(block + (Np,), out_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(whole + (D,), jnp.float32),
            jax.ShapeDtypeStruct(whole + (Np,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    with jax.named_scope(_PAD_SCOPE):
        return (
            out_ctx[:B].reshape(B * K, D),
            out_alpha[:B].reshape(B * K, Np)[:, :N],
        )


def fused_attend_reference(
    t1: jnp.ndarray,
    t2: jnp.ndarray,
    w2: jnp.ndarray,
    contexts: jnp.ndarray,
    row_mask: "jnp.ndarray | None" = None,
    compute_dtype: str = "float32",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Plain-XLA twin of :func:`fused_attend` (correctness oracle), over
    the same shapes: per-image ``t1``/``contexts``, per-beam-row ``t2``."""
    dt = jnp.dtype(compute_dtype)
    B, N, da = t1.shape
    K = _beams_per_grid(t2.shape[0], B)
    temp = (
        t1.astype(jnp.float32)[:, None]
        + t2.astype(jnp.float32).reshape(B, K, 1, da)
    )                                                              # [B,K,N,da]
    logits = (
        temp.astype(dt) @ w2.astype(dt)
    ).astype(jnp.float32)[..., 0]                                  # [B,K,N]
    if row_mask is not None:
        valid = row_mask.reshape(B, K, 1)
        logits = jnp.where(valid, logits, 0.0)
    alpha = jax.nn.softmax(logits, axis=-1)
    if row_mask is not None:
        alpha = jnp.where(valid, alpha, 0.0)
    ctx = jnp.einsum("bkn,bnd->bkd", alpha, contexts.astype(jnp.float32))
    if row_mask is not None:
        ctx = jnp.where(valid, ctx, 0.0)
    return ctx.reshape(B * K, -1), alpha.reshape(B * K, N)
