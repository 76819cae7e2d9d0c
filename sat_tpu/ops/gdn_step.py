"""One token of the gated delta rule for every beam of a search, as one
Pallas TPU kernel that READS a row's state where the row's parent left it
and WRITES it where the row lies: the search's reorder of the state, folded
into the pass over the state that the step makes anyway.

The contract.  ``state`` ``[R, nv, dk, dv]`` (R = B * K rows, K beams an
image, float32 or bfloat16 as it comes) is the state the LAST step left, in
the slots that step wrote; ``source`` ``[R]`` int32 names for each row the
row it descends from, one of its own image's K (``b * K + parent``).  With
``s = float32(state[source[r]])`` per value head h of key head h // r
(``r = nv / nk``), q, k ``[R, nk, dk]``, v ``[R, nv, dv]``, beta, decay
``[R, nv]``, all float32:

    s_k   = (s^T k) * decay             s_q = (s^T q) * decay          [dv]
    d     = beta * (v - s_k)
    new[r]= dtype(s * decay + k d^T)                                   [dk, dv]
    o[r]  = s_q + (q . k) * d                                          [dv]

``gdn_step_lax`` is that in ``lax`` (gather, then the arithmetic: the
tests' reference, the form of every backend but the TPU); the kernel may
differ from it by the float32 rounding of a ``dk``-term sum, whose order
is its own, and by nothing else.  A row that no ``source`` names is never
read into ``new`` or ``o`` (NaN there never arrives).

The kernel.  Grid (images, groups of ``hb`` value heads: whole key heads).
A program's block is the K rows of ONE image for one group of heads,
``[1, K, hb, dk, dv]`` in and out at the SAME block index: a row's source
is a row of its image, so the program that writes a block has read all it
needs of it, and ``input_output_aliases`` makes the update in place.  The
state is read once and written once a call and nothing else of its size
exists: no gathered copy, no second buffer for the loop to copy back.
Slot k takes ``in[source[b * K + k] % K]`` by a dynamic index on a leading
(untiled) axis.  Per head scalars (decay, beta, q . k) and the sources are
prefetched to SMEM; k and q arrive as rows ``[.., dk]`` and are turned to
columns by ONE transpose a program (the state's ``dk`` lies on sublanes).
All products are float32 multiplies and sums on the vector unit.

The caller chooses from shapes and backend (``takes``) and says nothing
else.  ``interpret=True`` (any backend but the TPU) runs the same kernel on
the CPU for the tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook: ``takes()`` off the TPU, the kernel in interpret mode.
FORCE_INTERPRET = False

_LANES = 128
_SUBLANES = 8

# Value heads a program: ``_HEADS * K`` heads' states in and as many out,
# each twice (the pipeline's two buffers).  At the published 128 x 128
# float32 and K = 3: 1.5 MB a block, 6 MB in all.
_HEADS = 8

# The per-head scalars lie in SMEM whole: 12 bytes a row and value head.
_MAX_SCALARS = 1 << 16


def _group(nk: int, nv: int) -> int:
    """Value heads a program: the most whole key heads within ``_HEADS``
    that divide the layer's (one key head's where even that is more)."""
    r = nv // nk
    return r * max(n for n in range(1, nk + 1) if nk % n == 0 and n * r <= max(_HEADS, r))


def takes(R: int, K: int, nk: int, nv: int, dk: int, dv: int) -> bool:
    """Whether the kernel takes a step of R rows (K an image) here: on the
    TPU (or under the tests' hook); there, heads of whole lane tiles, a
    group whose heads and whose k and q rows fill whole sublane tiles (the
    shapes Mosaic was shown), scalars that fit SMEM."""
    if FORCE_INTERPRET:
        return True
    hb = _group(nk, nv)
    return (
        jax.default_backend() == "tpu"
        and dk % _LANES == 0 and dv % _LANES == 0
        and hb % _SUBLANES == 0 and (2 * hb * nk // nv) % _SUBLANES == 0
        and R * nv <= _MAX_SCALARS
    )


def gdn_step_lax(state, source, q, k, v, beta, decay, dtype):
    """The contract in ``lax``: the state gathered by ``source``, then the
    recurrence with both products of the state as it came in ONE pass over
    it -> (new ``[R, nv, dk, dv]`` ``dtype``, o ``[R, nv, dv]`` float32)."""
    R, nv, dk, dv = state.shape
    nk = k.shape[1]
    r = nv // nk
    s = state[source].astype(jnp.float32).reshape(R, nk, r, dk, dv)
    decay = decay.reshape(R, nk, r)
    s_k = jnp.sum(s * k[:, :, None, :, None], axis=-2) * decay[..., None]       # [R, nk, r, dv]
    s_q = jnp.sum(s * q[:, :, None, :, None], axis=-2) * decay[..., None]
    d = beta.reshape(R, nk, r)[..., None] * (v.reshape(R, nk, r, dv) - s_k)
    s = s * decay[..., None, None] + k[:, :, None, :, None] * d[..., None, :]
    o = s_q + jnp.sum(q * k, axis=-1)[:, :, None, None] * d                     # the new state's product by q
    return s.reshape(R, nv, dk, dv).astype(dtype), o.reshape(R, nv, dv)


def _kernel(src_s, decay_s, beta_s, qk_s, kq_ref, v_ref, s_ref, new_ref, o_ref, *, K, nk, nv, hb):
    """Grid (images, groups of heads).  Scalar prefetch: src [R] int32 (the
    slot of a row's source within its image), decay, beta [R * nv], qk
    [R * nk] float32.  kq [1, K, 1, 2 * hb / r, dk] (the group's k rows,
    then its q rows), v [1, K, 1, hb, dv], s [1, K, hb, dk, dv]; out: new
    as s, o as v."""
    b, g = pl.program_id(0), pl.program_id(1)
    r = nv // nk
    nkb = hb // r
    dk = kq_ref.shape[-1]
    # every slot's k and q of this group as columns [dk, .]: one transpose
    rows = kq_ref[0, :, 0].reshape(K * 2 * nkb, dk)
    pad = -rows.shape[0] % _LANES
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, dk), rows.dtype)], axis=0)
    cols = rows.T                                           # [dk, K * 2 * nkb (+ pad)]
    for slot in range(K):
        row = b * K + slot
        src = src_s[row]
        for j in range(nkb):
            at = slot * 2 * nkb + j
            k_col, q_col = cols[:, at:at + 1], cols[:, at + nkb:at + nkb + 1]
            qk = qk_s[row * nk + g * nkb + j]
            for i in range(r):
                h = j * r + i
                head = row * nv + g * hb + h
                decay, beta = decay_s[head], beta_s[head]
                s = s_ref[0, src, h].astype(jnp.float32)                            # [dk, dv]
                s_k = jnp.sum(s * k_col, axis=0, keepdims=True) * decay             # [1, dv]
                s_q = jnp.sum(s * q_col, axis=0, keepdims=True) * decay
                d = beta * (v_ref[0, slot, 0, h:h + 1, :] - s_k)
                new_ref[0, slot, h] = (s * decay + k_col * d).astype(new_ref.dtype)
                o_ref[0, slot, 0, h:h + 1, :] = s_q + qk * d


@partial(jax.jit, static_argnames=("K", "dtype", "interpret"))
def gdn_step_kernel(state, source, q, k, v, beta, decay, *, K, dtype, interpret=False):
    """The contract through the kernel (the module's docstring); in place
    where ``dtype`` is the state's own."""
    R, nv, dk, dv = state.shape
    nk = k.shape[1]
    r, B = nv // nk, R // K
    hb = _group(nk, nv)
    G, nkb = nv // hb, hb // r
    # a group's k rows, then its q rows: [B, K, G, 2 * nkb, dk]
    kq = jnp.concatenate([k.reshape(B, K, G, nkb, dk), q.reshape(B, K, G, nkb, dk)], axis=3)
    qk = jnp.sum(q * k, axis=-1).reshape(-1)
    new, o = pl.pallas_call(
        partial(_kernel, K=K, nk=nk, nv=nv, hb=hb),
        name="gdn_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, G),
            in_specs=[
                pl.BlockSpec((1, K, 1, 2 * nkb, dk), lambda b, g, *_: (b, 0, g, 0, 0)),
                pl.BlockSpec((1, K, 1, hb, dv), lambda b, g, *_: (b, 0, g, 0, 0)),
                pl.BlockSpec((1, K, hb, dk, dv), lambda b, g, *_: (b, 0, g, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, K, hb, dk, dv), lambda b, g, *_: (b, 0, g, 0, 0)),
                pl.BlockSpec((1, K, 1, hb, dv), lambda b, g, *_: (b, 0, g, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, K, nv, dk, dv), dtype),
            jax.ShapeDtypeStruct((B, K, G, hb, dv), jnp.float32),
        ],
        # operands count the prefetched scalars: the state is the seventh
        input_output_aliases={6: 0} if state.dtype == dtype else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state's block in and out, two buffers each, and room
            vmem_limit_bytes=2 * K * hb * dk * dv * (state.dtype.itemsize + jnp.dtype(dtype).itemsize) + (16 << 20),
        ),
        interpret=interpret,
    )(
        source.astype(jnp.int32) % K, decay.astype(jnp.float32).reshape(-1), beta.astype(jnp.float32).reshape(-1), qk,
        kq, v.reshape(B, K, G, hb, dv), state.reshape(B, K, nv, dk, dv),
    )
    return new.reshape(R, nv, dk, dv), o.reshape(R, nv, dv)


def gdn_step(state, source, q, k, v, beta, decay, *, K, dtype):
    """The contract (the module's docstring) in the form this backend and
    these shapes take -> (new, o, whether the kernel ran)."""
    R, nv, dk, dv = state.shape
    if takes(R, K, k.shape[1], nv, dk, dv):
        new, o = gdn_step_kernel(
            state, source, q, k, v, beta, decay, K=K, dtype=dtype, interpret=jax.default_backend() != "tpu"
        )
        return new, o, True
    return gdn_step_lax(state, source, q, k, v, beta, decay, dtype) + (False,)
