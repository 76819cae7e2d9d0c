"""The held expert layer's combine, as one Pallas TPU kernel: each token's
weighted sum of the expert outputs that were computed for it, read from the
grouped products' rows where they lie, every computed row once and no
other row.

The contract.  ``out`` ``[P, H]`` bfloat16 is the last grouped product's
output, its rows sorted by expert; row r < ``done`` is the output of the
routed pair ``order[r] = t * k + j`` (token t's slot j); rows from ``done``
on hold nothing any product wrote.  ``weights`` ``[T, k]`` float32, ``done``
int32.  Per token t of T:

    y[t] = sum over rows r < done with order[r] // k == t
           of  weights[t, order[r] % k] * float32(out[r])        [T, H] float32

A token with no computed pair reads exactly 0.  A row at or past ``done``
is never read into y (NaN there never arrives).  The op ENDS at y: the
shared expert's term, the cast and the residual are the caller's.  Against
the ``lax`` form (``lm_common._combine_lax``: the reference of the tests,
the form of every other backend and of a step's few pairs) the result may
differ by the float32 rounding of a k-term sum, because the kernel adds a
token's rows in the order they lie in (by expert) and XLA's reduce in an
order of its own, and by nothing else: within
``k * 2**-23 * sum_j |w_j * row_j|``.

The ``lax`` form inverts ``order`` by a scatter over all T * k pairs and
gathers a row for EVERY pair (``[T * k, H]`` bfloat16 written, read back as
``[T, k, H]`` float32 under a mask); a layer that holds a sixteenth or an
eighth of its experts computed 6-13% of them.

The kernel.  Grid (tiles of H, blocks of ``out``'s rows).  A tile
``[T, th]`` of y stays in VMEM as float32 through a whole pass over the
rows; ``out`` arrives through an ordinary ``BlockSpec`` whose index map
stops at the last block under ``done`` (the blocks past it are neither
fetched nor looked at); the scalar core walks a block's computed rows and
adds ``w[r] * out[r, tile]`` at sublane ``tok[r]``.  ``tok = order[:P] // k``
and ``w = weights.reshape(-1)[order[:P]]`` are two gathers of P scalars,
prefetched to SMEM.  Nothing of shape ``[T * k, H]`` exists and nothing
inverts the T * k pairs.

The caller chooses from the shapes and the backend (``takes``) and says
nothing else: ``moe_combine`` is differentiable, its backward the ``lax``
form's derivative by two P-row gathers.  ``interpret=True`` (any backend
but the TPU) runs the same kernel on the CPU for the tests.
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

# Pairs from which the kernel is taken: ``lm_common._gmm_tiling``'s line
# between a step's few rows and a prefill's many.
_MIN_PAIRS = 8192

# Rows of ``out`` a program, and the most bytes of one float32 tile
# ``[T, th]`` of y, of which the pipeline keeps ONE buffer (a tile changes
# H / th times a call; a second buffer would halve the width that fits the
# core's 128 MiB).  The wider the tile, the fewer turns the scalar core
# takes a row: timed on a v5e at the two published shapes (4,096 tokens x 8
# over 16,384 rows of 5,120 / 8,192 of 6,144, an eighth / a sixteenth of
# them computed; ms a call, PERF.md section 6) rows x columns
# 256 x H/8 0.74 / 0.46 | 256 x H/4 0.62 / 0.41 | 128 x H/4 0.64 / 0.42 |
# 512 x H/4 0.60 / 0.40 | H/2 under one buffer: 256 rows 0.44 / 0.34, 512
# rows 0.44 / 0.35 (nothing to choose); two rows a turn of the loop 0.52 /
# 0.36 at H/4 and nothing at H/2; the ``lax`` form 3.39 / 1.52.
_ROWS = 512
_TILE_BYTES = 48 << 20

# ``tok`` and ``w`` lie in SMEM whole: 8 bytes a row of ``out``.
_MAX_ROWS = 65536


def _tile(T: int, H: int) -> int:
    """Columns of a tile of y: the most whole lane tiles that divide H and
    keep ``[T, th]`` float32 within ``_TILE_BYTES``; 0 where none does (H
    no whole lane tiles among them)."""
    chunks = 0 if H % _LANES else H // _LANES
    for n in range(chunks, 0, -1):
        if chunks % n == 0 and T * n * _LANES * 4 <= _TILE_BYTES:
            return n * _LANES
    return 0


def takes(T: int, k: int, H: int, P: int) -> bool:
    """Whether the kernel takes a combine of T tokens x k slots over P rows
    of H here: on the TPU (or under the tests' hook), a prefill's pairs,
    rows of whole lane tiles, a tile of y that fits."""
    return (
        (FORCE_INTERPRET or jax.default_backend() == "tpu")
        and T * k >= _MIN_PAIRS and P <= _MAX_ROWS and _tile(T, H) > 0
    )


def _kernel(done_s, tok_s, w_s, x_ref, y_ref, rows_ref, *, rb):
    """Grid (tiles of H, blocks of rows), the rows innermost.  Scalar
    prefetch: done [1], tok [P] int32, w [P] float32.  x [rb, th] bfloat16,
    y [T, th] float32 (the same block through a pass over the rows);
    scratch rows [rb, th] float32, the block converted once."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    first = i * rb
    live = jnp.minimum(done_s[0] - first, rb)

    @pl.when(live > 0)
    def _():
        rows_ref[...] = x_ref[...].astype(jnp.float32)

        def one_row(r, carry):
            at = pl.ds(tok_s[first + r], 1)
            y_ref[at, :] = y_ref[at, :] + w_s[first + r] * rows_ref[pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(0, live, one_row, None)


def _listed(order, weights, P: int):
    """(tok [P] int32, w [P] float32): the token and the weight of each of
    the first P sorted pairs."""
    k = weights.shape[1]
    pairs = order[:P].astype(jnp.int32)
    return pairs // k, weights.astype(jnp.float32).reshape(-1)[pairs]


@partial(jax.jit, static_argnames=("interpret",))
def combine_kernel(out, order, weights, done, *, interpret=False):
    """The contract through the kernel (the module's docstring).  H is whole
    lane tiles and a tile of y fits (``takes``)."""
    P, H = out.shape
    T = weights.shape[0]
    th = _tile(T, H)
    if not th:
        raise ValueError(f"{T} tokens x rows of {H}: no tile of whole {_LANES}-lane columns fits")
    rb = min(_ROWS, -(-P // 16) * 16)
    done = jnp.minimum(done, P).astype(jnp.int32).reshape(1)
    tok, w = _listed(order, weights, P)

    def live_block(h, i, done_s, *_):
        return jnp.minimum(i, jnp.maximum(done_s[0] - 1, 0) // rb), h

    return pl.pallas_call(
        partial(_kernel, rb=rb),
        name="moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(H // th, -(-P // rb)),
            in_specs=[pl.BlockSpec((rb, th), live_block)],
            out_specs=pl.BlockSpec(
                (T, th), lambda h, i, *_: (0, h), pipeline_mode=pl.Buffered(1)
            ),
            scratch_shapes=[pltpu.VMEM((rb, th), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # y's tile, x's two blocks and their float32 copy, and room
            vmem_limit_bytes=T * th * 4 + 2 * rb * th * 4 + (8 << 20),
        ),
        interpret=interpret,
    )(done, tok, w, out)


@jax.custom_vjp
def moe_combine(out, order, weights, done):
    """out [P, H] bfloat16, order [>= P] int32, weights [T, k] float32,
    done () int32 -> y [T, H] float32 (the module's docstring), through the
    kernel: interpreted off the TPU."""
    return combine_kernel(out, order, weights, done, interpret=jax.default_backend() != "tpu")


def _fused_fwd(out, order, weights, done):
    return moe_combine(out, order, weights, done), (out, order, weights, done)


def _fused_bwd(saved, dy):
    """The ``lax`` form's derivative by two P-row gathers:
    d out[r] = w[r] * dy[tok[r]], d w[r] = <out[r], dy[tok[r]]>, r < done."""
    out, order, weights, done = saved
    P = out.shape[0]
    tok, w = _listed(order, weights, P)
    live = (jnp.arange(P, dtype=jnp.int32) < done)[:, None]
    rows = dy[tok]
    d_out = jnp.where(live, w[:, None] * rows, 0.0).astype(out.dtype)
    d_w = jnp.sum(jnp.where(live, out.astype(jnp.float32) * rows, 0.0), axis=1)
    d_weights = jnp.zeros((weights.size,), jnp.float32).at[order[:P]].add(d_w)
    return d_out, None, d_weights.reshape(weights.shape).astype(weights.dtype), None


moe_combine.defvjp(_fused_fwd, _fused_bwd)
