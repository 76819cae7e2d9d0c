"""Causal attention of one whole sequence under a mask all heads share,
fused (flash-style) into one Pallas TPU kernel.

What it computes, per head ``h`` and query ``t`` of a sequence of S
positions (queries and keys are the same positions):

    s[t, j]  = (q[t, h] . k[j, h]) * scale            float32
    seen     = j <= t  and  (t < S - M  or  mask[t - (S - M), j] != 0)
               and  j > t - window                    (where there is a window)
    out[t,h] = sum_j exp(s[t, j] - max) v[j, h] / sum_j exp(s[t, j] - max)
               over the ``seen`` j

with bfloat16 MXU operands, float32 accumulation, float32 scores and
``exp``, the weights cast to bfloat16 for the second product and the
softmax's division once, after the weighted sum: the arithmetic of the
``lax`` blocked form it stands in for (``models``' sparse-attention
prefill), only the residency differs.  A block of scores ``[bq, bk]``
lives in VMEM from the first product to the second under a running
maximum and a running sum (online softmax); no float32 score reaches HBM,
where the ``lax`` form writes a block of them and reads it back three
times.

Shapes, not models.  ``q`` ``[heads, S, d_qk]``, ``k``
``[heads / group, S, d_qk]`` and ``v`` ``[heads / group, S, d_v]`` are
head-major (``group`` = 1: a key/value head a query head; more: query head
``h`` reads key/value head ``h // group``, grouped queries, and a key/value
block is fetched once for the heads of a program that share it, never
replicated), each head's rows together (the layout
the einsum that makes them is asked for: a free choice of its output, where
a ``[bq, 1, d]`` block of ``[S, heads, d]`` is no legal tile and a
transposing copy of three such arrays costs more than the kernel saves);
the output ``[S, heads * d_v]`` has the heads side by side in the lanes,
which is what an output projection reads (``d_v`` a whole multiple of 128
on the chip).  ``mask`` ``[M, S]`` int8 covers the LAST M queries (a
learned selection applies only where more keys are visible than it
keeps); the queries before attend all they see, causal by iota, and read
no mask.  One mask for all heads: a program takes ``hb`` heads, which
share each mask block it fetches, so the mask is read ``heads / hb``
times, not ``heads`` times.  Key blocks wholly above the diagonal are
neither fetched (their block index is clamped to the last one needed) nor
computed.  ``window`` (a sliding layer: a query sees itself and the
``window - 1`` positions before it) bounds the keys from BELOW as the
diagonal does from above: the band's edge by iota, no mask array, and the
key tiles wholly below the band neither fetched nor computed either: the
grid's key axis is as long as the most tiles a query tile's band meets (4
of 16 at 4,096 positions, a window of 513 and tiles of 512 x 256), and a
query tile's walk starts at its band's first tile.

A row may see nothing in a whole key block (a selection need not keep the
keys nearest the query), so masked scores take a finite floor, not -inf:
the running maximum of such a row stays at the floor, what it gathers
meanwhile is multiplied by ``exp(floor - first real maximum) = 0`` when a
visible key arrives, and no ``exp(-inf - -inf)`` is ever taken.  (A row
with no visible key AT ALL reads a finite average where the ``lax`` form
reads NaN; causal rows always see themselves or what the selection kept.)

Forward only: the differentiated path keeps the ``lax`` form.
``interpret=True`` (any backend but the TPU) runs the same kernel on the
CPU for the tests.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _NEG_INF

# Test hook: ``available()`` off the TPU, the kernel in interpret mode.
FORCE_INTERPRET = False

# (queries a tile, keys a tile, heads a program), from a sweep on a v5e at
# the published widths (64 heads, 4,096 positions, d = 256, the last 2,048
# queries under a mask of 2,048 keys a row; ms a call, PERF.md section 6):
# 512 x 256 x 16 4.16 | 512 x 256 x 8 4.30 | 512 x 256 x 4 4.53 |
# 512 x 512 x 8 4.51 | 512 x 128 x 8 4.87 | 256 x 256 x 8 4.72 |
# 1024 x 256 x 4 4.56 | 512 x 1024 x 4 4.99 | 512 x 2048 x 2 5.51; with
# the heads looped over, not unrolled, 512 x 256 x 16 4.80 and
# 512 x 256 x 8 4.97; the ``lax`` blocks 12.8.  Narrow key tiles keep a
# head's [512, 256] scores near the registers; more heads a program are
# more independent work to interleave and fewer reads of the mask.
_TILES = (512, 256, 16)

# Scoped VMEM a program may take: the q, k, v and output blocks double
# buffered (24 MB at ``_TILES`` and d = 256), the float32 accumulator and
# running statistics (16 MB) and the score tile's temporaries; the
# compiler's default is 16 MB, the core has 128 MiB.
_VMEM_LIMIT = 64 * 1024 * 1024

_LANES = 128

# The head loop unrolled: independent heads let the scheduler run one
# head's exponentials under another's products (the sweep above: 13-15%).
_UNROLL = True


def available() -> bool:
    """Whether a caller may take the kernel here: on the TPU, or under
    the tests' hook."""
    return FORCE_INTERPRET or jax.default_backend() == "tpu"


def _tiles(S: int, unmasked: int, heads: int) -> Tuple[int, int, int]:
    """``_TILES`` cut to what divides the shapes: whole query tiles on
    either side of the mask's first row, whole key tiles, whole groups of
    heads."""
    bq, bk, hb = _TILES
    return math.gcd(bq, S, unmasked), math.gcd(bk, S), math.gcd(hb, heads)


def _across(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """x [rows, w], every lane of a row equal, w dividing n -> [rows, n]."""
    return jnp.tile(x, (1, n // x.shape[1]))


def _first_key_tile(qi, bq: int, bk: int, window: int):
    """The key tile a query tile's band begins in."""
    return jnp.maximum(qi * bq - (window - 1), 0) // bk


def _kernel(*refs, heads, kv_heads, d_v, bq, bk, scale, first_masked, window):
    """Grid (head group, query tile, key tile), the key tiles innermost.
    Blocks: q [heads, bq, d_qk], k [kv_heads, bk, d_qk], v [kv_heads, bk,
    d_v] (the program's query head h reads ``h * kv_heads // heads``),
    mask [bq, bk] int8 (where the call has one), out [bq, heads * d_v];
    scratch per head: the running maximum and sum [heads, bq, lanes], the
    accumulator [heads, bq, d_v], float32."""
    if first_masked is None:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        mask_ref = None
    else:
        q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref = refs
    qi, ki = pl.program_id(1), pl.program_id(2)
    last = ((qi + 1) * bq - 1) // bk          # the key tile the diagonal ends in
    start = ki == 0
    if window is not None:                    # the walk starts where the band does
        ki = ki + _first_key_tile(qi, bq, bk, window)

    @pl.when(start)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki <= last)
    def _():
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        seen = cols <= rows
        if window is not None:
            seen = seen & (cols > rows - window)
        if mask_ref is not None:
            seen = seen & ((mask_ref[...].astype(jnp.int32) != 0) | (qi < first_masked))
        # made once a tile, added to the scores of each of its heads
        bias = jnp.where(seen, 0.0, _NEG_INF)

        def one_head(h, _):
            kv = h if kv_heads == heads else h * kv_heads // heads
            s = jax.lax.dot_general(
                q_ref[h], k_ref[kv], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale + bias
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - _across(m_next, bk))
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_next
            acc_ref[h] = acc_ref[h] * _across(alpha, d_v) + jnp.dot(
                p.astype(jnp.bfloat16), v_ref[kv], preferred_element_type=jnp.float32,
            )

        jax.lax.fori_loop(0, heads, one_head, None, unroll=_UNROLL)

    @pl.when(ki == last)
    def _():
        for h in range(heads):
            o_ref[:, h * d_v:(h + 1) * d_v] = (
                acc_ref[h] / _across(l_ref[h], d_v)
            ).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("scale", "tiles", "interpret", "window"))
def flash_prefill(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    *,
    scale: float,
    tiles: Optional[Tuple[int, int, int]] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """q [heads, S, d_qk], k [heads / group, S, d_qk], v [heads / group, S,
    d_v] bfloat16 (``group`` from the shapes: 1, or grouped queries), one
    causal sequence; mask [M, S] (int8 or bool; nonzero: query S - M + i attends
    key j, where j is also at or before it) or None -> [S, heads * d_v]
    bfloat16.  ``tiles``: (queries, keys, heads) a program, each dividing
    what it tiles (and S - M whole query tiles); None takes ``_TILES``.
    ``window``: a query attends its own position and the ``window - 1``
    before it, no others."""
    heads, S, d_qk = q.shape
    d_v = v.shape[2]
    group, uneven = divmod(heads, k.shape[0])
    masked = 0 if mask is None else mask.shape[0]
    bq, bk, hb = tiles or _tiles(S, S - masked, heads)
    if S % bq or S % bk or heads % hb or (S - masked) % bq:
        raise ValueError(
            f"tiles {(bq, bk, hb)} do not divide {S} positions ({masked} under the "
            f"mask) and {heads} heads"
        )
    if uneven or k.shape[0] != v.shape[0] or (hb % group and group % hb):
        raise ValueError(
            f"{heads} query heads, {hb} a program, over {k.shape[0]} key and "
            f"{v.shape[0]} value heads: a program's heads share whole key/value heads"
        )
    # key/value heads a program: those its query heads read, one at least;
    # program g's begin at key/value head g * hb // group
    kvb = max(hb // group, 1)
    if group == 1:
        def kv_block(g):
            return g
    else:
        def kv_block(g):
            return g * hb // (group * kvb)
    first_masked = (S - masked) // bq if masked else None

    key_tiles = S // bk
    if window is None:
        def key_tile(qi, ki):
            return jnp.minimum(ki, ((qi + 1) * bq - 1) // bk)
    else:
        if window < 1:
            raise ValueError(f"window {window}: a query sees at least itself")
        # the most key tiles a query tile's band meets
        key_tiles = max(
            ((qi + 1) * bq - 1) // bk - max(qi * bq - (window - 1), 0) // bk + 1
            for qi in range(S // bq)
        )

        def key_tile(qi, ki):
            return jnp.minimum(
                ki + _first_key_tile(qi, bq, bk, window), ((qi + 1) * bq - 1) // bk
            )

    in_specs = [
        pl.BlockSpec((hb, bq, d_qk), lambda g, qi, ki: (g, qi, 0)),
        pl.BlockSpec((kvb, bk, d_qk), lambda g, qi, ki: (kv_block(g), key_tile(qi, ki), 0)),
        pl.BlockSpec((kvb, bk, d_v), lambda g, qi, ki: (kv_block(g), key_tile(qi, ki), 0)),
    ]
    operands = [q, k, v]
    if masked:
        # queries before the mask's first row stay on its first block,
        # which is then fetched once and not read
        in_specs.append(pl.BlockSpec(
            (bq, bk),
            lambda g, qi, ki: (
                jnp.maximum(qi - first_masked, 0),
                jnp.where(qi >= first_masked, key_tile(qi, ki), 0),
            ),
        ))
        operands.append(mask.astype(jnp.int8))
    # the running maximum and sum, one number a row, kept across a vector
    # register's lanes where the tiles are whole registers wide
    lanes = _LANES if bk % _LANES == 0 and d_v % _LANES == 0 else 1
    return pl.pallas_call(
        partial(
            _kernel, heads=hb, kv_heads=kvb, d_v=d_v, bq=bq, bk=bk, scale=scale,
            first_masked=first_masked, window=window,
        ),
        name="flash_prefill",
        grid=(heads // hb, S // bq, key_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bq, hb * d_v), lambda g, qi, ki: (qi, g)),
        out_shape=jax.ShapeDtypeStruct((S, heads * d_v), jnp.bfloat16),
        scratch_shapes=[
            pltpu.VMEM((hb, bq, lanes), jnp.float32),
            pltpu.VMEM((hb, bq, lanes), jnp.float32),
            pltpu.VMEM((hb, bq, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*operands)
