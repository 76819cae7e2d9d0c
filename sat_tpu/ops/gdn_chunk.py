"""The gated delta rule over whole sequences in its CHUNKED form, as ``lax``
and as one Pallas TPU kernel that keeps everything a chunk makes in VMEM.

The rule.  q, k ``[B, S, nk, dk]`` (each head over its root of squares, q
times ``dk^-0.5``), v ``[B, S, nv, dv]``, g, beta ``[B, S, nv]``, all
float32; ``state`` ``[B, nv, dk, dv]`` before position 0 (None: zero).  Per
value head h of key head ``h // r`` (``r = nv / nk``), S in R^[dk, dv]:

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

-> (o ``[B, S, nv, dv]`` float32, the state after position S - 1).  Both
forms run it in chunks of ``CHUNK`` positions, as the public
``torch_chunk_gated_delta_rule``: with ``t`` the chunk's running sum of g,
``D[i, j] = exp(t_i - t_j)`` at ``j <= i`` (the exponent masked BEFORE the
``exp``) and ``A = beta_i (k_i . k_j) D[i, j]`` strictly below the diagonal,

    T      = (I + A)^-1                         forward substitution, float32
    value  = T (beta v)          kd = T (beta exp(t) k)
    v_new  = value - kd S
    o      = (exp(t) q) S + ((q k^T) D) v_new
    S     <- exp(t_last) S + (exp(t_last - t) k)^T v_new

EVERY product is float32 at ``HIGHEST`` with float32 accumulation and S is
float32 inside the rule, in both forms: what the configuration states.  The
two forms may differ by the float32 rounding of their sums' order (the
solve's blocks, a product's tiles, the last chunk's length) and by nothing
else; ``tests/test_gdn_chunk_kernel.py`` holds both to the float64
recurrence.

``gdn_chunk_lax`` is whole-batch ``einsum``s over ``[B, key head, (value
head,) chunk, position, ..]`` and a ``lax.scan`` over the chunks: every
chunk-local matrix is an HBM array; a sequence pads to whole chunks with
``beta = 0``, ``g = 0``, ``k = 0``, which leave S as it was.  It is
differentiable, and it is the form of every backend but the TPU, of every
shape the kernel refuses, and of ``teacher_forced`` everywhere (``train_lm``
differentiates it; a ``pallas_call`` has no transpose).

``gdn_chunk_kernel`` (``prefill`` on the TPU).  It takes q, k and v WHERE
THE CONV LEFT THEM: one ``[B, S, 2 nk dk + nv dv]`` array, concat(q, k, v)
after the conv's silu and BEFORE the heads' l2 norms, which the kernel
takes itself (``eps``): nothing is sliced, normed into ``[B, S, head, d]``
or turned to ``[B, head, chunk, position, d]`` in HBM first.  Grid
(sequences, key heads), both parallel.  A program reads its key head's q
and k ``[S, dk]`` and its r value heads' v ``[S, r dv]`` as blocks of whole
128-lane heads of that one array, and writes o into ``[B, S, nv dv]`` and
the final S once.  The sequence is its whole chunks and, where positions
are left, ONE shorter chunk (``_parts``: 196 = 3 x 64 + 8, four of the
eight past the end: the block reaches past the array, those rows are
SELECTED to zero whatever lies there, and the rows of o they make are not
written back).  The r value heads of a key head lie head-major down the
rows of ONE ``[P, P]`` matrix a chunk (``P = r C``, 128 at the published r =
2): ``k k^T`` and ``q k^T`` are taken once for them; the decay, ``A``, ``T``
and the chunk's own scores are block-diagonal in heads, so the solve and
the products by ``T`` and by the scores are whole-tile products that serve
all r heads at once.  What a chunk makes without S (scores, solve,
corrected values and keys) is made for ALL chunks of a part at once, ``[n,
..]`` arrays: a chunk's chain of products is long and serial and the
compiler keeps to the program's order, so the n chains lie side by side in
it.  Then the chain through S, a chunk after the other, S ``[r, dk, dv]``
float32 in VMEM scratch, the r heads r chains.  The solve (``_solve``) is
forward substitution: the ``_SOLVE_BLOCK``-row diagonal blocks row by row
on the vector unit, then the doubling rule of ``unit_lower_inverse`` (``X
<- X - X L X``, L the part of A a level takes in) in ``[P, P]`` products; no
level works on blocks smaller than ``_SOLVE_BLOCK``.  g and beta arrive as
rows of positions per key head and chunk (the running sum taken outside:
they are ``[B, S, nv]``); a column is turned out of a row by a masked sum.
None of decay, A, T, value, kd, the scores, v_new is an HBM array.

The caller chooses from shapes and backend (``takes``) and says nothing
else.  ``interpret=True`` (any backend but the TPU) runs the same kernel on
the CPU for the tests.
"""

from __future__ import annotations

from functools import partial, reduce

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook: ``takes()`` off the TPU, the kernel in interpret mode.
FORCE_INTERPRET = False

CHUNK = 64              # positions a chunk (a power of two)
HIGHEST = jax.lax.Precision.HIGHEST

_LANES = 128
# rows of a diagonal block that the vector unit solves row by row (two
# sublane tiles); the doubling rule merges them to a chunk
_SOLVE_BLOCK = 16
# chunks a sequence may have: a program makes all its chunks' own work at
# once (0.4 MB of VMEM a chunk) and unrolls the chain through S
_MAX_CHUNKS = 8


def takes(S: int, nk: int, nv: int, dk: int, dv: int) -> bool:
    """Whether the kernel takes sequences of S positions through a layer of
    these heads here: on the TPU (or under the tests' hook); there, heads
    of whole lane tiles, a key head's value heads filling ONE lane tile of
    chunk positions (r = 2: the ``[128, 128]`` matrices Mosaic was shown),
    and no more chunks than a program unrolls and holds in VMEM at once."""
    if FORCE_INTERPRET:
        return True
    return (
        jax.default_backend() == "tpu"
        and dk % _LANES == 0 and dv % _LANES == 0 and nv // nk * CHUNK == _LANES and S <= _MAX_CHUNKS * CHUNK
    )


# ---------------------------------------------------------------------------
# the ``lax`` form
# ---------------------------------------------------------------------------


def _mm32(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def unit_lower_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """a [..., C, C] strictly lower triangular (C a power of two) ->
    ``(I + a)^-1``: forward substitution in blocks.  The diagonal blocks'
    inverses double in size a level: of ``[[M11, 0], [M21, M22]]`` it is
    ``[[M11^-1, 0], [-M22^-1 M21 M11^-1, M22^-1]]``."""
    C = a.shape[-1]
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (C, 1, 1), a.dtype)        # 1 x 1 blocks of a unit diagonal
    s = 1
    while s < C:
        n = C // (2 * s)
        blocks = a.reshape(lead + (n, 2, s, n, 2, s))[..., :, 1, :, :, 0, :]     # [.., n, s, n, s]
        m21 = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)    # [.., n, s, s]
        inv = inv.reshape(lead + (n, 2, s, s))
        inv11, inv22 = inv[..., 0, :, :], inv[..., 1, :, :]
        x21 = -_mm32("...ij,...jk->...ik", _mm32("...ij,...jk->...ik", inv22, m21), inv11)
        inv = jnp.concatenate([
            jnp.concatenate([inv11, jnp.zeros_like(inv11)], axis=-1),
            jnp.concatenate([x21, inv22], axis=-1),
        ], axis=-2)                                                              # [.., n, 2s, 2s]
        s *= 2
    return inv[..., 0, :, :]


def gdn_chunk_lax(q, k, v, g, beta, state=None):
    """The contract in ``lax`` -> (o, the state), float32.  A key head's
    products with itself and with its query are taken once for the r value
    heads it serves."""
    B, S, nk, dk = q.shape
    nv, dv = v.shape[2:]
    r, C = nv // nk, CHUNK
    pad = -S % C
    if pad:     # beta = 0, g = 0, k = 0 leave S as it was; q = 0 gives an output nothing reads
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    n = (S + pad) // C
    # [B, key head, (value head of it,) chunk, position, ..]
    q, k = (jnp.transpose(x.reshape(B, n, C, nk, dk), (0, 3, 1, 2, 4)) for x in (q, k))
    v = jnp.transpose(v.reshape(B, n, C, nk, r, dv), (0, 3, 4, 1, 2, 5))
    g, beta = (jnp.transpose(x.reshape(B, n, C, nk, r), (0, 3, 4, 1, 2)) for x in (g, beta))
    total = jnp.cumsum(g, axis=-1)                                  # a chunk's decay up to each position
    ahead = jnp.arange(C)[:, None] - jnp.arange(C)[None, :]
    # exp(total_i - total_j) at j <= i (the exponent masked first: above the diagonal it may be large)
    decay = jnp.exp(jnp.where(ahead >= 0, total[..., :, None] - total[..., None, :], -jnp.inf))
    kk = _mm32("bhnid,bhnjd->bhnij", k, k)[:, :, None]              # [B, nk, 1, n, C, C]
    qk = _mm32("bhnid,bhnjd->bhnij", q, k)[:, :, None]
    solve = unit_lower_inverse(jnp.where(ahead > 0, beta[..., None] * kk * decay, 0.0))
    value = _mm32("bhrnij,bhrnjd->bhrnid", solve, v * beta[..., None])            # the corrected values
    k_decayed = _mm32("bhrnij,bhrnjd->bhrnid", solve, k[:, :, None] * (beta * jnp.exp(total))[..., None])
    within = qk * decay                                             # a chunk's own scores, the diagonal in
    q_in = q[:, :, None] * jnp.exp(total)[..., None]                # [B, nk, r, n, C, dk]
    k_out = k[:, :, None] * jnp.exp(total[..., -1:] - total)[..., None]
    last = jnp.exp(total[..., -1])                                  # [B, nk, r, n]: a chunk's total decay

    def one_chunk(s, xs):
        value_i, k_decayed_i, within_i, q_in_i, k_out_i, last_i = xs
        v_new = value_i - _mm32("bhrik,bhrkd->bhrid", k_decayed_i, s)
        o = _mm32("bhrik,bhrkd->bhrid", q_in_i, s) + _mm32("bhrij,bhrjd->bhrid", within_i, v_new)
        s = s * last_i[..., None, None] + _mm32("bhrik,bhrid->bhrkd", k_out_i, v_new)
        return s, o

    s0 = (
        jnp.zeros((B, nk, r, dk, dv), jnp.float32) if state is None
        else state.astype(jnp.float32).reshape(B, nk, r, dk, dv)
    )
    chunks = tuple(jnp.moveaxis(x, 3, 0) for x in (value, k_decayed, within, q_in, k_out, last))
    s, o = jax.lax.scan(one_chunk, s0, chunks)                      # o [n, B, nk, r, C, dv]
    o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(B, n * C, nv, dv)[:, :S]
    return o, s.reshape(B, nv, dk, dv)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _dot(a, b, contract=((1,), (0,))):
    """A float32 product at ``HIGHEST`` with float32 accumulation; operands
    of three dimensions are a chunk each along the first."""
    batch = ((0,), (0,)) if a.ndim == 3 else ((), ())
    contract = tuple((d + a.ndim - 2,) for (d,) in contract)
    return jax.lax.dot_general(a, b, (contract, batch), precision=HIGHEST, preferred_element_type=jnp.float32)


def _solve(a, row, col, C):
    """a [n, P, P], each strictly lower triangular within diagonal blocks
    of C (the heads), zero elsewhere -> ``(I + a)^-1`` (as block-diagonal):
    forward substitution.  The ``_SOLVE_BLOCK``-row diagonal blocks first,
    ALL of a matrix's at once and FOLDED to ``[b, P]`` (row i of every block
    one under the other's place: a block-diagonal matrix has one block a
    column).  With x = I, row ``j`` is done when the rows above it are, and
    then leaves every row below: ``x[i, :] -= a[i, j] x[j, :]``: one
    multiply and one subtraction of ``[b, P]`` a row, no sum.  The
    coefficients, column j of every block along its block's lanes, are
    copies: the folded blocks turned by each of 2 b - 2 lane distances
    once, every lane then taking the turn that brings its block's lane j
    (selects: the matrix unit has enough to do).  Then the doubling rule,
    of which the lower left of each pair of blocks alone is new."""
    n, P, _ = a.shape
    b = min(_SOLVE_BLOCK, C)
    same = (row // b) == (col // b)
    blocks = jnp.where(same, a, 0.0)
    folded = sum(blocks[:, i * b:(i + 1) * b] for i in range(P // b))   # [n, b, P]: a[i, j] at [i, block of j + j]
    at = jax.lax.broadcasted_iota(jnp.int32, (b, P), 1) % b             # a column's place in its block
    # column j of every block along its block's lanes: lane c of it is lane c + (j - c's place) of ``folded``
    ahead = {d: pltpu.roll(folded, -d % P, 2) for d in range(1 - b, b - 1)}     # [.., c] = folded[.., c + d]
    places = [at == place for place in range(b)]
    spread = jnp.concatenate([
        reduce(lambda y, place: jnp.where(places[place], ahead[j - place], y), range(b), 0.0)
        for j in range(b - 1)
    ], axis=1)
    x = jnp.broadcast_to(jnp.where(jax.lax.broadcasted_iota(jnp.int32, (b, P), 0) == at, 1.0, 0.0), (n, b, P))
    for j in range(b - 1):
        x = x - spread[:, j * b:(j + 1) * b] * x[:, j:j + 1]
    x = jnp.where(same, jnp.concatenate([x] * (P // b), axis=1), 0.0)
    while b < C:
        # of [[M11, 0], [M21, M22]] the lower left alone is new, -M22^-1 M21 M11^-1: only the rows of the
        # SECOND block of every pair, in both products (half the rows of a whole one)
        second = lambda m: jnp.concatenate([m[:, i * b:(i + 1) * b] for i in range(1, P // b, 2)], axis=1)  # noqa: E731
        zero = jnp.zeros((n, b, P), jnp.float32)
        back = lambda m: jnp.concatenate(  # noqa: E731 — [n, P / 2, P] -> those rows in their places, zero between
            [y for i in range(P // (2 * b)) for y in (zero, m[:, i * b:(i + 1) * b])], axis=1)
        m21 = jnp.where(((row // (2 * b)) == (col // (2 * b))) & ((row // b) != (col // b)), a, 0.0)
        x = x - back(_dot(second(x), back(_dot(second(m21), x))))
        b *= 2
    return x


def _parts(S: int):
    """(first position, chunks, positions a chunk) of a sequence of S: its
    whole chunks, then what is left as ONE shorter chunk of the least power
    of two (8 or more: a sublane tile) that holds it."""
    whole, left = divmod(S, CHUNK)
    parts = [(0, whole, CHUNK)] if whole else []
    if left:
        parts.append((whole * CHUNK, 1, max(8, 1 << (left - 1).bit_length())))
    return parts


def _prepare(q, k, v, gates, inside, r, eps):
    """What n chunks of C positions make without S, ALL of them at once (a
    chunk's chain of products is long and serial: n of them side by side
    are what the scheduler has to interleave).  q, k [n, C, dk] before their
    norm, v [n, C, r dv], gates [n, 2, r C] (the running sum of g, then
    beta: the r heads' rows of C positions one after the other), ``inside``
    [1, C, 1] the positions that lie in the sequence (None: all) -> per
    chunk, the r heads' rows one under the other (P = r C): the corrected
    values [n, P, dv] and keys [n, P, dk], q and k with the decay from the
    chunk's start and to its end [n, P, dk], the chunk's own scores [n, P,
    P], its total decay [n, P, 1]."""
    n, C, dk = q.shape
    dv = v.shape[-1] // r
    P = r * C
    row = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
    head = (row // C) == (col // C)
    eye = row == col
    if inside is not None:                                   # whatever lies past the sequence's end is not read
        q, k, v = (jnp.where(inside, x, 0.0) for x in (q, k, v))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + eps) * (dk ** -0.5)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + eps)
    t_row, beta_row = gates[:, 0:1], gates[:, 1:2]                              # [n, 1, P]

    def column(x):                                           # a row of positions [n, 1, P], turned: [n, P, 1]
        return jnp.sum(jnp.where(eye, x, 0.0), axis=2, keepdims=True)

    t, beta = column(t_row), column(beta_row)                                   # [n, P, 1]
    grown = jnp.exp(t)                                                          # the decay from the chunk's start
    # a row's head's last position of the chunk
    t_last = jnp.sum(jnp.where(col == row // C * C + C - 1, t_row, 0.0), axis=2, keepdims=True)
    # the r heads' rows one under the other: a key head's k and q serve them all
    k_all, q_all = jnp.concatenate([k] * r, axis=1), jnp.concatenate([q] * r, axis=1)
    v_all = jnp.concatenate([v[:, :, i * dv:(i + 1) * dv] for i in range(r)], axis=1)
    # exp(t_i - t_j) at j <= i of one head (the exponent masked first), zero elsewhere
    decay = jnp.exp(jnp.where(head & (row >= col), t - t_row, -jnp.inf))
    scores = _dot(jnp.concatenate([k, q], axis=1), k_all, ((1,), (1,)))         # [n, 2 C, P]: k k^T over q k^T
    kk = jnp.concatenate([scores[:, :C]] * r, axis=1)
    within = jnp.concatenate([scores[:, C:]] * r, axis=1) * decay               # a chunk's own scores, the diagonal in
    solve = _solve(jnp.where(eye, 0.0, beta * kk * decay), row, col, C)
    corrected = _dot(solve, jnp.concatenate([v_all * beta, k_all * (beta * grown)], axis=2))
    return (
        corrected[:, :, :dv], corrected[:, :, dv:], q_all * grown, k_all * jnp.exp(t_last - t), within, jnp.exp(t_last)
    )


def _kernel(*refs, S, r, eps, has_state):
    """Grid (sequences, key heads).  q, k [1, S', dk], v [1, S', r dv] (S'
    the chunks' positions: past S where the last is short), a gates [1, 1,
    n, 2, r C] a part of ``_parts(S)``; (state [1, r, dk, dv]); out: o as
    v, the final state [1, r, dk, dv]; scratch: S [r, dk, dv] float32."""
    parts = _parts(S)
    q_ref, k_ref, v_ref = refs[:3]
    o_ref, new_ref, s_ref = refs[-3:]
    dv = new_ref.shape[-1]
    chunks = []                                              # (first position, what ``_prepare`` made of the chunk)
    for (first, n, C), gates_ref in zip(parts, refs[3:]):
        q, k, v = (x[0, first:first + n * C].reshape(n, C, x.shape[-1]) for x in (q_ref, k_ref, v_ref))
        inside = jax.lax.broadcasted_iota(jnp.int32, (1, C, 1), 1) < S - first if first + n * C > S else None
        made = _prepare(q, k, v, gates_ref[0, 0], inside, r, eps)
        chunks += [(first + c * C, tuple(x[c] for x in made)) for c in range(n)]

    # the chain through S, a chunk after the other; a key head's r value heads are r chains
    s_ref[...] = refs[3 + len(parts)][0].astype(jnp.float32) if has_state else jnp.zeros_like(s_ref)
    for first, (value, k_decayed, q_in, k_out, within, last) in chunks:
        C = value.shape[0] // r
        through, v_new = [], []
        for i in range(r):
            rows = slice(i * C, (i + 1) * C)
            both = _dot(jnp.concatenate([k_decayed[rows], q_in[rows]], axis=0), s_ref[i])          # [2 C, dv]
            v_new.append(value[rows] - both[:C])
            through.append(both[C:])
        v_new = jnp.concatenate(v_new, axis=0)
        o = jnp.concatenate(through, axis=0) + _dot(within, v_new)
        for i in range(r):
            rows = slice(i * C, (i + 1) * C)
            o_ref[0, first:first + C, i * dv:(i + 1) * dv] = o[rows]
            s_ref[i] = s_ref[i] * last[i * C:i * C + 1] + _dot(k_out[rows], v_new[rows], ((0,), (0,)))
    new_ref[0] = s_ref[...].astype(new_ref.dtype)


@partial(jax.jit, static_argnames=("heads", "eps", "dtype", "interpret"))
def gdn_chunk_kernel(mixed, g, beta, state=None, *, heads, eps, dtype=jnp.float32, interpret=False):
    """The rule through the kernel (the module's docstring): ``mixed`` [B,
    S, 2 nk dk + nv dv] float32 is concat(q, k, v) as the conv and its silu
    leave them, ``heads`` (nk, nv, dk, dv), ``eps`` the l2 norms' -> (o [B,
    S, nv, dv] float32, the final state [B, nv, dk, dv] ``dtype``)."""
    nk, nv, dk, dv = heads
    B, S, _ = mixed.shape
    r = nv // nk
    parts = _parts(S)
    reach = parts[-1][0] + parts[-1][1] * parts[-1][2]      # the chunks' positions: S or, the last one short, past it
    gates = jnp.pad(jnp.stack([g, beta], axis=1), ((0, 0), (0, 0), (0, reach - S), (0, 0)))    # [B, 2, reach, nv]

    def rows(first, n, C):       # per key head and chunk, its r heads' rows of C positions: [B, nk, n, 2, r C]
        x = jnp.transpose(gates[:, :, first:first + n * C].reshape(B, 2, n, C, nk, r), (0, 4, 2, 1, 5, 3))
        # g's running sum along the positions where they lie last (along a middle axis it is a slow window sum)
        return jnp.stack([jnp.cumsum(x[:, :, :, 0], axis=-1), x[:, :, :, 1]], axis=3).reshape(B, nk, n, 2, r * C)

    def head(width, first=0):            # a key head's lanes of [B, S, ..], ``first`` blocks of them in
        return pl.BlockSpec((1, reach, width), lambda b, h: (b, 0, first + h))

    # v's blocks out of ``mixed`` itself where they lie on whole blocks of it, else out of a slice
    v, v_first = (mixed, 2 * nk * dk // (r * dv)) if 2 * nk * dk % (r * dv) == 0 else (mixed[..., 2 * nk * dk:], 0)
    whole = pl.BlockSpec((1, r, dk, dv), lambda b, h: (b, h, 0, 0))
    operands = [mixed, mixed, v] + [rows(*part) for part in parts]
    in_specs = [head(dk), head(dk, nk), head(r * dv, v_first)] + [
        pl.BlockSpec((1, 1, n, 2, r * C), lambda b, h: (b, h, 0, 0, 0)) for _, n, C in parts
    ]
    if state is not None:
        operands.append(state)
        in_specs.append(whole)
    o, new = pl.pallas_call(
        partial(_kernel, S=S, r=r, eps=eps, has_state=state is not None),
        name="gdn_chunk",
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=[head(r * dv), whole],
        out_shape=[jax.ShapeDtypeStruct((B, S, nv * dv), jnp.float32), jax.ShapeDtypeStruct((B, nv, dk, dv), dtype)],
        scratch_shapes=[pltpu.VMEM((r, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)
    return o.reshape(B, S, nv, dv), new
