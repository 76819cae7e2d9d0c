"""ops/gdn_chunk.py: the chunked gated delta rule of whole sequences as one
Pallas kernel a (sequence, key head), in interpret mode on the CPU against
the float64 recurrence (``tests/test_qwen3_next.py``'s ``_recurrence``, a
position at a time) and against the ``lax`` chunked form (what every
backend but the TPU runs, and ``teacher_forced`` everywhere).

The kernel reads q, k and v where the conv left them (one ``[B, S, 2 nk dk +
nv dv]`` array, q and k before their l2 norms) and norms the heads itself;
the ``lax`` form takes them normed.  The two may differ by the float32
rounding of their sums' order and by nothing else; both are held to the
recurrence at ``EXACT_TOL`` of the output's scale.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sat_tpu.ops import gdn_chunk as gc

from test_gdn_step_kernel import _on_bfloat16_grid
from test_qwen3_next import EXACT_TOL, _close, _recurrence

EPS = 1e-6
# (positions, key heads, value heads, dk, dv): the lengths and heads the ``lax`` form is held to, then the
# cell's 196 positions at the published head
CASES = {
    "under-a-chunk": (50, 2, 6, 16, 8),
    "one-chunk": (64, 2, 6, 16, 8),
    "ragged-third-chunk": (150, 2, 6, 16, 8),
    "nk-is-nv": (150, 3, 3, 16, 8),
    "one-key-head": (129, 1, 4, 16, 8),
    "published-head-196": (196, 2, 4, 128, 128),
}


def _inputs(S, nk, nv, dk, dv, seed=0, B=2):
    """(what the conv leaves [B, S, 2 nk dk + nv dv], g, beta [B, S, nv])."""
    rng = np.random.default_rng(seed)
    mixed = rng.normal(size=(B, S, 2 * nk * dk + nv * dv))
    g, beta = -rng.uniform(0, 1.5, size=(B, S, nv)), rng.uniform(0, 1, size=(B, S, nv))
    return tuple(jnp.asarray(x, jnp.float32) for x in (mixed, g, beta))


def _heads(mixed, nk, nv, dk, dv):
    """q, k [B, S, nk, dk] normed (q scaled), v [B, S, nv, dv]: ``qwen3_next._gdn_heads``' lines."""
    lead = mixed.shape[:-1]
    q = mixed[..., :nk * dk].reshape(lead + (nk, dk))
    k = mixed[..., nk * dk:2 * nk * dk].reshape(lead + (nk, dk))
    v = mixed[..., 2 * nk * dk:].reshape(lead + (nv, dv))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + EPS) * (dk ** -0.5)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + EPS)
    return q, k, v


def _kernel(mixed, g, beta, heads, state=None, dtype=jnp.float32):
    return gc.gdn_chunk_kernel(mixed, g, beta, state, heads=heads, eps=EPS, dtype=dtype, interpret=True)


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_the_kernel_is_the_recurrence_and_the_lax_form(case):
    """A ragged last chunk's block reaches past the sequence's end, where
    the interpreter puts NaN: those rows are selected to zero, so nothing of
    them arrives."""
    S, *heads = CASES[case]
    nk, nv, dk, dv = heads
    mixed, g, beta = _inputs(S, *heads, seed=S)
    o, state = _kernel(mixed, g, beta, tuple(heads))
    assert o.shape == (2, S, nv, dv) and state.shape == (2, nv, dk, dv) and state.dtype == jnp.float32
    q, k, v = _heads(mixed, *heads)
    want_o, want_state = _recurrence(q, k, v, g, beta)
    _close(o, want_o, EXACT_TOL)
    _close(state, want_state, EXACT_TOL)
    lax_o, lax_state = jax.jit(gc.gdn_chunk_lax)(q, k, v, g, beta)
    _close(o, lax_o, 1e-6)              # two float32 forms of one chunked rule: the order of their sums
    _close(state, lax_state, 1e-6)
    # S float32 inside the rule and out of it: its values lie off the bfloat16 grid
    assert _on_bfloat16_grid(state) < 0.01 and _on_bfloat16_grid(o) < 0.01


def test_the_kernel_goes_on_from_a_state_and_stores_it_as_it_is_told():
    heads = (2, 6, 16, 8)
    mixed, g, beta = _inputs(100, *heads, seed=3)
    whole_o, whole_state = _kernel(mixed, g, beta, heads)
    _, first = _kernel(mixed[:, :37], g[:, :37], beta[:, :37], heads)
    rest_o, rest = _kernel(mixed[:, 37:], g[:, 37:], beta[:, 37:], heads, state=first)
    _close(rest_o, whole_o[:, 37:], EXACT_TOL)
    _close(rest, whole_state, EXACT_TOL)
    # a bfloat16 store is the caller's (the benchmark's ``state_bf16`` control): on the grid, the output untouched
    o16, state16 = _kernel(mixed, g, beta, heads, dtype=jnp.bfloat16)
    assert state16.dtype == jnp.bfloat16 and _on_bfloat16_grid(state16) == 1.0
    assert np.array_equal(np.asarray(o16), np.asarray(whole_o))
    _close(state16, whole_state, 2.0 ** -8)


@pytest.mark.parametrize("block", [8, 16, 64])
def test_the_kernel_s_solve_is_forward_substitution_in_blocks(monkeypatch, block):
    """``_solve`` on the matrices of two heads' chunks (three chunks; each
    strictly lower triangular within a head's 64 x 64, zero between): rows of
    ``block`` by ``block`` diagonal blocks one at a time, then the doubling
    rule; at 64 the rows alone."""
    monkeypatch.setattr(gc, "_SOLVE_BLOCK", block)
    C, P = 64, 128
    rng = np.random.default_rng(block)
    a = np.zeros((3, P, P), np.float32)
    for h in range(P // C):
        a[:, h * C:(h + 1) * C, h * C:(h + 1) * C] = np.tril(rng.normal(size=(3, C, C)), -1) * 0.4
    def solve(a_ref, x_ref):        # the lane rotations are the kernel's own: inside one, interpreted
        row, col = (jax.lax.broadcasted_iota(jnp.int32, (P, P), i) for i in range(2))
        x_ref[...] = gc._solve(a_ref[...], row, col, C)

    from jax.experimental import pallas as pl

    got = pl.pallas_call(solve, out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32), interpret=True)(jnp.asarray(a))
    _close(got, np.linalg.inv(np.eye(P) + a.astype(np.float64)), EXACT_TOL)


def test_takes_asks_the_tpu_whole_lane_tiles_and_a_length_it_can_unroll(monkeypatch):
    """Off the TPU nothing is taken without the tests' hook; on it, heads of
    whole lane tiles whose value heads fill one lane tile of chunk positions
    (r = 2 at 128 x 128, the shapes Mosaic was shown), up to eight chunks."""
    assert not gc.takes(196, 16, 32, 128, 128)          # the CPU
    monkeypatch.setattr(gc, "FORCE_INTERPRET", True)
    assert gc.takes(150, 2, 6, 16, 8)
    monkeypatch.setattr(gc, "FORCE_INTERPRET", False)
    for backend in ("cpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda backend=backend: backend)
        assert not gc.takes(196, 16, 32, 128, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gc.takes(196, 16, 32, 128, 128) and gc.takes(512, 2, 4, 128, 256)
    assert not gc.takes(513, 16, 32, 128, 128)          # a ninth chunk
    assert not gc.takes(100, 2, 6, 16, 8)               # the toy's heads are no lane tiles
    assert not gc.takes(196, 16, 32, 128, 64) and not gc.takes(196, 16, 32, 192, 128)
    assert not gc.takes(196, 16, 16, 128, 128)          # one value head a key head: 64 rows, half a lane tile
    assert not gc.takes(196, 16, 64, 128, 128)          # four: 256
