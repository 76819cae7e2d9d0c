"""ops/gdn_step.py: the DeltaNet step's state update as one Pallas kernel
that reads a row's state at its SOURCE row and writes it in place, in
interpret mode on the CPU against the ``lax`` form (gather, then the
recurrence: ``gdn_step_lax``, what every backend but the TPU runs).

The two may differ by the float32 rounding of a ``dk``-term sum and by
nothing else; a row that no source names is never read (NaN there never
arrives), whichever slot its values lie in: the in-place hazard is a slot
overwritten by another's child before its own children have read it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sat_tpu.ops import gdn_step as gs

PARENTS = {
    "identity": (0, 1, 2),
    "all_from_beam_0": (0, 0, 0),           # step 0's case
    "permutation": (2, 0, 1),
    # slots 0 and 2 from parent 1, whose own slot goes to the child of 0
    "two_from_one_whose_slot_is_taken": (1, 0, 1),
}
SHAPES = {
    "toy_2_key_6_value_heads": (2, 2, 6, 16, 8),       # images, nk, nv, dk, dv: the rehearsal's heads
    "published_16_32_of_128": (2, 16, 32, 128, 128),
}


def _inputs(B, K, nk, nv, dk, dv, dtype, parents):
    R = B * K
    ks = jax.random.split(jax.random.PRNGKey(nk), 6)
    state = (jax.random.normal(ks[0], (R, nv, dk, dv)) * 0.5).astype(dtype)
    q = jax.random.normal(ks[1], (R, nk, dk)) * dk ** -0.5
    k = jax.random.normal(ks[2], (R, nk, dk)) * dk ** -0.5
    v = jax.random.normal(ks[3], (R, nv, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (R, nv)))
    decay = jnp.exp(-jax.nn.softplus(jax.random.normal(ks[5], (R, nv))))
    source = (jnp.arange(B)[:, None] * K + jnp.asarray(parents)[None]).reshape(-1).astype(jnp.int32)
    unnamed = np.setdiff1d(np.arange(R), np.asarray(source))
    state = state.at[unnamed].set(jnp.nan)            # nobody descends from these rows
    return state, source, q, k, v, beta, decay


def _on_bfloat16_grid(x) -> float:
    x = np.asarray(x, np.float32)
    return float(np.mean(np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)) == x))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
@pytest.mark.parametrize("parents", list(PARENTS), ids=list(PARENTS))
def test_the_kernel_reads_at_the_source_row_and_writes_in_place(parents, shape, dtype):
    B, nk, nv, dk, dv = SHAPES[shape]
    K = 3
    state, source, q, k, v, beta, decay = _inputs(B, K, nk, nv, dk, dv, dtype, PARENTS[parents])
    want, want_o = gs.gdn_step_lax(state, source, q, k, v, beta, decay, dtype)
    got, got_o = gs.gdn_step_kernel(state, source, q, k, v, beta, decay, K=K, dtype=dtype, interpret=True)
    assert got.shape == (B * K, nv, dk, dv) and got.dtype == dtype and got_o.shape == (B * K, nv, dv)
    assert np.isfinite(np.asarray(got, np.float32)).all() and np.isfinite(np.asarray(got_o)).all()
    # a dk-term float32 sum in another order; a bfloat16 store may then round to the other neighbour
    scale = float(np.abs(np.asarray(want, np.float32)).max())
    ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 0.0
    assert float(np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max()) <= (dk * 2.0 ** -23 + ulp) * scale
    assert float(np.abs(np.asarray(got_o) - np.asarray(want_o)).max()) <= dk * 2.0 ** -23 * float(np.abs(want_o).max())
    # the state's type is the caller's: float32 values lie off the bfloat16 grid, a bfloat16 store on it
    on_grid = _on_bfloat16_grid(got)
    assert on_grid == 1.0 if dtype == jnp.bfloat16 else on_grid < 0.01


def test_the_caller_s_form_follows_the_backend_and_the_hook(monkeypatch):
    """``gdn_step`` off the TPU is the ``lax`` form and says so; under the
    tests' hook the kernel, interpreted; ``takes`` asks whole lane tiles
    and whole sublane tiles of k and q rows only of the TPU."""
    state, source, q, k, v, beta, decay = _inputs(2, 3, 2, 6, 16, 8, jnp.float32, (1, 0, 1))
    new, o, fused = gs.gdn_step(state, source, q, k, v, beta, decay, K=3, dtype=jnp.float32)
    assert fused is False
    monkeypatch.setattr(gs, "FORCE_INTERPRET", True)
    new_k, o_k, fused = gs.gdn_step(state, source, q, k, v, beta, decay, K=3, dtype=jnp.float32)
    assert fused is True
    np.testing.assert_allclose(np.asarray(new_k), np.asarray(new), atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o), atol=1e-5)
    monkeypatch.setattr(gs, "FORCE_INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gs.takes(384, 3, 16, 32, 128, 128) and gs._group(16, 32) == 8
    assert not gs.takes(6, 3, 2, 6, 16, 8)              # the toy's heads are no lane tiles
    assert not gs.takes(3 * 4096, 3, 16, 32, 128, 128)  # the per-head scalars would not fit SMEM
