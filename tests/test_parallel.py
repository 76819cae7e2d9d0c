"""Distributed-layer tests on the 8-virtual-device CPU mesh (conftest.py).

Strategy per SURVEY.md §4: emulate a TPU slice with
xla_force_host_platform_device_count and check that (a) sharded programs
compile+run with the intended layouts, and (b) parallel results match the
single-device oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from sat_tpu.config import Config
from sat_tpu.parallel import (
    create_parallel_train_state,
    make_mesh,
    make_parallel_beam_search,
    make_parallel_train_step,
    shard_batch,
)
from sat_tpu.parallel.collectives import cross_replica_mean, make_global_batch
from sat_tpu.parallel.sharding import param_partition_specs
from sat_tpu.train.step import create_train_state, make_jit_train_step


def tiny_config(**kw):
    base = dict(
        cnn="vgg16",
        vocabulary_size=64,
        dim_embedding=16,
        num_lstm_units=16,
        dim_initialize_layer=16,
        dim_attend_layer=16,
        dim_decode_layer=32,
        max_caption_length=4,
        batch_size=8,
        compute_dtype="float32",
    )
    base.update(kw)
    return Config(**base)


def context_batch(config, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "contexts": jnp.asarray(
            rng.normal(size=(batch, config.num_ctx, config.dim_ctx)).astype(np.float32)
        ),
        "word_idxs": jnp.asarray(
            rng.integers(0, config.vocabulary_size, size=(batch, config.max_caption_length)).astype(np.int32)
        ),
        "masks": jnp.ones((batch, config.max_caption_length), jnp.float32),
    }


def test_initialize_distributed_single_host_is_noop(monkeypatch):
    """A lone TPU_WORKER_HOSTNAMES entry or a 1-task SLURM allocation is a
    single-process launch: bootstrapping a coordinator there crashes with
    'coordinator_address should be defined' (regression: single-chip
    TPU hosts export TPU_WORKER_HOSTNAMES=localhost)."""
    from sat_tpu.parallel import initialize_distributed

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert initialize_distributed() is False

    monkeypatch.setenv("SLURM_STEP_NODELIST", "node001")
    monkeypatch.setenv("SLURM_NTASKS", "1")
    assert initialize_distributed() is False

    # but a real pod signal still wires up (>1 hostnames)
    from sat_tpu.parallel import mesh as mesh_mod

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host0,host1")
    assert mesh_mod._multihost_env_signal() is True


def test_make_mesh_shapes():
    config = tiny_config(mesh_shape=(4, 2))
    mesh = make_mesh(config)
    assert mesh.shape == {"data": 4, "model": 2}
    # 0 = "all remaining devices"
    mesh = make_mesh(tiny_config(mesh_shape=(0, 2)))
    assert mesh.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(tiny_config(mesh_shape=(16, 2)))


def test_param_partition_specs_vocab_rule():
    config = tiny_config(mesh_shape=(4, 2))
    mesh = make_mesh(config)
    state = create_train_state(jax.random.PRNGKey(0), config)
    specs = param_partition_specs(state.params, config, mesh)
    dec = specs["decoder"]
    assert dec["word_embedding"]["weights"] == P("model", None)
    assert dec["decode"]["fc_2"]["kernel"] == P(None, "model")
    assert dec["decode"]["fc_2"]["bias"] == P("model")
    assert dec["lstm"]["kernel"] == P()


def test_parallel_train_step_matches_single_device():
    config = tiny_config(mesh_shape=(8, 1))
    mesh = make_mesh(config)
    batch = context_batch(config)
    rng = jax.random.PRNGKey(7)
    drop = jax.random.PRNGKey(11)

    # oracle: plain single-device jit
    state0 = create_train_state(rng, config)
    _, m_single = make_jit_train_step(config)(state0, batch, drop)

    pstate = create_parallel_train_state(rng, config, mesh)
    pstep = make_parallel_train_step(config, mesh)
    pstate, m_par = pstep(pstate, shard_batch(batch, mesh), drop)

    for k in m_single:
        np.testing.assert_allclose(
            np.asarray(m_single[k]), np.asarray(m_par[k]), rtol=2e-4, atol=2e-5,
            err_msg=k,
        )
    # a second step runs (donation + resharding are stable)
    pstate, _ = pstep(pstate, shard_batch(context_batch(config, seed=1), mesh), drop)
    assert int(pstate.step) == 2


def test_parallel_train_step_model_sharded():
    """DP×TP mesh: vocab-sharded embedding/softmax still matches the oracle."""
    config = tiny_config(mesh_shape=(4, 2))
    mesh = make_mesh(config)
    batch = context_batch(config)
    rng = jax.random.PRNGKey(3)
    drop = jax.random.PRNGKey(5)

    state0 = create_train_state(rng, config)
    _, m_single = make_jit_train_step(config)(state0, batch, drop)

    pstate = create_parallel_train_state(rng, config, mesh)
    emb = pstate.params["decoder"]["word_embedding"]["weights"]
    assert emb.sharding.spec == P("model", None)

    pstep = make_parallel_train_step(config, mesh)
    _, m_par = pstep(pstate, shard_batch(batch, mesh), drop)
    np.testing.assert_allclose(
        float(m_single["total_loss"]), float(m_par["total_loss"]), rtol=2e-4
    )


def test_parallel_beam_search_matches_single_device():
    config = tiny_config(mesh_shape=(8, 1), beam_size=3)
    mesh = make_mesh(config)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(8, 224, 224, 3)).astype(np.float32))

    from sat_tpu.models.captioner import encode, init_variables
    from sat_tpu.ops.beam_search import beam_search

    variables = init_variables(jax.random.PRNGKey(0), config)
    contexts, _ = encode(variables, config, images, train=False)
    oracle = beam_search(variables["params"]["decoder"], config, contexts, eos_id=1)

    pcaption = make_parallel_beam_search(config, mesh, eos_id=1)
    result = pcaption(variables, jax.device_put(images, None))
    np.testing.assert_array_equal(np.asarray(oracle.words), np.asarray(result.words))
    np.testing.assert_allclose(
        np.asarray(oracle.log_scores), np.asarray(result.log_scores), rtol=1e-4
    )


def test_cross_replica_mean_and_global_batch():
    config = tiny_config(mesh_shape=(8, 1))
    mesh = make_mesh(config)
    # one value per data-mesh row -> their mean, replicated
    out = cross_replica_mean({"x": jnp.arange(8.0)}, mesh)
    np.testing.assert_allclose(float(out["x"]), 3.5)
    out2 = cross_replica_mean({"m": jnp.ones((8, 2, 3))}, mesh)
    assert out2["m"].shape == (2, 3)
    np.testing.assert_allclose(np.asarray(out2["m"]), 1.0)

    batch = {"a": np.arange(16, dtype=np.float32).reshape(16, 1)}
    g = make_global_batch(mesh, batch)
    assert g["a"].sharding.spec == P("data")
    np.testing.assert_array_equal(np.asarray(g["a"]), batch["a"])
