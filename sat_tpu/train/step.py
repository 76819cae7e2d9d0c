"""Compiled training / evaluation steps.

The reference runs one sess.run per step over a statically unrolled graph
(/root/reference/base_model.py:57-60).  Here the whole step — encoder
forward, 20-step scan decoder, backward, clip, optimizer — is ONE jitted
XLA program.  Frozen-CNN training (the reference's trainable=train_cnn
gating, utils/nn.py:66,101) is expressed by differentiating only the
trainable sub-pytree, so no gradients or optimizer slots ever exist for the
CNN unless train_cnn is on.

The same step function works single-chip and under a device mesh: data
parallelism is sharding the batch dimension (see sat_tpu/parallel), XLA
inserts the gradient all-reduce.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..config import Config
from ..models.captioner import compute_loss, init_variables
from ..models.decoders import split_frozen
from .optimizer import make_optimizer


class TrainState(NamedTuple):
    params: Dict[str, Any]
    batch_stats: Dict[str, Any]       # {} for VGG16 / frozen-BN paths
    opt_state: Any
    step: jnp.ndarray                 # global step, like the reference's tf.Variable


def split_trainable(params: Dict[str, Any], config: Config):
    """(trainable, frozen) partition — CNN params are frozen unless
    train_cnn (reference utils/nn.py:66), and a decoder freezes what its
    interface says (models/decoders.py: the language-model stack unless
    train_lm)."""
    dec_train, dec_frozen = split_frozen(params["decoder"], config)
    trainable: Dict[str, Any] = {"decoder": dec_train}
    frozen: Dict[str, Any] = {"decoder": dec_frozen} if dec_frozen else {}
    if config.train_cnn:
        trainable = {**params, **trainable}
    else:
        frozen["cnn"] = params["cnn"]
    return trainable, frozen


def merge_params(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``over``'s leaves laid over it, dict by dict: the
    inverse of split_trainable, whose two halves may share a key (a
    decoder part frozen, part trained)."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merge_params(out[key], value)
        else:
            out[key] = value
    return out


def create_train_state(rng: jax.Array, config: Config) -> TrainState:
    variables = init_variables(rng, config)
    params = variables["params"]
    trainable, _ = split_trainable(params, config)
    opt_state = make_optimizer(config).init(trainable)
    return TrainState(
        params=params,
        batch_stats=variables.get("batch_stats", {}),
        opt_state=opt_state,
        step=jnp.zeros((), jnp.int32),
    )


def make_train_step(config: Config):
    """Returns train_step(state, batch, rng) -> (state, metrics)."""
    optimizer = make_optimizer(config)

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray], rng: jax.Array):
        trainable, frozen = split_trainable(state.params, config)

        def loss_fn(trainable_params):
            params = merge_params(frozen, trainable_params)
            variables: Dict[str, Any] = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            with jax.named_scope("loss"):
                total, aux = compute_loss(variables, config, batch, rng, train=True)
            return total, aux

        grads, aux = jax.grad(loss_fn, has_aux=True)(trainable)
        with jax.named_scope("optimizer"):  # clip + Adam
            updates, new_opt_state = optimizer.update(grads, state.opt_state, trainable)
            new_trainable = optax.apply_updates(trainable, updates)

        new_params = merge_params(state.params, new_trainable)
        new_batch_stats = aux["model_state"].get("batch_stats", state.batch_stats)
        new_state = TrainState(
            params=new_params,
            batch_stats=new_batch_stats,
            opt_state=new_opt_state,
            step=state.step + 1,
        )
        metrics = dict(aux["metrics"])
        with jax.named_scope("metrics"):
            metrics["grad_norm"] = optax.global_norm(grads)
            # attention-map stats (the reference's attentions summary,
            # model.py:538-540): Σ_t α per context position, ideally ≈1
            att = aux["attentions"]
            if att is not None:
                metrics["attention/mean"] = jnp.mean(att)
                metrics["attention/std"] = jnp.std(att)
                metrics["attention/max"] = jnp.max(att)
        if config.diag_level != "off":
            # update-side diag taps (telemetry/device.py): merged into the
            # metrics pytree so they ride the existing log-sync fetch —
            # zero extra device syncs.  Statically gated: with diag off
            # this branch never traces and the step program is unchanged.
            from ..telemetry.device import grad_taps

            metrics.update(
                grad_taps(
                    config.diag_level,
                    grads=grads,
                    updates=updates,
                    params=new_trainable,
                )
            )
        return new_state, metrics

    return train_step


def make_jit_train_step(config: Config):
    return jax.jit(make_train_step(config), donate_argnums=(0,))


def make_eval_loss_step(config: Config):
    """Deterministic forward pass returning metrics (no dropout, no update)."""

    def eval_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        variables: Dict[str, Any] = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        _, aux = compute_loss(variables, config, batch, rng=None, train=False)
        return aux["metrics"]

    return jax.jit(eval_step)
