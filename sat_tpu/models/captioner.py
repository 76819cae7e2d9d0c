"""The full caption model: CNN encoder + attention-LSTM decoder + losses.

Equivalent of the reference CaptionGenerator (/root/reference/model.py:6-13)
plus its loss graph (model.py:293-334), reorganized functionally:

* ``init_variables`` builds the parameter pytree {'cnn': ..., 'decoder': ...}
  (+ 'batch_stats' for ResNet50's BN);
* ``encode`` maps images → context grid, with stop_gradient when the CNN is
  frozen (the reference freezes via trainable=False, utils/nn.py:66);
* ``compute_loss`` reproduces the three-part objective: masked
  cross-entropy normalized by total mask, the doubly-stochastic attention
  penalty 0.01 * l2(1-Σα_masked)/(B·N), and L2 weight regularization —
  plus teacher-forced token accuracy.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from ..nn.layers import regularization_loss
from . import decoders
from .resnet50 import ResNet50
from .vgg16 import VGG16


def make_encoder(config: Config):
    dtype = jnp.dtype(config.compute_dtype)
    if config.cnn == "vgg16":
        return VGG16(dtype=dtype)
    if config.cnn == "resnet50":
        return ResNet50(dtype=dtype)
    raise ValueError(f"unknown cnn {config.cnn!r} (vgg16 or resnet50)")


def init_variables(rng: jax.Array, config: Config) -> Dict[str, Any]:
    """Initialize all model variables with dummy image input."""
    k_cnn, k_dec = jax.random.split(rng)
    encoder = make_encoder(config)
    dummy = jnp.zeros((1, config.image_size, config.image_size, 3), jnp.float32)
    cnn_vars = encoder.init(k_cnn, dummy, train=False)
    out = {
        "params": {
            "cnn": cnn_vars["params"],
            "decoder": decoders.init_params(k_dec, config),
        }
    }
    if "batch_stats" in cnn_vars:
        out["batch_stats"] = cnn_vars["batch_stats"]
    return out


@jax.named_scope("encoder")
def encode(
    variables: Dict[str, Any],
    config: Config,
    images: jnp.ndarray,
    train: bool = False,
    collect_activity: bool = False,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """images [B,224,224,3] → contexts [B,N,D].  Returns (contexts, new_model_state).

    train here means *the CNN is training* (train_cnn): enables BN batch
    statistics and gradient flow; otherwise contexts are stop-gradiented so
    the frozen CNN never enters the backward pass.

    collect_activity=True (static) additionally sums the 'activity'
    collection the Conv layers sow (Σ|relu output| per activated conv —
    VGG16 only; ResNet convs pass activation=None like the reference,
    utils/nn.py:55-57) into new_state['activity_l1']."""
    if images.dtype == jnp.uint8:
        # device-side preprocessing tail (ImageLoader raw=True feed): the
        # host already decoded/BGR→RGB/resized in uint8; the final
        # astype(float32) − ILSVRC mean runs here instead — bitwise equal
        # to the host path (reference utils/misc.py:22-27 order), 4× less
        # host→device traffic
        from ..data.images import ILSVRC_2012_MEAN

        images = images.astype(jnp.float32) - jnp.asarray(ILSVRC_2012_MEAN)
    if config.encoder_quant != "off" and "qcnn" in variables:
        # serve-path quantized encoder (nn/quant.py): the engine swaps the
        # fp32 cnn params for the 'qcnn' collection at load time, so this
        # branch is structurally unreachable from training (train variables
        # never carry qcnn) and config.encoder_quant="off" stays bitwise
        # the flax path below
        from ..nn import quant

        contexts = quant.quantized_encode(variables, config, images)
        return jax.lax.stop_gradient(contexts), {}
    encoder = make_encoder(config)
    cnn_vars: Dict[str, Any] = {"params": variables["params"]["cnn"]}
    if "batch_stats" in variables:
        cnn_vars["batch_stats"] = variables["batch_stats"]

    new_state: Dict[str, Any] = {}
    mutable = []
    if train and "batch_stats" in cnn_vars:
        mutable.append("batch_stats")
    if collect_activity:
        mutable.append("activity")
    if mutable:
        bn = "batch_stats" in mutable
        apply_mut = lambda v, im: encoder.apply(  # noqa: E731
            v, im, train=bn, mutable=list(mutable)
        )
        if train and config.remat_cnn:
            apply_mut = jax.checkpoint(apply_mut)
        contexts, mutated = apply_mut(cnn_vars, images)
        if bn:
            new_state["batch_stats"] = mutated["batch_stats"]
        if collect_activity:
            new_state["activity_l1"] = jax.tree_util.tree_reduce(
                lambda a, b: a + b, mutated.get("activity", {}), jnp.float32(0)
            )
    else:
        apply_fn = lambda v, im: encoder.apply(v, im, train=False)  # noqa: E731
        if train and config.remat_cnn:
            # full encoder remat: backward recomputes the CNN forward from
            # the images instead of storing every conv activation — the
            # memory lever that buys joint-training batch size (the conv1/2
            # stacks at 224^2 dominate live activation footprint)
            apply_fn = jax.checkpoint(apply_fn)
        contexts = apply_fn(cnn_vars, images)
    if not train:
        contexts = jax.lax.stop_gradient(contexts)
    return contexts, new_state


def token_ce(
    logits: jnp.ndarray,
    sentences: jnp.ndarray,
    config: Config,
    train: bool = True,
) -> jnp.ndarray:
    """Per-token cross-entropy [B, T] — the ONE implementation shared by
    the single-device loss and the context-parallel twin
    (parallel/context.py), so config.ce_dtype behaves identically on
    every path.

    ce_dtype="bfloat16" (train only): ce = logsumexp - target_logit
    computed WITHOUT materializing a [B,T,V] fp32 log-softmax —
    max/shift/exp stay in the logits' bf16 (halving that tensor's HBM
    traffic) and only the V-axis normalizer sum accumulates in fp32,
    where the precision actually matters.  Eval/metrics keep the exact
    fp32 path."""
    if config.ce_dtype == "bfloat16" and train:
        m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        s = jnp.sum(
            jnp.exp(logits - m), axis=-1, dtype=jnp.float32
        )  # [B,T] fp32 accumulation of bf16 exps
        lse = m[..., 0].astype(jnp.float32) + jnp.log(s)
        tgt = jnp.take_along_axis(logits, sentences[..., None], axis=-1)
        return lse - tgt[..., 0].astype(jnp.float32)           # [B,T]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, sentences[..., None], axis=-1)[..., 0]


def compute_loss(
    variables: Dict[str, Any],
    config: Config,
    batch: Dict[str, jnp.ndarray],
    rng: Optional[jax.Array] = None,
    train: bool = True,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Forward pass + the reference's total loss (model.py:293-334).

    batch: images [B,224,224,3] (or precomputed 'contexts' [B,N,D]),
    word_idxs [B,T] int32, masks [B,T] float32.
    Returns (total_loss, aux) with aux carrying metrics, alphas, and any
    mutated model state (BN stats).
    """
    if train and rng is None:
        raise ValueError("compute_loss(train=True) requires an rng for dropout")
    # L1 activity regularization gates (reference utils/nn.py:23-26,40-43):
    # fc activity when training, conv activity only when the CNN trains.
    fc_act_scale = config.fc_activity_regularizer_scale if train else 0.0
    train_cnn = train and config.train_cnn
    conv_act_scale = config.conv_activity_regularizer_scale if train_cnn else 0.0
    if "contexts" in batch:
        contexts, new_state = batch["contexts"], {}
    else:
        contexts, new_state = encode(
            variables, config, batch["images"], train_cnn,
            collect_activity=conv_act_scale > 0,
        )
    conv_activity = new_state.pop("activity_l1", jnp.float32(0))

    sentences = batch["word_idxs"]
    masks = batch["masks"].astype(jnp.float32)
    B, T = sentences.shape
    N = contexts.shape[1]

    logits, alphas, fc_activity = decoders.train_logits(
        variables["params"]["decoder"], config, contexts, sentences, train, rng,
        with_activity=fc_act_scale > 0,
    )  # [B,T,V], [B,T,N] (+ activity L1)
    if fc_activity is None:
        fc_activity = jnp.float32(0)

    # masked sparse softmax cross-entropy, summed / mask-sum (model.py:316-318)
    ce = token_ce(logits, sentences, config, train)            # [B,T]
    mask_sum = masks.sum()
    cross_entropy_loss = (ce * masks).sum() / mask_sum
    if alphas is None:
        # a decoder with no attention over the grid (lfm2_moe): the loss
        # is the masked token cross-entropy alone — no doubly stochastic
        # penalty exists for it, and its frozen stack is not regularised
        predictions = jnp.argmax(logits, axis=-1)
        zero = jnp.float32(0)
        return cross_entropy_loss, {
            "metrics": {
                "cross_entropy_loss": cross_entropy_loss,
                "attention_loss": zero,
                "reg_loss": zero,
                "total_loss": cross_entropy_loss,
                "accuracy": ((predictions == sentences) * masks).sum() / mask_sum,
            },
            "attentions": None,
            "model_state": new_state,
        }

    # doubly stochastic attention penalty (model.py:320-326):
    # alphas masked per-step, summed over time; penalize departure from 1
    masked_alphas = alphas * masks[..., None]          # [B,T,N]
    attentions = masked_alphas.sum(axis=1)             # [B,N]
    diffs = 1.0 - attentions
    attention_loss = (
        config.attention_loss_factor * 0.5 * jnp.sum(diffs * diffs) / (B * N)
    )

    reg_loss = regularization_loss(
        variables["params"],
        fc_scale=config.fc_kernel_regularizer_scale if train else 0.0,
        conv_scale=config.conv_kernel_regularizer_scale,
        train_cnn=train_cnn,
    )
    # activity terms join the same reg bucket the reference sums via
    # tf.losses.get_regularization_loss() (model.py:328)
    reg_loss = reg_loss + fc_act_scale * fc_activity + conv_act_scale * conv_activity

    total_loss = cross_entropy_loss + attention_loss + reg_loss

    predictions = jnp.argmax(logits, axis=-1)
    accuracy = ((predictions == sentences) * masks).sum() / mask_sum

    aux = {
        "metrics": {
            "cross_entropy_loss": cross_entropy_loss,
            "attention_loss": attention_loss,
            "reg_loss": reg_loss,
            "total_loss": total_loss,
            "accuracy": accuracy,
        },
        "attentions": attentions,
        "model_state": new_state,
    }
    if train and config.diag_level != "off":
        # forward-side diag taps (docs/OBSERVABILITY.md): computed here
        # where alphas/logits are live so nothing bulky rides through aux;
        # gated statically on diag_level, so the off-path XLA program is
        # bit-for-bit the pre-diagnostics program
        from ..telemetry.device import loss_taps

        aux["metrics"].update(
            loss_taps(config.diag_level, alphas=alphas, masks=masks, logits=logits)
        )
    return total_loss, aux
