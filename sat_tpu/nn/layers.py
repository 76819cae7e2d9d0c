"""NN primitives with the reference's initialization/regularization semantics.

Equivalent of the reference's NN wrapper class (/root/reference/utils/nn.py):

* conv kernels: Xavier/Glorot-uniform init (utils/nn.py:15);
* fc kernels + embeddings: uniform(-0.08, 0.08) init (utils/nn.py:29-31);
* L2 kernel regularization is *not* baked into layers here — JAX losses are
  functional, so `regularization_loss` below walks the param pytree and
  reproduces the reference's accounting (utils/nn.py:17-43): fc kernels
  always regularized in training, conv kernels only when the CNN is
  trainable, biases and LSTM internals never.
* batch norm: TF1 defaults momentum=0.99 eps=1e-3, batch statistics only
  when the CNN trains (utils/nn.py:116-125).

All matmul/conv compute runs in ``compute_dtype`` (bfloat16 on TPU → MXU),
params stay ``param_dtype`` (fp32 master copies).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

Dtype = Any

conv_kernel_init = nn.initializers.glorot_uniform()


def fc_kernel_init(scale: float = 0.08) -> Callable:
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, minval=-scale, maxval=scale)

    return init


class Conv(nn.Module):
    """'same'-padded conv2d, optional relu (reference utils/nn.py:45-70)."""

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    activation: Optional[str] = "relu"
    use_bias: bool = True
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(
            features=self.features,
            kernel_size=self.kernel_size,
            strides=self.strides,
            padding="SAME",
            use_bias=self.use_bias,
            kernel_init=conv_kernel_init,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="conv",
        )(x)
        if self.activation == "relu":
            x = nn.relu(x)
            # L1 activity hook (reference utils/nn.py:23-26,55-57: the
            # activity regularizer attaches only to *activated* convs —
            # ResNet convs pass activation=None and never collect).  sow
            # is a no-op (and the sum DCE'd) unless the caller requests
            # the 'activity' collection as mutable.
            self.sow(
                "activity", "l1", jnp.abs(x.astype(jnp.float32)).sum(),
                reduce_fn=lambda a, b: a + b, init_fn=lambda: jnp.float32(0),
            )
        return x


def max_pool2d(x, pool_size=(2, 2), strides=(2, 2)):
    """'same'-padded max pool (reference utils/nn.py:72-83)."""
    return nn.max_pool(x, window_shape=pool_size, strides=strides, padding="SAME")


# the name a dropout mask carries for ``jax.checkpoint`` policies
DROPOUT_MASK = "dropout_mask"


def dropout(x, rate: float, deterministic: bool, rng=None):
    """Inverted dropout matching tf.layers.dropout semantics.

    The mask is named (an identity outside a ``jax.checkpoint``), so that
    a rematerialised region can keep it with ``save_only_these_names``
    and rebuild the rest without drawing the bits again."""
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = checkpoint_name(jax.random.bernoulli(rng, keep, x.shape), DROPOUT_MASK)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


# ---------------------------------------------------------------------------
# Regularization accounting (functional replacement for TF's collection of
# per-layer regularizers, reference utils/nn.py:17-43 + model.py:328).
# ---------------------------------------------------------------------------


def regularization_loss(
    params,
    fc_scale: float,
    conv_scale: float,
    train_cnn: bool,
    exclude_substrings: Sequence[str] = ("lstm",),
) -> jnp.ndarray:
    """0.5 * scale * sum(w**2) per kernel — TF's l2_regularizer semantics.

    Rank-4 kernels are conv kernels (counted only when the CNN trains, since
    frozen-CNN runs exclude them from the loss in the reference); rank-2
    'kernel'/'embedding' leaves are fc kernels.  LSTM internals are excluded
    (the reference's LSTMCell has an initializer but no regularizer,
    model.py:228-230).
    """
    total = jnp.asarray(0.0, jnp.float32)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        leaf_name = str(names[-1]) if names else ""
        full = "/".join(str(n) for n in names).lower()
        if any(s in full for s in exclude_substrings):
            continue
        # 'weights' catches the embedding table (reference regularizes it,
        # model.py:219-225); biases and BN scales/offsets never count.
        if leaf_name not in ("kernel", "embedding", "weights"):
            continue
        w = leaf.astype(jnp.float32)
        if w.ndim == 4:
            if train_cnn and conv_scale > 0:
                total = total + 0.5 * conv_scale * jnp.sum(w * w)
        elif w.ndim >= 2:
            if fc_scale > 0:
                total = total + 0.5 * fc_scale * jnp.sum(w * w)
    return total
