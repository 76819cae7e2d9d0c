"""Plain reference of Show, Attend and Tell (Xu et al. 2015, soft attention)
as the source repository configures it: straight ``jax.numpy``, float32,
every matmul and convolution at ``Precision.HIGHEST``, no kernel, no cache,
no beam bookkeeping.  It imports nothing of the program and is given only
what the benchmark itself made from the seed (``params.make_weights``,
generated images and captions).

Departures from the paper, all of them the source repository's
(Cheng-Lin-Li/show-attend-and-tell ``model.py``), kept because the
configuration under test is that repository's:
  * the LSTM is TF1's ``LSTMCell`` (gates i, j, f, o; forget bias +1) fed
    concat(attention context, word embedding);
  * attention is a two-layer tanh MLP over (context, previous output);
  * logits come from a two-layer MLP over concat(output, context, embedding);
  * training adds 0.01 * the doubly stochastic penalty and L2 on the fully
    connected kernels (not the LSTM's), clips the global gradient norm at 5
    and uses Adam(1e-4, 0.9, 0.999, eps 1e-6);
  * dropout: 0.5 on every fully connected input, 0.3 on LSTM input, output
    and recurrent state, with the key derivation of the program copied
    (``jax.random`` is JAX, not the program) so that the masks are the same.

``mode`` selects the arithmetic of the CONTROL, never of the reference
proper: "f32" is the reference; "fp8" rounds both operands of every matmul
and convolution to float8 e4m3 (3 mantissa bits, per-tensor scale to the
format's 448 maximum), the nearest precision below the configurations'
bfloat16; "fp8enc" does so in the encoder alone and leaves the decoder in
float32 (what a quantised frozen encoder would be: the program's own
``encoder_quant=int8`` is the other such control); "bf16" rounds them to
bfloat16 (used by tests only).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .params import RESNET_STAGES, VGG_LAYERS, nest

HIGHEST = lax.Precision.HIGHEST
ILSVRC_MEAN = np.array([104.00698793, 116.66876762, 122.67891434], np.float32)


def _quant(x, mode: str):
    if mode == "f32":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode != "fp8":
        raise ValueError(mode)
    # float8 e4m3 by hand, so that it is the same on every backend: scale
    # the tensor's largest magnitude to 448, keep 3 mantissa bits (round
    # half to even), subnormals below 2**-6 in steps of 2**-9.  Straight-
    # through in the backward pass.
    scale = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    y = x / scale
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = jnp.exp2(e - 3.0)
    rounded = jnp.round(y / step) * step * scale
    return x + lax.stop_gradient(rounded - x)


def split_mode(mode: str):
    """(encoder's arithmetic, decoder's arithmetic)."""
    return ("fp8", "f32") if mode == "fp8enc" else (mode, mode)


def _mm(x, w, mode):
    return jnp.matmul(_quant(x, mode), _quant(w, mode), precision=HIGHEST)


def _dense(p, x, mode, tanh=False):
    y = _mm(x, p["kernel"], mode)
    if "bias" in p:
        y = y + p["bias"]
    return jnp.tanh(y) if tanh else y


def _conv(x, kernel, stride, mode):
    return lax.conv_general_dilated(
        _quant(x, mode), _quant(kernel, mode), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _max_pool(x, k, s):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1), (1, s, s, 1), "SAME")


def _bn(p, stats, x):
    return (x - stats["mean"]) * lax.rsqrt(stats["var"] + 1e-3) * p["scale"] + p["bias"]


def encode(weights: Dict[str, jax.Array], cnn: str, images_u8, mode: str = "f32"):
    """uint8 RGB images [B,S,S,3] -> context grid [B,N,D] float32."""
    p = nest(weights, "params/cnn")
    x = images_u8.astype(jnp.float32) - jnp.asarray(ILSVRC_MEAN)
    if cnn == "vgg16":
        for name, _cout, pool in VGG_LAYERS:
            c = p[name]["conv"]
            x = jax.nn.relu(_conv(x, c["kernel"], 1, mode) + c["bias"])
            if pool:
                x = _max_pool(x, 2, 2)
        return x.reshape(x.shape[0], -1, 512)
    s = nest(weights, "batch_stats")
    c = p["conv1"]["conv"]
    x = _conv(x, c["kernel"], 2, mode) + c["bias"]
    x = jax.nn.relu(_bn(p["bn_conv1"], s["bn_conv1"], x))
    x = _max_pool(x, 3, 2)
    for stage, _width, n_identity, stride in RESNET_STAGES:
        for i in range(n_identity + 1):
            st = f"{stage}{chr(ord('a') + i)}"
            bp, bs = p[f"res{st}"], s[f"res{st}"]

            def unit(br, inp, strd, bp=bp, bs=bs, st=st):
                y = _conv(inp, bp[f"res{st}_branch{br}"]["conv"]["kernel"], strd, mode)
                return _bn(bp[f"bn{st}_branch{br}"], bs[f"bn{st}_branch{br}"], y)

            first = stride if i == 0 else 1
            shortcut = unit("1", x, first) if i == 0 else x
            y = jax.nn.relu(unit("2a", x, first))
            y = jax.nn.relu(unit("2b", y, 1))
            x = jax.nn.relu(shortcut + unit("2c", y, 1))
    return x.reshape(x.shape[0], -1, 2048)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------


def _dropout(key, x, rate):
    if key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def _split(key, n):
    return (None,) * n if key is None else tuple(jax.random.split(key, n))


def _init_state(d, contexts, key, hp, mode):
    k0, k1, k2 = _split(key, 3)
    mean = _dropout(k0, contexts.mean(axis=1), hp["fc_drop_rate"])
    ta = _dense(d["initialize"]["fc_a1"], mean, mode, tanh=True)
    tb = _dense(d["initialize"]["fc_b1"], mean, mode, tanh=True)
    ta = _dropout(k1, ta, hp["fc_drop_rate"])
    tb = _dropout(k2, tb, hp["fc_drop_rate"])
    memory = _dense(d["initialize"]["fc_a2"], ta, mode)
    output = _dense(d["initialize"]["fc_b2"], tb, mode)
    return memory, output, output      # cell, emitted h, recurrent h


def _step(d, contexts, state, word, key, hp, mode):
    memory, output, recurrent = state
    k_att, k_in, k_out, k_state, k_dec = _split(key, 5)
    kc, ko, kt = _split(k_att, 3)
    fc, lstm = hp["fc_drop_rate"], hp["lstm_drop_rate"]
    # attention (dropout falls on attend's own copies; the weighted sum
    # below uses the undropped grid, as the source does)
    t1 = _dense(d["attend"]["fc_1a"], _dropout(kc, contexts, fc), mode, tanh=True)
    t2 = _dense(d["attend"]["fc_1b"], _dropout(ko, output, fc), mode, tanh=True)
    temp = _dropout(kt, t1 + t2[:, None, :], fc)
    alpha = jax.nn.softmax(_dense(d["attend"]["fc_2"], temp, mode)[..., 0], axis=-1)
    context = (contexts * alpha[..., None]).sum(axis=1)
    embed = d["word_embedding"]["weights"][word]
    x = _dropout(k_in, jnp.concatenate([context, embed], axis=-1), lstm)
    z = _mm(jnp.concatenate([x, recurrent], axis=-1), d["lstm"]["kernel"], mode)
    i, j, f, o = jnp.split(z + d["lstm"]["bias"], 4, axis=-1)
    new_c = jax.nn.sigmoid(f + 1.0) * memory + jax.nn.sigmoid(i) * jnp.tanh(j)
    new_h = jax.nn.sigmoid(o) * jnp.tanh(new_c)
    emitted = _dropout(k_out, new_h, lstm)
    rec = _dropout(k_state, new_h, lstm)
    k0, k1 = _split(k_dec, 2)
    expanded = _dropout(k0, jnp.concatenate([emitted, context, embed], axis=-1), fc)
    hidden = _dropout(k1, _dense(d["decode"]["fc_1"], expanded, mode, tanh=True), fc)
    logits = _dense(d["decode"]["fc_2"], hidden, mode)
    return (new_c, emitted, rec), logits, alpha


def teacher_forced(decoder, contexts, tokens, key, hp, mode: str = "f32"):
    """logits [B,T,V], alphas [B,T,N] with the input word at step t being
    tokens[:, t-1] (<start> = 0 at t = 0).  ``key`` None: no dropout."""
    B, T = tokens.shape
    k_init, k_steps = _split(key, 2)
    state = _init_state(decoder, contexts, k_init, hp, mode)
    words_in = jnp.concatenate([jnp.zeros((B, 1), tokens.dtype), tokens[:, :-1]], axis=1)

    @jax.checkpoint
    def body(state, xs):
        word, k = xs if key is not None else (xs, None)
        state, logits, alpha = _step(decoder, contexts, state, word, k, hp, mode)
        return state, (logits, alpha)

    xs = words_in.T if key is None else (words_in.T, jax.random.split(k_steps, T))
    _, (logits, alphas) = lax.scan(body, state, xs)
    return logits.transpose(1, 0, 2), alphas.transpose(1, 0, 2)


def served_logits(weights, model: dict, images_u8, tokens, mode: str = "f32", block: int = 8):
    """Teacher-forced logits [n,T,V] of the captions a served or evaluated
    path returned, inference arithmetic (no dropout).  Images go through
    the encoder ``block`` rows at a time so that float32 VGG16 fits beside
    whatever else is on the device."""
    decoder = nest(weights, "params/decoder")
    hp = {"fc_drop_rate": 0.0, "lstm_drop_rate": 0.0}
    enc_mode, dec_mode = split_mode(mode)

    @jax.jit
    def run(w, d, imgs, toks):
        ctx = encode(w, model["cnn"], imgs, enc_mode)
        return teacher_forced(d, ctx, toks, None, hp, dec_mode)[0]

    out = []
    for i in range(0, images_u8.shape[0], block):
        out.append(np.asarray(run(weights, decoder, images_u8[i:i + block], tokens[i:i + block])))
    return np.concatenate(out, axis=0)


# ---------------------------------------------------------------------------
# training: loss, gradient, clipped Adam
# ---------------------------------------------------------------------------


def train_loss(decoder, contexts, tokens, masks, key, hp, mode: str = "f32"):
    logits, alphas = teacher_forced(decoder, contexts, tokens, key, hp, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    B, N = contexts.shape[0], contexts.shape[1]
    cross_entropy = (ce * masks).sum() / masks.sum()
    attentions = (alphas * masks[..., None]).sum(axis=1)
    attention = hp["attention_loss_factor"] * 0.5 * jnp.sum((1.0 - attentions) ** 2) / (B * N)
    reg = 0.0
    for group, layers in decoder.items():
        if group == "lstm":
            continue
        for leaf in jax.tree_util.tree_leaves(layers):
            if leaf.ndim >= 2:
                reg = reg + 0.5 * hp["fc_kernel_regularizer_scale"] * jnp.sum(leaf * leaf)
    return cross_entropy + attention + reg


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(tree)))


def train_steps(weights, model: dict, hp: dict, batches, seed: int, mode: str = "f32",
                block: int = 16):
    """Follow the trainer through ``len(batches)`` steps from the seeded
    weights with the CNN frozen.  Each batch is (images_u8, tokens, masks).
    Returns per-step losses, the first gradient as the optimizer gets it
    (after the global-norm clip), and the parameters' change after the
    last step — the last two as {leaf path: array}."""
    decoder = nest(weights, "params/decoder")
    enc_mode, dec_mode = split_mode(mode)
    enc = jax.jit(lambda w, im: encode(w, model["cnn"], im, enc_mode))
    grad_fn = jax.jit(jax.value_and_grad(
        lambda d, ctx, tok, m, k: train_loss(d, ctx, tok, m, k, hp, dec_mode)))
    root = jax.random.key(seed + 1, impl=hp["rng_impl"])
    b1, b2, eps, lr, clip = hp["beta1"], hp["beta2"], hp["epsilon"], hp["learning_rate"], hp["clip_gradients"]

    @jax.jit
    def adam(d, mu, nu, g, t):
        norm = _global_norm(g)
        g = jax.tree_util.tree_map(lambda x: jnp.where(norm < clip, x, x / norm * clip), g)
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        d = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
            d, mu, nu)
        return d, mu, nu, g

    start = decoder
    mu = jax.tree_util.tree_map(jnp.zeros_like, decoder)
    nu = jax.tree_util.tree_map(jnp.zeros_like, decoder)
    losses, first_grad = [], None
    for step, (images, tokens, masks) in enumerate(batches):
        ctx = jnp.concatenate(
            [enc(weights, images[i:i + block]) for i in range(0, images.shape[0], block)], axis=0)
        key = jax.random.fold_in(root, step)
        loss, g = grad_fn(decoder, ctx, jnp.asarray(tokens), jnp.asarray(masks, jnp.float32), key)
        decoder, mu, nu, clipped = adam(decoder, mu, nu, g, jnp.float32(step + 1))
        losses.append(float(loss))
        if step == 0:
            first_grad = clipped
    delta = jax.tree_util.tree_map(lambda a, b: a - b, decoder, start)
    return losses, flatten(first_grad, "params/decoder"), flatten(delta, "params/decoder")


def flatten(tree, prefix: str) -> Dict[str, np.ndarray]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[prefix + "/" + "/".join(str(p.key) for p in path)] = np.asarray(leaf)
    return out
