"""Plain reference of the LFM2-MoE captioner
(``configs/sat-lfm2-8b-a1b.json``): straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, the FULL forward over
``[prefix; <start>; served tokens]`` with no cache, no prefill/step
split and no grouping of experts: every expert is applied densely to
every token and its output is masked by the routing weights.  It imports
nothing of the program, and is given only what the benchmark itself made
from the seed (``params_lfm2.make_weights``, generated images).

The stack follows LiquidAI's ``lfm2_moe`` (``LFM2-8B-A1B`` config.json,
and the layer equations of its public ``modeling_lfm2_moe``):

    h = x + mixer(operator_norm(x));   y = h + ffn(ffn_norm(h))

* conv mixer: ``B, C, u = split3(in_proj(x))``;
  ``out_proj(C * causal_depthwise_conv1d(B * u))``, ``conv_L_cache`` taps,
  no bias;
* attention mixer: q/k/v without bias; RMSNorm over the head on q and k;
  rotary embedding (rotate-half, ``rope_theta``, the whole head); grouped
  queries; scale ``head ** -0.5``; causal; ``out_proj``;
* dense ffn (the first ``num_dense_layers`` layers):
  ``w2(silu(w1 x) * w3 x)``;
* expert ffn: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest
  of ``s + expert_bias`` are chosen; weights = ``s`` at the chosen, divided
  by their sum + 1e-6, times ``routed_scaling_factor``;
* ``embedding_norm`` after the last kept layer, then the head.

Departures from the source, each a line of the configuration's
``assumed``: the head is tied to the embedding; the image enters through a
connector (one linear map with bias from the grid's D to the hidden size)
as N prefix positions in raster order, then ``<start>`` (id 0), then the
caption; the weights are random; the 15 layers the cut leaves out add
nothing, here as in the program.

One layer's weights are made (from the seed) and upcast at a time, so that
the float32 stack fits beside whatever else is on the device.

``calibrate`` fits, on a seeded calibration batch, the two things a
trained deployment has fitted to its traffic and random weights have not:
the connector's bias (so that the mean grid vector maps to zero: a
VGG16 grid's positions share four fifths of their power in one direction,
and with it every token of a batch would choose the same few experts) and
every expert layer's ``expert_bias``, by the source's own balancing rule
(``fit_expert_bias``).  What it returns replaces the seed's draw of those
leaves in the program's checkpoint AND in this reference (``fitted``).

``mode`` selects the arithmetic of the CONTROL, never of the reference
proper: "f32" is the reference; "fp8" rounds both operands of every
matmul and convolution to float8 e4m3 (``reference/model.py``), the
nearest precision below the configuration's bfloat16 (the router's small
product stays exact: the configuration computes it in float32).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import params_lfm2
from .model import _quant, encode
from .params import nest


def _mm(x, w, mode):
    return jnp.matmul(_quant(x, mode), _quant(w, mode))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [n, S, heads, hd] at positions 0..S-1: rotate-half, the whole head."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def conv_mixer(p, x, m, mode):
    L = int(m["conv_L_cache"])
    S = x.shape[1]
    gate_b, gate_c, u = jnp.split(_mm(x, p["in_proj"], mode), 3, axis=-1)
    bu = jnp.pad(gate_b * u, ((0, 0), (L - 1, 0), (0, 0)))
    taps = _quant(p["conv"], mode)
    conv = sum(_quant(bu[:, j:j + S], mode) * taps[j] for j in range(L))
    return _mm(gate_c * conv, p["out_proj"], mode)


def attention_mixer(p, x, m, mode):
    n, S, H = x.shape
    nh, kv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    hd = H // nh
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    q = _mm(x, p["q_proj"], mode).reshape(n, S, nh, hd)
    k = _mm(x, p["k_proj"], mode).reshape(n, S, kv, hd)
    v = _mm(x, p["v_proj"], mode).reshape(n, S, kv, hd)
    q = _rope(_rms(q, p["q_layernorm"], eps), theta)
    k = _rope(_rms(k, p["k_layernorm"], eps), theta)
    k = jnp.repeat(k, nh // kv, axis=2)           # query head i reads key head i // (nh/kv)
    v = jnp.repeat(v, nh // kv, axis=2)
    scores = jnp.einsum("nshd,nthd->nhst", _quant(q, mode), _quant(k, mode)) * (hd ** -0.5)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("nhst,nthd->nshd", _quant(probs, mode), _quant(v, mode))
    return _mm(ctx.reshape(n, S, H), p["out_proj"], mode)


def dense_ffn(p, x, mode):
    return _mm(jax.nn.silu(_mm(x, p["w1"], mode)) * _mm(x, p["w3"], mode), p["w2"], mode)


def route(p, x, m):
    """x [..., H] -> (chosen experts [..., k], routing weights [..., E],
    zero off the chosen).  Exact float32 whatever the control's mode."""
    k = int(m["num_experts_per_tok"])
    scores = jax.nn.sigmoid(jnp.matmul(x, p["gate"]))
    choose = scores + p["expert_bias"] if m.get("use_expert_bias", True) else scores
    _, chosen = jax.lax.top_k(choose, k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if m.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    picked = picked * float(m.get("routed_scaling_factor", 1.0))
    onehot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)    # [..., k, E]
    return chosen, jnp.einsum("...k,...ke->...e", picked, onehot)


def expert_ffn(p, x, m, mode):
    """Every expert applied to every token, masked by the routing weights."""
    chosen, weights = route(p, x, m)
    xq = _quant(x, mode)

    def one(acc, ew):
        w1, w3, w2, we = ew                       # one expert's maps, its weight per token
        y = _mm(jax.nn.silu(jnp.matmul(xq, _quant(w1, mode))) * jnp.matmul(xq, _quant(w3, mode)),
                w2, mode)
        return acc + y * we[..., None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (p["w1"], p["w3"], p["w2"], jnp.moveaxis(weights, -1, 0)))
    return out, chosen


def mix(p, x, kind: str, m, mode: str = "f32"):
    """The first half of a layer: x + mixer(operator_norm(x))."""
    h = _rms(x, p["operator_norm"], float(m["norm_eps"]))
    if kind == "conv":
        return x + conv_mixer(p["conv"], h, m, mode)
    return x + attention_mixer(p["self_attn"], h, m, mode)


def ffn(p, x, moe: bool, m, mode: str = "f32"):
    """The second half: (x + ffn(ffn_norm(x)), chosen experts or None)."""
    h = _rms(x, p["ffn_norm"], float(m["norm_eps"]))
    if moe:
        y, chosen = expert_ffn(p["feed_forward"], h, m, mode)
        return x + y, chosen
    return x + dense_ffn(p["feed_forward"], h, mode), None


def layer(p, x, kind: str, moe: bool, m, mode: str = "f32"):
    """One layer of the stack over whole sequences x [n, S, H]; returns
    (y, chosen experts [n, S, k] or None)."""
    return ffn(p, mix(p, x, kind, m, mode), moe, m, mode)


_mix_jit = jax.jit(mix, static_argnames=("kind", "m", "mode"))
_ffn_jit = jax.jit(ffn, static_argnames=("moe", "m", "mode"))


class _Static(dict):
    """The model block as a hashable static argument of ``layer``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _f32(tree):
    """Leaves on the device in float32 (upcast there: exact, and half the
    bytes over the wire for a bfloat16 leaf)."""
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def forward(weights_of, model: dict, contexts, tokens, mode: str = "f32"):
    """contexts [n, N, D] float32, tokens [n, T] -> (logits [n, T, V] of
    the caption positions, chosen experts [moe layers, n, N+T, k]), on
    the device.  ``weights_of(prefix)``: the leaves under
    ``params/decoder/<prefix>`` as nested dicts; called once per layer,
    so that one layer's float32 copy is on the device at a time.  May be
    traced (``train_loss`` differentiates it in the connector)."""
    m = model
    n, T = tokens.shape
    N = contexts.shape[1]
    with jax.default_matmul_precision("highest"):
        x, embed = _sequence(weights_of, contexts, tokens, mode)
        routes = []
        for i, kind in enumerate(m["layer_types"]):
            moe = params_lfm2.is_moe(m, i)
            p = _f32(weights_of(f"lm/layers/{params_lfm2.layer_name(i)}"))
            x = _mix_jit(p, x, kind=kind, m=_Static(m), mode=mode)
            x, chosen = _ffn_jit(p, x, moe=moe, m=_Static(m), mode=mode)
            if chosen is not None:
                routes.append(chosen)
            del p
        norm = _f32(weights_of("lm/embedding_norm"))
        h = _rms(x[:, N:], norm, float(m["norm_eps"]))
        logits = jnp.einsum("nth,vh->ntv", _quant(h, mode), _quant(embed, mode))
        return logits, jnp.stack(routes) if routes else jnp.zeros((0, n, N + T, 0), jnp.int32)


def _sequence(weights_of, contexts, tokens, mode: str):
    """(x [n, N+T, H]: the prefix through the connector, then ``<start>``
    and the tokens but the last, embedded; the float32 embedding)."""
    n = tokens.shape[0]
    c = _f32(weights_of("connector"))
    embed = _f32(weights_of("lm/embed_tokens"))
    words_in = jnp.concatenate([jnp.zeros((n, 1), jnp.int32), jnp.asarray(tokens[:, :-1], jnp.int32)], axis=1)
    x = jnp.concatenate([_mm(jnp.asarray(contexts), c["kernel"], mode) + c["bias"], embed[words_in]], axis=1)
    return x, embed


def _grids(model: dict, weights, images_u8, mode: str, block: int = 8) -> np.ndarray:
    """Images through the float32 VGG16 (the ``params/cnn/`` leaves of
    ``weights``), ``block`` rows at a time."""
    cnn = {k: jnp.asarray(v) for k, v in weights.items() if k.startswith("params/cnn/")}
    run = jax.jit(lambda w, im: encode(w, model["cnn"], im, "fp8" if mode == "fp8" else "f32"))
    return np.concatenate([np.asarray(run(cnn, images_u8[i:i + block]))
                           for i in range(0, images_u8.shape[0], block)], axis=0)


def _seeded(model: dict, seed: int, fitted=None):
    """``weights_of(prefix)`` over the seed's leaves, made when asked for
    (a layer at a time), with the calibration's leaves laid over them."""
    fitted = fitted or {}

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        under = lambda name: name == path or name.startswith(path + "/")  # noqa: E731
        flat = params_lfm2.make_weights(model, seed, only=under)
        flat.update({k: v for k, v in fitted.items() if under(k)})
        return flat[path] if path in flat else nest(flat, path)

    return weights_of


def served_logits(model: dict, seed: int, images_u8, tokens, mode: str = "f32", fitted=None):
    """Teacher-forced logits [n, T, V] of the captions an evaluated path
    returned, and the experts the reference chose [moe layers, n, N+T, k].
    The weights are made from the seed here, a layer at a time; ``fitted``:
    what ``calibrate`` returned for this seed."""
    cnn = params_lfm2.make_weights(model, seed, only=lambda name: name.startswith("params/cnn/"))
    ctx = _grids(model, cnn, images_u8, mode)
    logits, routes = forward(_seeded(model, seed, fitted), model, ctx, np.asarray(tokens), mode)
    return np.asarray(logits), np.asarray(routes)


def fit_expert_bias(scores: np.ndarray, share: np.ndarray, k: int, bias: np.ndarray,
                    rounds: int = 400, first: float = 0.02, last: float = 1e-4) -> np.ndarray:
    """The source's balancing rule (the bias is a buffer no gradient
    touches: after a batch, an expert that took more than the mean share
    has its bias lowered by the update rate, one that took less has it
    raised), run over ONE batch's router scores [n, E] with a rate that
    decays from ``first`` to ``last``.  ``share`` [n]: each token's weight
    in the load (sums to 1)."""
    bias = np.asarray(bias, np.float32).copy()
    E = scores.shape[1]
    per_pair = np.repeat(share, k)
    for r in range(rounds):
        rate = first * (last / first) ** (r / max(rounds - 1, 1))
        chosen = np.argpartition(-(scores + bias), k - 1, axis=1)[:, :k]
        load = np.bincount(chosen.ravel(), weights=per_pair, minlength=E)
        bias += np.float32(rate) * np.sign(load.mean() - load).astype(np.float32)
    return bias


def calibrate(model: dict, weights: Dict[str, np.ndarray], images_u8, tokens, block: int = 32) -> Dict[str, np.ndarray]:
    """{leaf path: value} of the connector's bias and of every expert
    layer's ``expert_bias``, fitted on the calibration batch (images
    [n, S, S, 3] uint8, tokens [n, T]) in float32, layer by layer: a
    layer's bias is fitted on the scores its router gives the batch, and
    the batch goes on through the layer as routed WITH that bias.  Prefix
    positions and caption positions weigh one half each in the load: a
    step routes caption tokens only, a prefill prefix positions only, and
    each has to spread over the experts.  ``weights``: every leaf of the
    seed (``params_lfm2.make_weights``).  The batch goes through ``block``
    rows at a time (the shape of the check that follows, so that the two
    share their compiled layers)."""
    m, k = _Static(model), int(model["num_experts_per_tok"])

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        return weights[path] if path in weights else nest(weights, path)

    ctx = _grids(model, weights, images_u8, "f32")
    tokens = np.asarray(tokens)
    n, T = tokens.shape
    N, D = ctx.shape[1:]
    fitted: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        kernel = np.asarray(weights_of("connector")["kernel"], np.float32)
        centre = -(ctx.reshape(-1, D).astype(np.float64).mean(axis=0) @ kernel.astype(np.float64))
        fitted["params/decoder/connector/bias"] = params_lfm2._round_bf16(centre.astype(np.float32))

        def with_bias(prefix: str):
            got = weights_of(prefix)
            return {**got, "bias": fitted["params/decoder/connector/bias"]} if prefix == "connector" else got

        x, _ = _sequence(with_bias, ctx, tokens, "f32")
        xs = [x[i:i + block] for i in range(0, n, block)]
        del x
        share = np.concatenate([np.full((n, N), 0.5 / (n * N)), np.full((n, T), 0.5 / (n * T))], axis=1)
        for i, kind in enumerate(model["layer_types"]):
            name = f"lm/layers/{params_lfm2.layer_name(i)}"
            p = _f32(weights_of(name))
            moe = params_lfm2.is_moe(model, i)
            xs = [_mix_jit(p, x, kind=kind, m=m, mode="f32") for x in xs]
            if moe:
                f = p["feed_forward"]
                scores = np.concatenate([np.asarray(jax.nn.sigmoid(jnp.matmul(
                    _rms(x, p["ffn_norm"], float(model["norm_eps"])), f["gate"]))) for x in xs])
                bias = fit_expert_bias(scores.reshape(n * (N + T), -1), share.ravel(), k,
                                       np.asarray(f["expert_bias"]))
                fitted[f"params/decoder/{name}/feed_forward/expert_bias"] = bias
                p = {**p, "feed_forward": {**f, "expert_bias": jnp.asarray(bias)}}
            xs = [_ffn_jit(p, x, moe=moe, m=m, mode="f32")[0] for x in xs]
            del p
    return fitted


def train_loss(weights: Dict[str, np.ndarray], model: dict, contexts, tokens, masks):
    """The masked token cross-entropy of the teacher-forced forward, as a
    function of the connector alone (the stack is frozen): returns
    (loss, {'kernel', 'bias'} gradient).  ``weights``: every decoder leaf
    (toy sizes: the tests)."""
    dec = nest(weights, "params/decoder")

    def loss_of(connector):
        held = {**dec, "connector": connector}

        def weights_of(prefix):
            node = held
            for part in prefix.split("/"):
                node = node[part]
            return node

        logits, _ = forward(weights_of, model, contexts, np.asarray(tokens))
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, jnp.asarray(tokens)[..., None], axis=-1)[..., 0]
        return (ce * masks).sum() / masks.sum()

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_of)(_f32(dec["connector"]))
