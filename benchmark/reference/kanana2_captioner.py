"""Plain reference of the kanana-2 captioner
(``configs/sat-kanana2-30b-a3b.json``): straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, the FULL forward over
``[prefix; <start>; served tokens]`` in the EXPANDED form of latent
attention only (every key and value made from its token's latent), with
no cache, no prefill/step split, no absorbed products and no grouping of
experts: every expert is applied densely to every token and its output is
masked by the routing weights.  It imports nothing of the program, and is
given only what the benchmark itself made from the seed
(``params_kanana2.make_weights``, generated images).

The stack is ``model_type: "deepseek_v3"`` at the sizes of kakaocorp's
``kanana-2-30b-a3b-instruct-2601`` config.json (``q_lora_rank: null``: no
query compression; ``rope_scaling: null``: no mscale; ``n_group`` =
``topk_group`` = 1: the group-limited choice is a plain top-k).  x is the
residual stream; every norm is RMSNorm with ``norm_eps``:

    x += MLA(operator_norm(x));   x += ffn(ffn_norm(x))

* MLA, ``h = operator_norm(x)``: ``q = h W_q`` split per head into
  ``q_nope`` | ``q_rope``; ``[c_raw ; k_rope_raw] = h W_kva``;
  ``c = kv_a_layernorm(c_raw)``; ``k_rope = rope(k_rope_raw)``, one rotary
  key a token shared by all heads; ``q_rope = rope(q_rope)``; per head
  ``[k_nope ; v] = c W_kvb``;
  ``score = (q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-0.5``;
  causal softmax; ``o_proj`` over the heads' outputs.  No biases.
  ``rope_interleave: true``: the pair ``(x[2i], x[2i+1])`` turns by
  ``pos * theta^(-2i/d)``.  Here, as the source's modelling code does, the
  pairs are first brought to the halves layout (evens, then odds) and
  turned by rotate-half: the same rotation of the same pairs, its result
  in another order of the d dimensions, alike for query and key, so every
  score is the one the equation gives.
* dense ffn (the first ``num_dense_layers`` layers): ``w2(silu(w1 x) * w3 x)``;
* expert ffn: ``s = sigmoid(x W_g)``; the ``num_experts_per_tok`` largest
  of ``s + expert_bias`` (the source's ``e_score_correction_bias``) are
  chosen; weights = ``s`` at the chosen, divided by their sum + 1e-20,
  times ``routed_scaling_factor``; plus ONE shared SwiGLU of
  ``n_shared_experts x moe_intermediate_size`` that every token goes
  through;
* ``norm`` after the last kept layer, then the untied head ``lm_head``.

Departures from the source, each a line of the configuration's
``assumed``: the image enters through a connector (one linear map with
bias from the grid's D to the hidden size) as N prefix positions in raster
order, then ``<start>`` (id 0), then the caption; the weights are random;
the 43 layers the cut leaves out add nothing, here as in the program.

One layer's weights are made (from the seed) and upcast at a time.
``calibrate`` fits the connector's bias and every ``expert_bias`` on a
seeded calibration batch, as ``lfm2_captioner.calibrate`` does and for its
reasons.  ``mode``: "f32" is the reference; "fp8" (the CONTROL) rounds both
operands of every matmul to float8 e4m3 and leaves the router's product
exact.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import params_kanana2
from .lfm2_captioner import _f32, _grids, _mm, _rms, _sequence, _Static, dense_ffn, fit_expert_bias
from .model import _quant
from .params import nest


def _rope(x, theta):
    """x [n, S, heads, d] at positions 0..S-1, pairs interleaved: to the
    halves layout, then rotate-half."""
    S, d = x.shape[1], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def mla_mixer(p, x, m, mode):
    """Latent attention over whole sequences x [n, S, H] (normed), expanded."""
    n, S, _ = x.shape
    nh, rank = int(m["num_attention_heads"]), int(m["kv_lora_rank"])
    nope, rope, vd = int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"]), int(m["v_head_dim"])
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    q = _mm(x, p["q_proj"], mode).reshape(n, S, nh, nope + rope)
    raw = _mm(x, p["kv_a_proj"], mode)
    latent = _rms(raw[..., :rank], p["kv_a_layernorm"], eps)
    k_rope = _rope(raw[..., None, rank:], theta)                        # [n, S, 1, rope]
    kv = _mm(latent, p["kv_b_proj"], mode).reshape(n, S, nh, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (n, S, nh, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    scores = jnp.einsum("nshd,nthd->nhst", _quant(q, mode), _quant(k, mode)) * ((nope + rope) ** -0.5)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("nhst,nthd->nshd", _quant(probs, mode), _quant(kv[..., nope:], mode))
    return _mm(ctx.reshape(n, S, nh * vd), p["o_proj"], mode)


def route(p, x, m):
    """x [..., H] -> (chosen experts [..., k], routing weights [..., E],
    zero off the chosen).  Exact float32 whatever the control's mode."""
    k = int(m["num_experts_per_tok"])
    scores = jax.nn.sigmoid(jnp.matmul(x, p["gate"]))
    choose = scores + p["expert_bias"] if m.get("use_expert_bias", True) else scores
    _, chosen = jax.lax.top_k(choose, k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if m.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * float(m.get("routed_scaling_factor", 1.0))
    onehot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)    # [..., k, E]
    return chosen, jnp.einsum("...k,...ke->...e", picked, onehot)


def expert_ffn(p, x, m, mode):
    """Every routed expert applied to every token, masked by the routing
    weights; plus the shared expert, which every token goes through."""
    chosen, weights = route(p, x, m)
    xq = _quant(x, mode)

    def one(acc, ew):
        w1, w3, w2, we = ew                       # one expert's maps, its weight per token
        y = _mm(jax.nn.silu(jnp.matmul(xq, _quant(w1, mode))) * jnp.matmul(xq, _quant(w3, mode)),
                w2, mode)
        return acc + y * we[..., None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (p["w1"], p["w3"], p["w2"], jnp.moveaxis(weights, -1, 0)))
    if "shared" in p:
        out = out + dense_ffn(p["shared"], x, mode)
    return out, chosen


def mix(p, x, m, mode: str = "f32"):
    """The first half of a layer: x + MLA(operator_norm(x))."""
    return x + mla_mixer(p["self_attn"], _rms(x, p["operator_norm"], float(m["norm_eps"])), m, mode)


def ffn(p, x, moe: bool, m, mode: str = "f32"):
    """The second half: (x + ffn(ffn_norm(x)), chosen experts or None)."""
    h = _rms(x, p["ffn_norm"], float(m["norm_eps"]))
    if moe:
        y, chosen = expert_ffn(p["feed_forward"], h, m, mode)
        return x + y, chosen
    return x + dense_ffn(p["feed_forward"], h, mode), None


def layer(p, x, moe: bool, m, mode: str = "f32"):
    """One layer over whole sequences x [n, S, H]; returns (y, chosen
    experts [n, S, k] or None)."""
    return ffn(p, mix(p, x, m, mode), moe, m, mode)


_mix_jit = jax.jit(mix, static_argnames=("m", "mode"))
_ffn_jit = jax.jit(ffn, static_argnames=("moe", "m", "mode"))


def forward(weights_of, model: dict, contexts, tokens, mode: str = "f32"):
    """contexts [n, N, D] float32, tokens [n, T] -> (logits [n, T, V] of
    the caption positions, chosen experts [moe layers, n, N+T, k]), on
    the device.  ``weights_of(prefix)``: the leaves under
    ``params/decoder/<prefix>`` as nested dicts; called once per layer.
    May be traced (``train_loss`` differentiates it in the connector)."""
    m = model
    n, T = tokens.shape
    N = contexts.shape[1]
    with jax.default_matmul_precision("highest"):
        x, embed = _sequence(weights_of, contexts, tokens, mode)
        head = embed.T if m.get("tie_word_embeddings", False) else _f32(weights_of("lm/lm_head"))
        del embed
        routes = []
        for i in range(len(m["layer_types"])):
            moe = params_kanana2.is_moe(m, i)
            p = _f32(weights_of(f"lm/layers/{params_kanana2.layer_name(i)}"))
            x = _mix_jit(p, x, m=_Static(m), mode=mode)
            x, chosen = _ffn_jit(p, x, moe=moe, m=_Static(m), mode=mode)
            if chosen is not None:
                routes.append(chosen)
            del p
        h = _rms(x[:, N:], _f32(weights_of("lm/norm")), float(m["norm_eps"]))
        logits = jnp.einsum("nth,hv->ntv", _quant(h, mode), _quant(head, mode))
        return logits, jnp.stack(routes) if routes else jnp.zeros((0, n, N + T, 0), jnp.int32)


def _seeded(model: dict, seed: int, fitted=None):
    """``weights_of(prefix)`` over the seed's leaves, made when asked for
    (a layer at a time), with the calibration's leaves laid over them."""
    fitted = fitted or {}

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        under = lambda name: name == path or name.startswith(path + "/")  # noqa: E731
        flat = params_kanana2.make_weights(model, seed, only=under)
        flat.update({k: v for k, v in fitted.items() if under(k)})
        return flat[path] if path in flat else nest(flat, path)

    return weights_of


def served_logits(model: dict, seed: int, images_u8, tokens, mode: str = "f32", fitted=None):
    """Teacher-forced logits [n, T, V] of the captions an evaluated path
    returned, and the experts the reference chose [moe layers, n, N+T, k]."""
    cnn = params_kanana2.make_weights(model, seed, only=lambda name: name.startswith("params/cnn/"))
    ctx = _grids(model, cnn, images_u8, mode)
    logits, routes = forward(_seeded(model, seed, fitted), model, ctx, np.asarray(tokens), mode)
    return np.asarray(logits), np.asarray(routes)


def calibrate(model: dict, weights: Dict[str, np.ndarray], images_u8, tokens, block: int = 32) -> Dict[str, np.ndarray]:
    """{leaf path: value} of the connector's bias and of every expert
    layer's ``expert_bias``, fitted on the calibration batch in float32,
    layer by layer (``lfm2_captioner.calibrate``'s procedure over this
    stack's layers: a layer's bias is fitted on the scores its router gives
    the batch, and the batch goes on through the layer as routed WITH that
    bias; prefix and caption positions weigh one half each)."""
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
        fitted["params/decoder/connector/bias"] = params_kanana2._round_bf16(centre.astype(np.float32))

        def with_bias(prefix: str):
            got = weights_of(prefix)
            return {**got, "bias": fitted["params/decoder/connector/bias"]} if prefix == "connector" else got

        x, _ = _sequence(with_bias, ctx, tokens, "f32")
        xs = [x[i:i + block] for i in range(0, n, block)]
        del x
        share = np.concatenate([np.full((n, N), 0.5 / (n * N)), np.full((n, T), 0.5 / (n * T))], axis=1)
        for i in range(len(model["layer_types"])):
            name = f"lm/layers/{params_kanana2.layer_name(i)}"
            p = _f32(weights_of(name))
            moe = params_kanana2.is_moe(model, i)
            xs = [_mix_jit(p, x, m=m, mode="f32") for x in xs]
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
