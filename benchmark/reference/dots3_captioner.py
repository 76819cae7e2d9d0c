"""Plain reference of the dots3-note-prev captioner
(``configs/sat-dots3-note-prev.json``): straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, the FULL forward over
``[prefix; <start>; served tokens]`` with no cache, no prefill/step split,
no absorbed form, no kept tail, no gather and no grouping of experts.  It
imports nothing of the program and is given only what the benchmark itself
made from the seed (``params_dots3.make_weights``, generated images).

The stack follows dots-studio's ``dots3_note`` config.json.  For a layer of
kind k in {full_attention, sliding_attention} with its own ``(nh, r_q,
r_kv, d_n, d_r, d_v, theta)`` = full ``(128, 1024, 512, 128, 64, 128,
8e7)``, sliding (the ``swa_*`` keys) ``(64, 1024, 1024, 192, 64, 128,
5e4)``; ``H`` = 5,120; ``u = RMSNorm(x)``:

    qr      = RMSNorm(u W_qa) * a_q            a_q  = sqrt(H / r_q)   (apply_mla_qkv_lora_rescale; else 1)
    q       = qr W_qb  -> per head [q_n (d_n) ; q_r (d_r)],  q_r turned (interleaved pairs, theta_k)
    [c' ; k_r'] = u W_kva ;  c = RMSNorm(c') * a_kv ,  a_kv = sqrt(H / r_kv) ;  k_r = rope(k_r')   one a token
    [k_n ; v]   = c W_kvb     per head
    s[t, j] = (q_n[t,h] . k_n[j,h] + q_r[t,h] . k_r[j]) * (d_n + d_r)^-0.5
    seen    : full    j in S_t, the min(2048, t + 1) positions j <= t of largest I[t, j]   (the indexer of
                      glm52_captioner.py, 64 heads x 128, from qr and u; EVERY full layer has its own)
              sliding t - 513 < j <= t          (a comparison of positions)
    o[t,h]  = sum_seen softmax(s[t, .])[j] v[j,h]
    g[t]    = sigmoid(u[t] W_g)   in R^nh ;   o[t,h] <- g[t,h] * o[t,h]          (headwise gate)
    x      <- x + concat_h(o) W_o
    x      <- x + FFN(RMSNorm(x)):  layer 0 SwiGLU 13,824;  after it the sigmoid router over 256 (+ selection
              bias, top-8, weights / (sum + 1e-20) x 1.0) over the experts HELD + one shared SwiGLU of 1,536

The indexer reads ``qr`` after ``a_q`` (a positive scale of ``qr``
multiplies a row of ``I`` by one constant: the selection is the same either
way); its rope turns by the full layer's theta.  Departures from the
source, each a line of the configuration's ``assumed``: the gate's input
and place, the rescale as LongCat's keys define it, the window's count, the
indexer's details as ``sat-glm-5.2`` lists them; MTP and the vision and
audio towers are not run; the image enters through a connector as N prefix
positions, then ``<start>`` (id 0), then the caption; the weights are
random; the 41 layers and 224 experts the cut leaves out add nothing, here
as in the program.

It runs in blocks so that it fits, as ``glm52_captioner``: ``block``
captions at a time through a layer whose float32 weights are on the device
one layer at a time, a caption at a time inside the attention,
``_QUERY_BLOCK`` queries at a time inside a caption.  ``calibrate`` is
``glm52_captioner.calibrate``'s procedure (PR 26's) over this stack.
``mode``: "f32" is the reference; "fp8" (the CONTROL) rounds both operands
of every matmul to float8 e4m3 and leaves the router's product exact.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import params_dots3
from .glm52_captioner import _inputs, ffn, fit_expert_bias, index_scores, select
from .kanana2_captioner import _rope
from .lfm2_captioner import _f32, _grids, _mm, _rms, _Static
from .model import _quant
from .params import nest

_QUERY_BLOCK = 512


def attend_one(p, x, kind: str, m, mode):
    """One layer's attention over ONE sequence x [S, H] (normed),
    expanded.  Returns (output [S, H], the positions each query attended
    [S, S] bool)."""
    S, H = x.shape
    w = params_dots3.kind_widths(m, kind)
    nh, rank, nope, rope, vd, theta = w["heads"], w["kv_rank"], w["nope"], w["rope"], w["v"], w["theta"]
    eps = float(m["norm_eps"])
    rescale = bool(m.get("mla_lora_rescale", False))
    a_q = (H / w["q_rank"]) ** 0.5 if rescale else 1.0
    a_kv = (H / rank) ** 0.5 if rescale else 1.0
    qr = _rms(_mm(x, p["q_a_proj"], mode), p["q_a_layernorm"], eps) * a_q
    q = _mm(qr, p["q_b_proj"], mode).reshape(1, S, nh, nope + rope)
    raw = _mm(x, p["kv_a_proj"], mode)
    latent = _rms(raw[..., :rank], p["kv_a_layernorm"], eps) * a_kv
    k_rope = _rope(raw[None, :, None, rank:], theta)                     # [1, S, 1, rope]
    kv = _mm(latent, p["kv_b_proj"], mode).reshape(S, nh, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[0], (S, nh, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)[0]
    if kind == "full_attention":
        seen = select(index_scores(p["indexer"], x, qr, m, mode), int(m["index_topk"]))
    else:
        ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        seen = (ahead >= 0) & (ahead < int(m["sliding_window_size"]))
    kq, vq = _quant(k, mode), _quant(kv[..., nope:], mode)
    ctx = []
    for a in range(0, S, _QUERY_BLOCK):
        scores = jnp.einsum("shd,thd->hst", _quant(q[a:a + _QUERY_BLOCK], mode), kq) * ((nope + rope) ** -0.5)
        probs = jax.nn.softmax(jnp.where(seen[None, a:a + _QUERY_BLOCK], scores, -jnp.inf), axis=-1)
        ctx.append(jnp.einsum("hst,thd->shd", _quant(probs, mode), vq))
    ctx = jnp.concatenate(ctx, axis=0)                                   # [S, nh, vd]
    if "gate_proj" in p:
        ctx = ctx * jax.nn.sigmoid(_mm(x, p["gate_proj"], mode))[..., None]
    return _mm(ctx.reshape(S, nh * vd), p["o_proj"], mode), seen


def mix(p, x, kind: str, m, mode: str = "f32"):
    """The first half of a layer over sequences x [n, S, H], a sequence at
    a time: (x + attention(operator_norm(x)), what each query attended
    [n, S, S])."""
    h = _rms(x, p["operator_norm"], float(m["norm_eps"]))
    y, seen = jax.lax.map(lambda one: attend_one(p["self_attn"], one, kind, m, mode), h)
    return x + y, seen


_mix_jit = jax.jit(mix, static_argnames=("kind", "m", "mode"))
_ffn_jit = jax.jit(ffn, static_argnames=("moe", "m", "mode"))


def _through_the_stack(weights_of, model: dict, xs, mode: str, fit=None):
    """xs: blocks [b, S, H] (host float32) through every layer, a layer's
    float32 weights on the device at a time.  ``fit(name, p, blocks)``: the
    calibration's hook before an expert layer's ffn; it returns the layer
    with its fitted bias in.  Returns (the blocks after the last layer,
    chosen experts a moe layer [n, S, k], the selections of each full layer
    [n, S, S] bool), on the host."""
    m = _Static(model)
    routes, selections = [], []
    for i, kind in enumerate(model["layer_types"]):
        name = f"lm/layers/{params_dots3.layer_name(i)}"
        p = _f32(weights_of(name))
        moe = params_dots3.is_moe(model, i)
        mixed = [_mix_jit(p, jnp.asarray(x), kind=kind, m=m, mode=mode) for x in xs]
        xs = [np.asarray(x) for x, _ in mixed]
        if kind == "full_attention":
            selections.append(np.concatenate([np.asarray(seen) for _, seen in mixed]))
        del mixed
        if moe and fit is not None:
            p = fit(name, p, xs)
        out = [_ffn_jit(p, jnp.asarray(x), moe=moe, m=m, mode=mode) for x in xs]
        xs = [np.asarray(x) for x, _ in out]
        if moe:
            routes.append(np.concatenate([np.asarray(c) for _, c in out]))
        del p, out
    return xs, routes, selections


def forward(weights_of, model: dict, contexts, tokens, mode: str = "f32", block: int = 4):
    """contexts [n, N, D] float32, tokens [n, T] -> (logits [n, T, V] of
    the caption positions, chosen experts [moe layers, n, N+T, k], S_t of
    the caption positions [full layers, n, T, N+T] bool), on the host.
    ``weights_of(prefix)``: the leaves under ``params/decoder/<prefix>`` as
    nested dicts; called once per layer."""
    m = model
    n, T = tokens.shape
    N = contexts.shape[1]
    with jax.default_matmul_precision("highest"):
        xs = _inputs(weights_of, contexts, tokens, mode, block)
        xs, routes, selections = _through_the_stack(weights_of, m, xs, mode)
        head = _f32(weights_of("lm/embed_tokens")).T if m.get("tie_word_embeddings", False) \
            else _f32(weights_of("lm/lm_head"))
        norm = _f32(weights_of("lm/norm"))
        logits = np.concatenate([np.asarray(jnp.einsum(
            "nth,hv->ntv", _quant(_rms(jnp.asarray(x[:, N:]), norm, float(m["norm_eps"])), mode),
            _quant(head, mode))) for x in xs])
    routes = np.stack(routes) if routes else np.zeros((0, n, N + T, 0), np.int32)
    return logits, routes, np.stack([s[:, N:] for s in selections])


def _seeded(model: dict, seed: int, fitted=None):
    """``weights_of(prefix)`` over the seed's leaves, made when asked for
    (a layer at a time), with the calibration's leaves laid over them."""
    fitted = fitted or {}

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        under = lambda name: name == path or name.startswith(path + "/")  # noqa: E731
        flat = params_dots3.make_weights(model, seed, only=under)
        flat.update({k: v for k, v in fitted.items() if under(k)})
        return flat[path] if path in flat else nest(flat, path)

    return weights_of


def served_logits(model: dict, seed: int, images_u8, tokens, mode: str = "f32", fitted=None, block: int = 4):
    """Teacher-forced logits [n, T, V] of the captions an evaluated path
    returned, the experts the reference chose [moe layers, n, N+T, k] and
    the positions its full layers attended at the caption's steps
    [full layers, n, T, N+T] bool."""
    cnn = params_dots3.make_weights(model, seed, only=lambda name: name.startswith("params/cnn/"))
    ctx = _grids(model, cnn, images_u8, mode, block=2)
    return forward(_seeded(model, seed, fitted), model, ctx, np.asarray(tokens), mode, block=block)


def calibrate(model: dict, weights: Dict[str, np.ndarray], images_u8, tokens, block: int = 4) -> Dict[str, np.ndarray]:
    """{leaf path: value} of the connector's bias and of every expert
    layer's ``expert_bias`` (all ``num_experts`` outputs), fitted on the
    calibration batch in float32, layer by layer:
    ``glm52_captioner.calibrate``'s procedure over this stack's layers."""
    k = int(model["num_experts_per_tok"])

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        return weights[path] if path in weights else nest(weights, path)

    t0 = time.perf_counter()
    ctx = _grids(model, weights, images_u8, "f32", block=2)
    tokens = np.asarray(tokens)
    n, T = tokens.shape
    N, D = ctx.shape[1:]
    fitted: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        kernel = np.asarray(weights_of("connector")["kernel"], np.float32)
        centre = -(ctx.reshape(-1, D).astype(np.float64).mean(axis=0) @ kernel.astype(np.float64))
        fitted["params/decoder/connector/bias"] = params_dots3._round_bf16(centre.astype(np.float32))

        def with_bias(prefix: str):
            got = weights_of(prefix)
            return {**got, "bias": fitted["params/decoder/connector/bias"]} if prefix == "connector" else got

        xs = _inputs(with_bias, ctx, tokens, "f32", block)
        share = np.concatenate([np.full((n, N), 0.5 / (n * N)), np.full((n, T), 0.5 / (n * T))], axis=1)

        def fit(name, p, blocks):
            f = p["feed_forward"]
            scores = np.concatenate([np.asarray(jax.nn.sigmoid(jnp.matmul(
                _rms(jnp.asarray(b), p["ffn_norm"], float(model["norm_eps"])), f["gate"]))) for b in blocks])
            bias = fit_expert_bias(scores.reshape(n * (N + T), -1), share.ravel(), k,
                                   np.asarray(f["expert_bias"]))
            fitted[f"params/decoder/{name}/feed_forward/expert_bias"] = bias
            return {**p, "feed_forward": {**f, "expert_bias": jnp.asarray(bias)}}

        _through_the_stack(weights_of, model, xs, "f32", fit=fit)
    print(f"benchmark: calibration {time.perf_counter() - t0:.1f} s over {n} sequences", flush=True)
    return fitted
