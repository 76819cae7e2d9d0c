"""Plain reference of the command-a-plus captioner
(``configs/sat-command-a-plus.json``): straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, the FULL forward over
``[prefix; <start>; served tokens]`` with no cache, no prefill/step split,
no kernel, no kept tail, no grouping of experts, keys and values repeated
over their group, the four shared experts as four.  It imports nothing of
the program and is given only what the benchmark itself made from the seed
(``params_cohere2.make_weights``, generated images).

The stack follows CohereLabs' ``cohere2_moe`` config.json
(``command-a-plus-05-2026``): the public Cohere2 PARALLEL block with the
feed-forward a mixture.  ``H`` = 4,096, ``nh`` = 128, ``nkv`` = 8, ``d`` =
128, ``group`` = 16; a layer of kind k in {sliding_attention,
full_attention}:

    u        = (x - mean(x)) / sqrt(var(x) + 1e-5) * w_ln          over H, no bias: the layer's ONE norm
    q = u W_q  [nh, d] ;  k = u W_k  [nkv, d] ;  v = u W_v  [nkv, d]       no bias, no q/k norm
    sliding :  q, k <- rope(q), rope(k)   interleaved pairs (x_2i, x_2i+1), theta 50,000, all 128 dims,
               position = the index in the one causal sequence (grid in raster order, <start>, caption)
    full    :  nothing: no positional term
    s[t, j]  = q[t, h] . k[j, h // 16] * 128^-0.5
    seen     : full  j <= t ;   sliding  t - 4096 < j <= t        (a comparison of positions)
    a[t, h]  = sum_seen softmax(s[t, .])[j] v[j, h // 16]
    p        = sigmoid(u W_r) in R^128 ;  r = top-8(p) ;  w_e = p_e / sum_{e in r} p_e      no bias, no factor
    routed   = sum_{e in r, e HELD} w_e W2_e (silu(u W1_e) * (u W3_e))
    shared   = 1/4 sum_{s < 4} W2_s (silu(u W1_s) * (u W3_s))
    x       <- x + concat_h(a) W_o + routed + shared               attention and feed-forward both read u
    logits   = LayerNorm_f(x_last) E^T * logit_scale               E the tied embedding, its held rows

Departures from the source, each a line of the configuration's ``assumed``:
"average" and the router's plain reading; the window counts the query; the
full layers carry no positional term; the vision tower is not run: the
image enters through a connector as N prefix positions, then ``<start>``
(id 0), then the caption; the weights are random, the router's map with its
columns' components along the calibration batch's mean inputs and the
leading directions of their spread taken out (``calibrate``); the 28 layers and 112 experts the cut leaves out add
nothing, here as in the program.

It runs in blocks only so that it fits: ``block`` captions at a time
through a layer whose float32 weights are on the device one layer at a
time, a caption at a time inside the attention, ``_QUERY_BLOCK`` queries at
a time inside a caption.  ``mode``: "f32" is the reference; "fp8" (the
CONTROL) rounds both operands of every matmul to float8 e4m3 and leaves the
router's product exact.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import params_cohere2
from .glm52_captioner import _inputs
from .kanana2_captioner import _rope
from .lfm2_captioner import _f32, _grids, _mm, _Static, dense_ffn
from .model import _quant
from .params import nest

_QUERY_BLOCK = 256


def _ln(x, w, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def attend_one(p, u, kind: str, m, mode):
    """One layer's attention over ONE sequence u [S, H] (normed): its
    output [S, H]."""
    S = u.shape[0]
    nh, kv, d = int(m["num_attention_heads"]), int(m["num_key_value_heads"]), params_cohere2.head_dim(m)
    q = _mm(u, p["q_proj"], mode).reshape(1, S, nh, d)
    k = _mm(u, p["k_proj"], mode).reshape(1, S, kv, d)
    v = _mm(u, p["v_proj"], mode).reshape(S, kv, d)
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = ahead >= 0
    if kind == "sliding_attention":
        q, k = _rope(q, float(m["rope_theta"])), _rope(k, float(m["rope_theta"]))
        seen = seen & (ahead < int(m["sliding_window_size"]))
    # every query head beside its own copy of its group's key/value head
    kq = _quant(jnp.repeat(k[0], nh // kv, axis=1), mode)
    vq = _quant(jnp.repeat(v, nh // kv, axis=1), mode)
    ctx = []
    for a in range(0, S, _QUERY_BLOCK):
        scores = jnp.einsum("shd,thd->hst", _quant(q[0, a:a + _QUERY_BLOCK], mode), kq) * (d ** -0.5)
        probs = jax.nn.softmax(jnp.where(seen[None, a:a + _QUERY_BLOCK], scores, -jnp.inf), axis=-1)
        ctx.append(jnp.einsum("hst,thd->shd", _quant(probs, mode), vq))
    return _mm(jnp.concatenate(ctx, axis=0).reshape(S, nh * d), p["o_proj"], mode)


def route(p, u, m):
    """u [..., H] -> (chosen experts [..., k], routing weights [..., E],
    zero off the chosen): top-k of the sigmoid scores, the chosen scores
    over their sum.  Exact float32 whatever the control's mode."""
    scores = jax.nn.sigmoid(jnp.matmul(u, p["gate"]))
    picked, chosen = jax.lax.top_k(scores, int(m["num_experts_per_tok"]))
    picked = picked / picked.sum(axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)    # [..., k, E]
    return chosen, jnp.einsum("...k,...ke->...e", picked, onehot)


def expert_ffn(p, u, m, mode):
    """The experts held here applied to every token, masked by the routing
    weights over ALL experts; plus the MEAN of the shared experts, each
    applied apart."""
    chosen, weights = route(p, u, m)
    first, held = int(m.get("first_expert", 0)), params_cohere2.held_experts(m)
    uq = _quant(u, mode)

    def one(acc, ew):
        w1, w3, w2, we = ew                       # one expert's maps, its weight per token
        y = _mm(jax.nn.silu(jnp.matmul(uq, _quant(w1, mode))) * jnp.matmul(uq, _quant(w3, mode)), w2, mode)
        return acc + y * we[..., None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (p["w1"], p["w3"], p["w2"], jnp.moveaxis(weights[..., first:first + held], -1, 0)))
    n, I = int(m["n_shared_experts"]), int(m["moe_intermediate_size"])
    for s in range(n):
        cols = slice(s * I, (s + 1) * I)
        one_shared = {"w1": p["shared"]["w1"][:, cols], "w3": p["shared"]["w3"][:, cols],
                      "w2": p["shared"]["w2"][cols]}
        out = out + dense_ffn(one_shared, u, mode) / n
    return out, chosen


def layer(p, x, kind: str, m, mode: str = "f32"):
    """One PARALLEL block over sequences x [n, S, H], a sequence at a time
    inside the attention: (x + attention(u) + experts(u), chosen experts
    [n, S, k]), u the block's one norm of x."""
    u = _ln(x, p["input_norm"], float(m["norm_eps"]))
    a = jax.lax.map(lambda one: attend_one(p["self_attn"], one, kind, m, mode), u)
    y, chosen = expert_ffn(p["feed_forward"], u, m, mode)
    return x + a + y, chosen


_layer_jit = jax.jit(layer, static_argnames=("kind", "m", "mode"))


def _through_the_stack(weights_of, model: dict, xs, mode: str, fit=None):
    """xs: blocks [b, S, H] (host float32) through every layer, a layer's
    float32 weights on the device at a time.  ``fit(name, p, blocks)``: the
    calibration's hook before a layer runs; it returns the layer with its
    fitted router in, and the pass ends at the last layer's fit.  Returns (the blocks after the last layer, chosen
    experts a layer [n, S, k]), on the host."""
    m = _Static(model)
    routes = []
    for i, kind in enumerate(model["layer_types"]):
        name = f"lm/layers/{params_cohere2.layer_name(i)}"
        p = _f32(weights_of(name))
        if fit is not None:
            p = fit(name, p, xs)
            if i == len(model["layer_types"]) - 1:
                break                   # the calibration reads no output of the last layer
        out = [_layer_jit(p, jnp.asarray(x), kind=kind, m=m, mode=mode) for x in xs]
        xs = [np.asarray(x) for x, _ in out]
        routes.append(np.concatenate([np.asarray(c) for _, c in out]))
        del p, out
    return xs, routes


def forward(weights_of, model: dict, contexts, tokens, mode: str = "f32", block: int = 4):
    """contexts [n, N, D] float32, tokens [n, T] -> (logits [n, T, V] of
    the caption positions, chosen experts [layers, n, N+T, k]), on the
    host.  ``weights_of(prefix)``: the leaves under
    ``params/decoder/<prefix>`` as nested dicts; called once per layer."""
    m = model
    N = contexts.shape[1]
    with jax.default_matmul_precision("highest"):
        xs = _inputs(weights_of, contexts, tokens, mode, block)
        xs, routes = _through_the_stack(weights_of, m, xs, mode)
        head = _f32(weights_of("lm/embed_tokens")).T
        norm = _f32(weights_of("lm/norm"))
        logits = np.concatenate([np.asarray(jnp.einsum(
            "nth,hv->ntv", _quant(_ln(jnp.asarray(x[:, N:]), norm, float(m["norm_eps"])), mode),
            _quant(head, mode))) for x in xs]) * float(m.get("logit_scale", 1.0))
    return logits, np.stack(routes)


def _seeded(model: dict, seed: int, fitted=None):
    """``weights_of(prefix)`` over the seed's leaves, made when asked for
    (a layer at a time), with the calibration's leaves laid over them."""
    fitted = fitted or {}

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        under = lambda name: name == path or name.startswith(path + "/")  # noqa: E731
        flat = params_cohere2.make_weights(model, seed, only=under)
        flat.update({k: v for k, v in fitted.items() if under(k)})
        return flat[path] if path in flat else nest(flat, path)

    return weights_of


def served_logits(model: dict, seed: int, images_u8, tokens, mode: str = "f32", fitted=None, block: int = 4):
    """Teacher-forced logits [n, T, V] of the captions an evaluated path
    returned and the experts the reference chose [layers, n, N+T, k]."""
    cnn = params_cohere2.make_weights(model, seed, only=lambda name: name.startswith("params/cnn/"))
    ctx = _grids(model, cnn, images_u8, mode, block=1)
    return forward(_seeded(model, seed, fitted), model, ctx, np.asarray(tokens), mode, block=block)


def balanced_gate(gate, directions):
    """gate [H, E] with each column's component in the span of
    ``directions`` (vectors [H]) taken out, bfloat16-exact: every expert's
    logit at each of them is then 0 (to the rounding), so no expert is
    preferred by what all tokens of a kind share."""
    g, basis = np.asarray(gate, np.float64), []
    for d in directions:
        d = np.asarray(d, np.float64)
        for b in basis:
            d = d - (d @ b) * b
        if np.linalg.norm(d) > 1e-12:
            basis.append(d / np.linalg.norm(d))
    for b in basis:
        g = g - np.outer(b, b @ g)
    return params_cohere2._round_bf16(g.astype(np.float32))


_SPREAD_DIRECTIONS = 8      # leading principal directions of the prefix positions' spread the router loses
_SPREAD_SAMPLE = 16         # every so-many-th prefix position enters the spread's decomposition


def calibrate(model: dict, weights: Dict[str, np.ndarray], images_u8, tokens, block: int = 4) -> Dict[str, np.ndarray]:
    """{leaf path: value} of the connector's bias and of every layer's
    router map, fitted on the calibration batch in float32, layer by layer.
    The source has no selection bias, so none is added: random weights give
    the tokens' normed inputs a common component and a spread that lies
    mostly along a few directions (a quarter of it along ONE), and along
    those a random router prefers a few experts for most tokens (fullest
    expert 3-10x the mean, PERF.md section 6).  Each column of ``W_r``
    loses its component along the batch's mean ``u`` of the prefix
    positions, along that of the caption positions, and along the
    ``_SPREAD_DIRECTIONS`` leading principal directions of the prefix
    positions' spread round their mean: 10 of 4,096 directions the router
    no longer sees.  The batch goes on through the layer as routed by the
    fitted map (nothing reads the last layer's own output).  The
    connector's bias centres the prefix, as ``glm52_captioner.calibrate``'s."""

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        return weights[path] if path in weights else nest(weights, path)

    t0 = time.perf_counter()
    ctx = _grids(model, weights, images_u8, "f32", block=1)
    tokens = np.asarray(tokens)
    N, D = ctx.shape[1:]
    fitted: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        kernel = np.asarray(weights_of("connector")["kernel"], np.float32)
        centre = -(ctx.reshape(-1, D).astype(np.float64).mean(axis=0) @ kernel.astype(np.float64))
        fitted["params/decoder/connector/bias"] = params_cohere2._round_bf16(centre.astype(np.float32))

        def with_bias(prefix: str):
            got = weights_of(prefix)
            return {**got, "bias": fitted["params/decoder/connector/bias"]} if prefix == "connector" else got

        xs = _inputs(with_bias, ctx, tokens, "f32", block)

        def fit(name, p, blocks):
            f = p["feed_forward"]
            u = np.concatenate([np.asarray(_ln(jnp.asarray(b), p["input_norm"], float(model["norm_eps"])))
                                for b in blocks])                                   # [n, N + T, H]
            prefix, caption = u[:, :N].reshape(-1, u.shape[-1]), u[:, N:].reshape(-1, u.shape[-1])
            mean = prefix.mean(axis=0, dtype=np.float64)
            spread = np.linalg.svd(prefix[::_SPREAD_SAMPLE].astype(np.float64) - mean, full_matrices=False)[2]
            gate = balanced_gate(np.asarray(f["gate"]),
                                 [mean, caption.mean(axis=0, dtype=np.float64), *spread[:_SPREAD_DIRECTIONS]])
            fitted[f"params/decoder/{name}/feed_forward/gate"] = gate.astype(params_cohere2.BF16)
            return {**p, "feed_forward": {**f, "gate": jnp.asarray(gate)}}

        _through_the_stack(weights_of, model, xs, "f32", fit=fit)
    print(f"benchmark: calibration {time.perf_counter() - t0:.1f} s over {tokens.shape[0]} sequences", flush=True)
    return fitted

