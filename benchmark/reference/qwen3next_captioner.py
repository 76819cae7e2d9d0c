"""Plain reference of the Qwen3-Next captioner
(``configs/sat-qwen3-next-80b-a3b.json``): straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, the FULL forward over
``[prefix; <start>; served tokens]`` with no cache, no prefill/step split,
no chunking (the Gated DeltaNet recurrence is a ``lax.scan`` over
positions, a token at a time), no kernel, no grouping of experts, keys and
values repeated over their group.  It imports nothing of the program and
is given only what the benchmark itself made from the seed
(``params_qwen3next.make_weights``, generated images).

The stack follows Qwen's ``qwen3_next`` config.json
(``Qwen3-Next-80B-A3B-Instruct``) and the public modelling code of that
``model_type`` (its Gated DeltaNet layer is the public
flash-linear-attention one).  ``H`` = 2,048; a layer l is
``full_attention`` where ``(l + 1) % 4 == 0``, else ``linear_attention``:

    norm(x; w)  = x / sqrt(mean(x^2) + 1e-6) * (1 + w)            every RMSNorm but the gated one
    layer l     : x <- x + mixer_l(norm(x; w1)) ;  x <- x + moe(norm(x; w2))

    Gated DeltaNet (nk = 16 key heads, nv = 32 value heads, dk = dv = 128; u the normed input):
    u W_qkvz [12288] per key head [q 128 | k 128 | v 2 x 128 | z 2 x 128] ;  u W_ba [64] per key head [b 2 | a 2]
    c           = silu(causal depthwise conv over time, 4 taps, no bias, of concat(q [2048], k [2048], v [4096]))
    q, k, v     = split(c) ;  a key head h serves value heads 2h and 2h + 1
    q           = q / sqrt(sum(q^2) + 1e-6) * 128^-0.5 ;  k = k / sqrt(sum(k^2) + 1e-6)       per head
    beta        = sigmoid(b) ;  g = -exp(A_log) * softplus(a + dt_bias)                       per value head
    per value head, S in R^[dk, dv], S = 0 before position 0:
        S      <- exp(g_t) S
        d_t     = beta_t (v_t - S^T k_t)
        S      <- S + k_t d_t^T
        o_t     = S^T q_t
    y_t         = w_n * o_t / sqrt(mean(o_t^2) + 1e-6) * silu(z_t)      per value head over dv; w_n plain
    mixer       = concat_heads(y_t) W_out                               [4096] -> H

    gated full attention (nh = 16, nkv = 2, d = 256, group 8):
    u W_q [16 x 512] per head [query 256 | gate 256] ;  k = u W_k [2, 256] ;  v = u W_v [2, 256] ;  no bias
    q = norm(q; w_q), k = norm(k; w_k) over d ;  rope (rotate-half, theta 1e7) on the FIRST 64 of the 256
    a[t, h]     = sum_{j <= t} softmax_j(q[t, h] . k[j, h // 8] * 256^-0.5) v[j, h // 8]
    mixer       = (concat_h(a) * sigmoid(gate)) W_o

    expert layer (u the normed input):
    p           = softmax(u W_r) over all 512 ;  r = top-10(p) ;  w_e = p_e / sum_{e in r} p_e        no bias, no factor
    moe         = sum_{e in r, e HELD} w_e W2_e (silu(u W1_e) * (u W3_e)) + sigmoid(u w_g) * W2_s (silu(u W1_s) * (u W3_s))
    logits      = norm(x_last; w_f) W_head                              untied, the held rows

Departures from the source, each a line of the configuration's ``assumed``:
the vision input is the repo's VGG16 grid through a connector as N prefix
positions in raster order (positions 0..N-1), then ``<start>`` (id 0), then
the caption; the weights are random, the router's map with its columns'
components along the calibration batch's mean inputs and the leading
directions of their spread taken out (``calibrate``); the 44 layers and 256
experts the cut leaves out add nothing, here as in the program; the
multi-token head is not run.

It runs in blocks only so that it fits: ``block`` captions at a time
through a layer whose float32 weights are on the device one layer at a
time.  ``mode``: "f32" is the reference; "fp8" (a CONTROL) rounds both
operands of every matmul to float8 e4m3 and leaves the router's product
and the recurrence exact; "state_bf16" (the other CONTROL: the precision
the configuration does NOT state for S) is the reference with S rounded to
bfloat16 after every token and nothing else changed: ``state_bf16_share``
reads it off the state's values.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import params_qwen3next
from .cohere2_captioner import _SPREAD_DIRECTIONS, _SPREAD_SAMPLE, balanced_gate
from .glm52_captioner import _inputs
from .lfm2_captioner import _f32, _grids, _mm, _Static, dense_ffn
from .model import _quant
from .params import nest

_L2_EPS = 1e-6


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _matmul_mode(mode: str) -> str:
    """What the products run in: the state's control changes S alone."""
    return "f32" if mode == "state_bf16" else mode


def delta_rule(q, k, v, g, beta, round_state: bool = False):
    """The recurrence, a position at a time: q, k [n, S, nv, dk] (normed, q
    scaled, a key head already repeated over its value heads), v
    [n, S, nv, dv], g, beta [n, S, nv] -> (o [n, S, nv, dv], S after the
    last position [n, nv, dk, dv])."""
    n, _, nv, dk = q.shape
    dv = v.shape[-1]

    def one(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        d_t = beta_t[..., None] * (v_t - jnp.einsum("nhkv,nhk->nhv", state, k_t))
        state = state + k_t[..., :, None] * d_t[..., None, :]
        if round_state:     # not ``astype`` there and back: the TPU compiler drops that pair as excess precision
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.einsum("nhkv,nhk->nhv", state, q_t)

    state, o = jax.lax.scan(one, jnp.zeros((n, nv, dk, dv), jnp.float32),
                            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def gdn_mixer(p, u, m, mode):
    """u [n, S, H] normed -> (the mixer's output [n, S, H], S after the
    last position [n, nv, dk, dv])."""
    nk, nv, dk, dv, r, _ = params_qwen3next.gdn_dims(m)
    L, eps = int(m["linear_conv_kernel_dim"]), float(m["norm_eps"])
    n, S, _ = u.shape
    mm = _matmul_mode(mode)
    qkvz = _mm(u, p["in_proj_qkvz"], mm).reshape(n, S, nk, 2 * dk + 2 * r * dv)
    ba = _mm(u, p["in_proj_ba"], mm).reshape(n, S, nk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(n, S, nv, dv)
    b, a = ba[..., :r].reshape(n, S, nv), ba[..., r:].reshape(n, S, nv)
    mixed = jnp.concatenate([q.reshape(n, S, -1), k.reshape(n, S, -1), v.reshape(n, S, -1)], axis=-1)
    padded = jnp.pad(mixed, ((0, 0), (L - 1, 0), (0, 0)))
    taps = _quant(p["conv1d"], mm)
    conv = jax.nn.silu(sum(_quant(padded[:, j:j + S], mm) * taps[j] for j in range(L)))
    q = conv[..., :nk * dk].reshape(n, S, nk, dk)
    k = conv[..., nk * dk:2 * nk * dk].reshape(n, S, nk, dk)
    v = conv[..., 2 * nk * dk:].reshape(n, S, nv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + _L2_EPS) * (dk ** -0.5)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + _L2_EPS)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    # value head j reads key head j // r
    o, state = delta_rule(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta,
                          round_state=mode == "state_bf16")
    y = p["norm"] * o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * jax.nn.silu(z)
    return _mm(y.reshape(n, S, nv * dv), p["out_proj"], mm), state


def _rope(x, theta: float, rotary: int):
    """x [n, S, heads, d] at positions 0..S-1: rotate-half over the FIRST
    ``rotary`` of a head's d; the rest pass."""
    S = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    turned, passed = x[..., :rotary], x[..., rotary:]
    rot = jnp.concatenate([-turned[..., rotary // 2:], turned[..., :rotary // 2]], axis=-1)
    return jnp.concatenate([turned * jnp.cos(emb) + rot * jnp.sin(emb), passed], axis=-1)


def attention_mixer(p, u, m, mode):
    """u [n, S, H] normed -> the gated full-attention mixer's output."""
    n, S, _ = u.shape
    nh, kv, d = int(m["num_attention_heads"]), int(m["num_key_value_heads"]), int(m["head_dim"])
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    rotary = int(float(m["partial_rotary_factor"]) * d)
    mm = _matmul_mode(mode)
    qg = _mm(u, p["q_proj"], mm).reshape(n, S, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(n, S, nh * d)
    k = _mm(u, p["k_proj"], mm).reshape(n, S, kv, d)
    v = _mm(u, p["v_proj"], mm).reshape(n, S, kv, d)
    q = _rope(_norm(q, p["q_norm"], eps), theta, rotary)
    k = _rope(_norm(k, p["k_norm"], eps), theta, rotary)
    k = jnp.repeat(k, nh // kv, axis=2)           # query head i reads key head i // (nh / kv)
    v = jnp.repeat(v, nh // kv, axis=2)
    scores = jnp.einsum("nshd,nthd->nhst", _quant(q, mm), _quant(k, mm)) * (d ** -0.5)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("nhst,nthd->nshd", _quant(probs, mm), _quant(v, mm)).reshape(n, S, nh * d)
    return _mm(ctx * jax.nn.sigmoid(gate), p["o_proj"], mm)


def route(p, u, m):
    """u [..., H] -> (chosen experts [..., k], routing weights [..., E],
    zero off the chosen): a softmax over ALL experts, its top-k, the chosen
    over their sum.  Exact float32 whatever the control's mode."""
    scores = jax.nn.softmax(jnp.matmul(u, p["gate"]), axis=-1)
    picked, chosen = jax.lax.top_k(scores, int(m["num_experts_per_tok"]))
    picked = picked / picked.sum(axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)    # [..., k, E]
    return chosen, jnp.einsum("...k,...ke->...e", picked, onehot)


def expert_ffn(p, u, m, mode):
    """The experts held here applied to every token, masked by the routing
    weights over ALL experts; plus the one shared expert times its gate."""
    chosen, weights = route(p, u, m)
    first, held = int(m.get("first_expert", 0)), params_qwen3next.held_experts(m)
    mm = _matmul_mode(mode)
    uq = _quant(u, mm)

    def one(acc, ew):
        w1, w3, w2, we = ew                       # one expert's maps, its weight per token
        y = _mm(jax.nn.silu(jnp.matmul(uq, _quant(w1, mm))) * jnp.matmul(uq, _quant(w3, mm)), w2, mm)
        return acc + y * we[..., None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (p["w1"], p["w3"], p["w2"], jnp.moveaxis(weights[..., first:first + held], -1, 0)))
    shared = p["shared"]
    return out + jax.nn.sigmoid(jnp.matmul(u, shared["gate"])) * dense_ffn(shared, u, mm), chosen


def mix(p, x, kind: str, m, mode: str = "f32"):
    """The first half of a layer: (x + mixer(norm(x)), a DeltaNet layer's
    final state or None)."""
    u = _norm(x, p["input_layernorm"], float(m["norm_eps"]))
    if kind == "linear_attention":
        y, state = gdn_mixer(p["linear_attn"], u, m, mode)
        return x + y, state
    return x + attention_mixer(p["self_attn"], u, m, mode), None


def ffn(p, x, m, mode: str = "f32"):
    """The second half: (x + moe(norm(x)), chosen experts [n, S, k])."""
    y, chosen = expert_ffn(p["feed_forward"], _norm(x, p["post_attention_layernorm"], float(m["norm_eps"])), m, mode)
    return x + y, chosen


def layer(p, x, kind: str, m, mode: str = "f32"):
    """One layer over sequences x [n, S, H]: (y, chosen experts [n, S, k],
    a DeltaNet layer's final state or None)."""
    x, state = mix(p, x, kind, m, mode)
    x, chosen = ffn(p, x, m, mode)
    return x, chosen, state


_mix_jit = jax.jit(mix, static_argnames=("kind", "m", "mode"))
_ffn_jit = jax.jit(ffn, static_argnames=("m", "mode"))


def _through_the_stack(weights_of, model: dict, xs, mode: str, fit=None):
    """xs: blocks [b, S, H] (host float32) through every layer, a layer's
    float32 weights on the device at a time.  ``fit(name, p, blocks)``: the
    calibration's hook between a layer's two halves; it returns the layer
    with its fitted router in.  Returns (the blocks after the last layer,
    chosen experts a layer [n, S, k], the DeltaNet layers' final states
    [n, nv, dk, dv] each), on the host."""
    m = _Static(model)
    routes, states = [], []
    for i, kind in enumerate(model["layer_types"]):
        name = f"lm/layers/{params_qwen3next.layer_name(i)}"
        p = _f32(weights_of(name))
        out = [_mix_jit(p, jnp.asarray(x), kind=kind, m=m, mode=mode) for x in xs]
        xs = [np.asarray(x) for x, _ in out]
        if kind == "linear_attention":
            states.append(np.concatenate([np.asarray(s) for _, s in out]))
        if fit is not None:
            p = fit(name, p, xs)
        out = [_ffn_jit(p, jnp.asarray(x), m=m, mode=mode) for x in xs]
        xs = [np.asarray(x) for x, _ in out]
        routes.append(np.concatenate([np.asarray(c) for _, c in out]))
        del p, out
    return xs, routes, states


def forward(weights_of, model: dict, contexts, tokens, mode: str = "f32", block: int = 8):
    """contexts [n, N, D] float32, tokens [n, T] -> (logits [n, T, V] of
    the caption positions, chosen experts [layers, n, N+T, k], the DeltaNet
    layers' states after the last position [those layers, n, nv, dk, dv]),
    on the host.  ``weights_of(prefix)``: the leaves under
    ``params/decoder/<prefix>`` as nested dicts; called once per layer."""
    m = model
    N = contexts.shape[1]
    mm = _matmul_mode(mode)
    with jax.default_matmul_precision("highest"):
        xs = _inputs(weights_of, contexts, tokens, mm, block)
        xs, routes, states = _through_the_stack(weights_of, m, xs, mode)
        head = _f32(weights_of("lm/lm_head"))
        final = _f32(weights_of("lm/norm"))
        logits = np.concatenate([np.asarray(jnp.einsum(
            "nth,hv->ntv", _quant(_norm(jnp.asarray(x[:, N:]), final, float(m["norm_eps"])), mm),
            _quant(head, mm))) for x in xs])
    return logits, np.stack(routes), np.stack(states)


def _seeded(model: dict, seed: int, fitted=None):
    """``weights_of(prefix)`` over the seed's leaves, made when asked for
    (a layer at a time), with the calibration's leaves laid over them."""
    fitted = fitted or {}

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        under = lambda name: name == path or name.startswith(path + "/")  # noqa: E731
        flat = params_qwen3next.make_weights(model, seed, only=under)
        flat.update({k: v for k, v in fitted.items() if under(k)})
        return flat[path] if path in flat else nest(flat, path)

    return weights_of


def served_logits(model: dict, seed: int, images_u8, tokens, mode: str = "f32", fitted=None, block: int = 8):
    """Teacher-forced logits [n, T, V] of the captions an evaluated path
    returned, the experts the reference chose [layers, n, N+T, k] and the
    DeltaNet layers' states after the last position."""
    cnn = params_qwen3next.make_weights(model, seed, only=lambda name: name.startswith("params/cnn/"))
    ctx = _grids(model, cnn, images_u8, _matmul_mode(mode))
    return forward(_seeded(model, seed, fitted), model, ctx, np.asarray(tokens), mode, block=block)


def state_gap(program, reference) -> float:
    """program, reference [DeltaNet layers, n, nv, dk, dv]: the widest gap
    of a layer's states, as a share of the reference's norm over that
    layer (all rows and heads: a head that remembers long weighs by what it
    holds)."""
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    axes = tuple(range(1, r.ndim))
    return float((np.sqrt(((p - r) ** 2).sum(axis=axes)) / np.sqrt((r ** 2).sum(axis=axes))).max())


def state_bf16_share(states) -> float:
    """The share of a float32 state's nonzero values that lie on the
    bfloat16 grid (their low 16 bits all zero): what precision the state
    was KEPT in, read off the values themselves.  A state kept in float32
    reads about 2**-16; one rounded to bfloat16 between tokens reads 1.
    (``state_gap`` cannot tell the two apart: bfloat16 inputs move S by as
    much as rounding S itself does, PERF.md section 6.)"""
    bits = np.ascontiguousarray(np.asarray(states, np.float32)).view(np.uint32)
    nonzero = (bits & 0x7FFFFFFF) != 0
    return float(((bits & 0xFFFF) == 0)[nonzero].mean()) if nonzero.any() else 1.0


def calibrate(model: dict, weights: Dict[str, np.ndarray], images_u8, tokens, block: int = 8) -> Dict[str, np.ndarray]:
    """{leaf path: value} of the connector's bias and of every layer's
    router map, fitted on the calibration batch in float32, layer by layer,
    as ``cohere2_captioner.calibrate`` (the source has no selection bias,
    so none is added): each column of ``W_r`` loses its component along the
    batch's mean normed input of the prefix positions, along that of the
    caption positions, and along the leading principal directions of the
    prefix positions' spread round their mean.  The batch goes on through
    the layer as routed by the fitted map.  The connector's bias centres
    the prefix."""

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        return weights[path] if path in weights else nest(weights, path)

    t0 = time.perf_counter()
    ctx = _grids(model, weights, images_u8, "f32")
    tokens = np.asarray(tokens)
    N, D = ctx.shape[1:]
    fitted: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        kernel = np.asarray(weights_of("connector")["kernel"], np.float32)
        centre = -(ctx.reshape(-1, D).astype(np.float64).mean(axis=0) @ kernel.astype(np.float64))
        fitted["params/decoder/connector/bias"] = params_qwen3next._round_bf16(centre.astype(np.float32))

        def with_bias(prefix: str):
            got = weights_of(prefix)
            return {**got, "bias": fitted["params/decoder/connector/bias"]} if prefix == "connector" else got

        xs = _inputs(with_bias, ctx, tokens, "f32", block)

        def fit(name, p, blocks):
            f = p["feed_forward"]
            u = np.concatenate([np.asarray(_norm(jnp.asarray(b), p["post_attention_layernorm"],
                                                 float(model["norm_eps"]))) for b in blocks])   # [n, N + T, H]
            prefix, caption = u[:, :N].reshape(-1, u.shape[-1]), u[:, N:].reshape(-1, u.shape[-1])
            mean = prefix.mean(axis=0, dtype=np.float64)
            sample = prefix[::max(1, min(_SPREAD_SAMPLE, len(prefix) // 64))].astype(np.float64)
            spread = np.linalg.svd(sample - mean, full_matrices=False)[2]
            gate = balanced_gate(np.asarray(f["gate"]),
                                 [mean, caption.mean(axis=0, dtype=np.float64), *spread[:_SPREAD_DIRECTIONS]])
            fitted[f"params/decoder/{name}/feed_forward/gate"] = gate.astype(params_qwen3next.BF16)
            return {**p, "feed_forward": {**f, "gate": jnp.asarray(gate)}}

        _through_the_stack(weights_of, model, xs, "f32", fit=fit)
    print(f"benchmark: calibration {time.perf_counter() - t0:.1f} s over {tokens.shape[0]} sequences", flush=True)
    return fitted
