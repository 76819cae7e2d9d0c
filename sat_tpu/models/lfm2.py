"""LFM2-MoE as the caption decoder — pure-functional JAX.

The encoder's grid ``[B, N, D]`` goes through a connector (one linear map
``D -> hidden_size``) and becomes the first N positions of ONE causal
sequence: the N prefix positions in raster order, then ``<start>``, then
the caption's tokens.  The stack is LiquidAI's ``lfm2_moe``
(``LFM2-8B-A1B``): per layer, pre-norm (RMSNorm, ``norm_eps``)

    h = x + mixer(operator_norm(x));   y = h + ffn(ffn_norm(h))

* conv mixer: ``B, C, u = split3(in_proj(x))``;
  ``out_proj(C * causal_depthwise_conv1d(B * u, conv_L_cache taps))``.
  State: the last ``conv_L_cache`` positions of ``B * u``.
* attention mixer: q/k/v without bias, RMSNorm over the head on q and k,
  rotary embedding (rotate-half, ``rope_theta``, the whole head), grouped
  queries, scale ``head ** -0.5``, causal, ``out_proj``.  State: keys and
  values.
* ffn: a dense SwiGLU in the first ``num_dense_layers`` layers, else
  ``models/lm_common.py``'s mixture of ``num_experts`` SwiGLU experts (the
  router, the sort by expert, the grouped product, the un-sort: shared with
  ``models/deepseek_v3.py``), with this source's 1e-6 in the sum of the
  chosen scores and no shared expert.
* after the last layer ``embedding_norm``; the head is tied to the
  embedding.

Precision: ``lm_common``'s (bfloat16 parameters and products with float32
accumulation, a bfloat16 residual stream; norms, softmax and the whole
router in float32).

Three entry points share one set of layer functions: ``teacher_forced``
(train, and the tests' full forward), ``prefill`` (the N prefix positions
of each IMAGE, once) and ``step`` (one token for each of ``B*K`` beams,
through the cache).  The cache is of two kinds and in two places: the
prefix's keys and values stay ``[B, N, ...]``, one per image, read in
place by every beam of that image and never tiled or reordered; the
per-beam part (a conv state per conv layer, the suffix keys and values
``[B*K, T, ...]`` per attention layer) is what ``ops/beam_search.py``
reorders by parent each step.  ``<start>`` is the first step's input, as
it is the LSTM's.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from . import lm_common
from .lm_common import Params, StepCounters, init_counters, layer_name  # noqa: F401
from .lm_common import embed as _embed
from .lm_common import join_routes as _join_routes
from .lm_common import mm as _mm
from .lm_common import prefix as _prefix
from .lm_common import rms_norm as _rms_norm
from .lm_common import stack_counts as _stack_counts

# the source's router adds 1e-6 to the sum of the chosen scores
_route = partial(lm_common.route, sum_eps=1e-6)
moe_ffn = partial(lm_common.moe_ffn, sum_eps=1e-6)
_ffn = partial(lm_common.ffn, sum_eps=1e-6)


class BeamCache(NamedTuple):
    """Per-beam decode state: every leaf is ``[B*K, ...]`` and is moved
    by the search's per-parent reorder."""

    conv: Tuple[jnp.ndarray, ...]   # per conv layer [R, L, H]: last L of B*u
    keys: Tuple[jnp.ndarray, ...]   # per attention layer [R, T, kv*hd]
    values: Tuple[jnp.ndarray, ...]
    # [R, T * moe layers * k] int32 (step-major; one row of lanes a beam):
    # the experts the beam's own tokens chose, step by step; it follows
    # the beam through every reorder, so
    # at the end a live beam holds the choices of ITS ancestry (what a
    # teacher-forced pass over its caption would choose).  None for the
    # state of whole sequences (``sequence_forward``).
    routes: Any = None


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _head_dim(config: Config) -> int:
    return config.hidden_size // config.num_attention_heads


def init_params(rng: jax.Array, config: Config) -> Params:
    """``lm_common.init_stack``'s tree over this stack's layers: a conv
    or an attention mixer a layer, no shared expert, ``embedding_norm``."""
    c = config
    H = c.hidden_size
    hd, kv = _head_dim(c), c.num_key_value_heads

    def layer_params(layer, linear, ones):
        p: Params = {"operator_norm": ones(H), "ffn_norm": ones(H)}
        if c.layer_types[layer] == "conv":
            p["conv"] = {
                "in_proj": linear(H, 3 * H),
                "conv": linear(c.conv_L_cache, H),
                "out_proj": linear(H, H),
            }
        else:
            p["self_attn"] = {
                "q_proj": linear(H, H), "k_proj": linear(H, kv * hd),
                "v_proj": linear(H, kv * hd), "out_proj": linear(H, H),
                "q_layernorm": ones(hd), "k_layernorm": ones(hd),
            }
        return p

    return lm_common.init_stack(
        rng, c, layer_params, keys_per_layer=8, norm="embedding_norm", shared=False,
        connector_first=True,
    )


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [..., S, heads, hd] float32, positions [S]: rotate-half over the
    whole head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = positions.astype(jnp.float32)[:, None] * inv[None, :]        # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _qkv(p: Params, config: Config, h: jnp.ndarray, positions: jnp.ndarray):
    """h [..., S, H] normed -> q [..., S, nh, hd], k, v [..., S, kv, hd]
    (q, k normed over the head and rotated), bfloat16."""
    c = config
    hd, nh, kv = _head_dim(c), c.num_attention_heads, c.num_key_value_heads
    lead = h.shape[:-1]
    q = _mm(h, p["q_proj"]).reshape(lead + (nh, hd))
    k = _mm(h, p["k_proj"]).reshape(lead + (kv, hd))
    v = _mm(h, p["v_proj"]).reshape(lead + (kv, hd))
    q = _rope(_rms_norm(q, p["q_layernorm"], c.norm_eps), positions, c.rope_theta)
    k = _rope(_rms_norm(k, p["k_layernorm"], c.norm_eps), positions, c.rope_theta)
    return q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v


def _conv_taps(window: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """window [..., L, H] (oldest first), taps [L, H] -> [..., H]."""
    return jnp.sum(window.astype(jnp.float32) * taps.astype(jnp.float32), axis=-2)


# ---------------------------------------------------------------------------
# whole sequences: teacher forcing and the prefill
# ---------------------------------------------------------------------------


def sequence_forward(lm: Params, config: Config, x: jnp.ndarray):
    """x [B, S, H] bfloat16 at positions 0..S-1 -> (hidden after the last
    layer [B, S, H], BeamCache-shaped per-layer state of the sequence,
    tokens per expert [moe layers, E], experts chosen
    [B, S, moe layers * k]).  The state: per conv layer the
    last L positions of B*u ``[B, L, H]``, per attention layer the keys
    and values of every position ``[B, S, kv*hd]``."""
    c = config
    B, S, H = x.shape
    L = c.conv_L_cache
    positions = jnp.arange(S)
    causal = positions[:, None] >= positions[None, :]
    conv_state, keys, values, counts, routes = [], [], [], [], []
    for i, kind in enumerate(c.layer_types):
        p = lm["layers"][layer_name(i)]
        if kind == "conv":
            with jax.named_scope("decoder/lm/conv"):
                m = p["conv"]
                h = _rms_norm(x, p["operator_norm"], c.norm_eps)
                gate_b, gate_c, u = jnp.split(_mm(h, m["in_proj"]), 3, axis=-1)
                bu = jnp.pad(gate_b * u, ((0, 0), (L - 1, 0), (0, 0)))   # [B, S+L-1, H]
                conv = sum(
                    bu[:, j:j + S].astype(jnp.float32) * m["conv"][j].astype(jnp.float32)
                    for j in range(L)
                )
                y = _mm(gate_c * conv.astype(jnp.bfloat16), m["out_proj"])
                conv_state.append(bu[:, S - 1:])           # the last L positions
                x = x + y
        else:
            with jax.named_scope("decoder/lm/attn"):
                m = p["self_attn"]
                h = _rms_norm(x, p["operator_norm"], c.norm_eps)
                q, k, v = _qkv(m, c, h, positions)
                kv, hd = c.num_key_value_heads, _head_dim(c)
                g = c.num_attention_heads // kv
                scores = jnp.einsum(
                    "bshgd,bthd->bhgst", q.reshape(B, S, kv, g, hd), k,
                    preferred_element_type=jnp.float32,
                ) * (hd ** -0.5)
                probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
                ctx = jnp.einsum(
                    "bhgst,bthd->bshgd", probs.astype(jnp.bfloat16), v,
                    preferred_element_type=jnp.float32,
                ).astype(jnp.bfloat16)
                x = x + _mm(ctx.reshape(B, S, H), m["out_proj"])
                keys.append(k.reshape(B, S, kv * hd))
                values.append(v.reshape(B, S, kv * hd))
        x, sizes, experts, _ = _ffn(p, c, i, x)
        if sizes is not None:
            counts.append(sizes)
            routes.append(experts)
    state = BeamCache(tuple(conv_state), tuple(keys), tuple(values))
    return x, state, _stack_counts(counts), _join_routes(routes, (B, S))


def _head(lm: Params, config: Config, x: jnp.ndarray) -> jnp.ndarray:
    """[..., H] -> float32 logits [..., V] through the tied embedding."""
    with jax.named_scope("decoder/lm/head"):
        h = _rms_norm(x, lm["embedding_norm"], config.norm_eps).astype(jnp.bfloat16)
        return jnp.einsum(
            "...h,vh->...v", h, lm["embed_tokens"], preferred_element_type=jnp.float32
        )


def teacher_forced(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    sentences: jnp.ndarray,
) -> jnp.ndarray:
    """logits [B, T, V]: the input at caption step t is sentences[:, t-1]
    (``<start>`` = 0 at t = 0), after the N prefix positions."""
    lm = params["lm"]
    N = contexts.shape[1]
    x = lm_common.sequence_inputs(params, contexts, sentences)
    hidden, _, _, _ = sequence_forward(lm, config, x)
    return _head(lm, config, hidden[:, N:])


def prefill(params: Params, config: Config, contexts: jnp.ndarray):
    """The N prefix positions of each image, once: (the prefix's BeamCache
    over ``[B, ...]`` rows — its keys and values are the per-image cache —
    the tokens per expert, and the experts every position chose
    ``[B, N, moe layers * k]``)."""
    _, state, counts, routes = sequence_forward(
        params["lm"], config, _prefix(params, contexts)
    )
    return state, counts, routes


# ---------------------------------------------------------------------------
# one token through the cache
# ---------------------------------------------------------------------------


def init_cache(config: Config, conv, rows: int, max_len: int) -> BeamCache:
    """The per-beam cache of ``rows`` beams before the first step: their
    conv states ``conv`` (the prefix's, tiled by the caller), an empty
    suffix of ``max_len`` keys and values per attention layer, and an
    empty record of routes."""
    c = config
    width = c.num_key_value_heads * _head_dim(c)
    n_attn = sum(kind == "full_attention" for kind in c.layer_types)
    empty = tuple(jnp.zeros((rows, max_len, width), jnp.bfloat16) for _ in range(n_attn))
    routes = lm_common.empty_routes(c, rows, max_len)
    return BeamCache(conv=tuple(conv), keys=empty, values=empty, routes=routes)


def start_beams(config: Config, prefix: BeamCache, K: int, max_len: int, tile) -> BeamCache:
    """The per-beam cache of the K beams of each image before the first
    step: the prefix's conv states, ``tile``d to a row a beam (they start
    per image and then differ per beam), over ``init_cache``'s empties."""
    conv = tuple(tile(x, K) for x in prefix.conv)
    return init_cache(config, conv, prefix.keys[0].shape[0] * K, max_len)


def step(
    params: Params,
    config: Config,
    prefix: BeamCache,
    cache: BeamCache,
    counters: StepCounters,
    last_word: jnp.ndarray,
):
    """One token for each of R = B*K beams.  prefix: the per-image keys
    and values ``[B, N, kv*hd]`` (its conv leaf is not read); cache: the
    beams' own state; last_word [R] int32 at position N + t.  Returns
    (cache, counters, logits [R, V] float32)."""
    c = config
    lm = params["lm"]
    R = last_word.shape[0]
    t = counters.t
    kv, hd = c.num_key_value_heads, _head_dim(c)
    g = c.num_attention_heads // kv
    x = _embed(lm, last_word)                               # [R, H]
    conv_state, keys, values, counts, routes = [], [], [], [], []
    conv_i = attn_i = 0
    for i, kind in enumerate(c.layer_types):
        p = lm["layers"][layer_name(i)]
        if kind == "conv":
            with jax.named_scope("decoder/lm/conv"):
                m = p["conv"]
                h = _rms_norm(x, p["operator_norm"], c.norm_eps)
                gate_b, gate_c, u = jnp.split(_mm(h, m["in_proj"]), 3, axis=-1)
                window = jnp.concatenate(
                    [cache.conv[conv_i][:, 1:], (gate_b * u)[:, None]], axis=1
                )
                conv = _conv_taps(window, m["conv"]).astype(jnp.bfloat16)
                x = x + _mm(gate_c * conv, m["out_proj"])
                conv_state.append(window)
                conv_i += 1
        else:
            with jax.named_scope("decoder/lm/attn"):
                m = p["self_attn"]
                pk, pv = prefix.keys[attn_i], prefix.values[attn_i]
                B, N = pk.shape[0], pk.shape[1]
                K = R // B
                h = _rms_norm(x, p["operator_norm"], c.norm_eps)
                q, k, v = _qkv(m, c, h[:, None], (N + t)[None])
                T = cache.keys[attn_i].shape[1]
                sk = jax.lax.dynamic_update_slice(
                    cache.keys[attn_i], k.reshape(R, 1, kv * hd), (0, t, 0)
                )
                sv = jax.lax.dynamic_update_slice(
                    cache.values[attn_i], v.reshape(R, 1, kv * hd), (0, t, 0)
                )
                q = q.reshape(B, K, kv, g, hd)
                # every beam of an image reads that image's prefix in place
                s_pre = jnp.einsum(
                    "bkhgd,bnhd->bkhgn", q, pk.reshape(B, N, kv, hd),
                    preferred_element_type=jnp.float32,
                )
                s_suf = jnp.einsum(
                    "bkhgd,bkthd->bkhgt", q, sk.reshape(B, K, T, kv, hd),
                    preferred_element_type=jnp.float32,
                )
                s_suf = jnp.where(jnp.arange(T) <= t, s_suf, -jnp.inf)
                probs = jax.nn.softmax(
                    jnp.concatenate([s_pre, s_suf], axis=-1) * (hd ** -0.5), axis=-1
                ).astype(jnp.bfloat16)
                ctx = jnp.einsum(
                    "bkhgn,bnhd->bkhgd", probs[..., :N], pv.reshape(B, N, kv, hd),
                    preferred_element_type=jnp.float32,
                ) + jnp.einsum(
                    "bkhgt,bkthd->bkhgd", probs[..., N:], sv.reshape(B, K, T, kv, hd),
                    preferred_element_type=jnp.float32,
                )
                x = x + _mm(ctx.astype(jnp.bfloat16).reshape(R, -1), m["out_proj"])
                keys.append(sk)
                values.append(sv)
                attn_i += 1
        x, sizes, experts, _ = _ffn(p, c, i, x)
        if sizes is not None:
            counts.append(sizes)
            routes.append(experts)
    counters, taken = lm_common.record_step(counters, cache.routes, counts, routes)
    return (
        BeamCache(tuple(conv_state), tuple(keys), tuple(values), taken),
        counters,
        _head(lm, c, x),
    )
