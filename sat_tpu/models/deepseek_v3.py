"""DeepSeek-V3's block as the caption decoder — pure-functional JAX.

The stack of ``model_type: "deepseek_v3"`` at a source's own sizes
(kakaocorp's ``kanana-2-30b-a3b-instruct-2601``: no query compression,
``q_lora_rank: null``; ``n_group = topk_group = 1``, so the group-limited
choice of experts is a plain top-k and there is no group path here).  The
image enters as ``lfm2_moe``'s does: the grid through the connector as the
first N positions of one causal sequence, then ``<start>``, then the
caption.  Per layer, pre-norm (RMSNorm, ``norm_eps``)

    h = x + MLA(operator_norm(x));   y = h + ffn(ffn_norm(h))

* latent attention (MLA), ``u = operator_norm(x)``: ``q = u W_q``, per head
  ``q_nope`` (``qk_nope_head_dim``) and ``q_rope`` (``qk_rope_head_dim``);
  ``[c_raw ; k_rope_raw] = u W_kva``; ``c = kv_a_layernorm(c_raw)``, the
  ``kv_lora_rank``-wide latent; ``k_rope = rope(k_rope_raw)``, ONE rotary
  key a token for all heads; rope in the interleaved convention (the pair
  ``(x[2i], x[2i+1])`` turns by ``pos * theta^(-2i/d)``), no scaling.  Per
  head ``[k_nope ; v] = c W_kvb``; scores
  ``(q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-0.5``, causal
  softmax in float32, ``o_proj`` over the heads' ``v_head_dim`` outputs.
  No biases.  What a token leaves behind is ``[c ; k_rope]``
  (512 + 64 = 576 numbers at the source's sizes), whatever the number of
  heads.
* two forms that agree in exact arithmetic.  EXPANDED (whole sequences:
  the prefill, teacher forcing): keys and values are made from the latent
  by ``W_kvb``, as written above.  ABSORBED (one token through the cache):
  ``W_kvb``'s key half goes into the query, ``q~ = q_nope W_kvb^K^T`` (per
  head, nope -> kv_lora_rank), the scores are ``q~ . c + q_rope . k_rope``
  over the latents themselves, the weighted sum ``u = sum_j p_j c_j`` is
  taken over the latents too, and ``W_kvb``'s value half un-absorbs it,
  ``o = u W_kvb^V``: no key or value of the cache is ever expanded.
* ffn: a dense SwiGLU in the first ``num_dense_layers`` layers, else
  ``models/lm_common.py``'s mixture of experts with DeepSeek-V3's router
  (``noaux_tc``: the rule ``lfm2_moe``'s ``expert_bias`` follows; 1e-20 in
  the sum) and ONE shared SwiGLU of
  ``n_shared_experts x moe_intermediate_size`` that every token goes
  through, added to the routed sum.
* after the last layer ``norm``; the head ``lm_head`` is its own map
  unless ``tie_word_embeddings``.

Three entry points share the layer functions: ``teacher_forced``,
``prefill`` (the N prefix positions of each IMAGE, once, expanded) and
``step`` (one token for each of ``B*K`` beams, absorbed).  The cache: per
layer the prefix's latents stay ``[B, N, 576]``, one per image, read in
place by every beam of that image and never tiled, reordered or expanded;
per beam a suffix ``[B*K, T, 576]`` a layer and the record of routes, which
``ops/beam_search.py`` reorders by parent each step.  Precision as
``lm_common``'s.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from . import lm_common
from .lm_common import Params, StepCounters, init_counters, layer_name, mm, rms_norm  # noqa: F401

# DeepSeek-V3's router adds 1e-20 to the sum of the chosen scores
_ffn = partial(lm_common.ffn, sum_eps=1e-20)


class LatentCache(NamedTuple):
    """Latents ``[c ; k_rope]`` per layer.  As the prefix's: ``[B, N, 576]``
    a layer, per image, closed over by the step.  As the beams' own state:
    ``[B*K, T, 576]`` a layer, every leaf moved by the search's per-parent
    reorder."""

    latents: Tuple[jnp.ndarray, ...]
    routes: Any = None      # lm_common.empty_routes; None for the prefix's


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _qk_dim(config: Config) -> int:
    return config.qk_nope_head_dim + config.qk_rope_head_dim


ATTN_SCOPE = "decoder/lm/attn"


@dataclasses.dataclass(frozen=True, kw_only=True)
class Widths:
    """One KIND of layer's latent attention as numbers: what the layer
    functions below read, so that a stack whose layers differ in heads,
    ranks, head widths and rope base (``models/dots3_note.py``) calls them
    with one record a kind.  A stack of one kind makes its own from its
    ``Config`` (``widths``)."""

    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    eps: float
    # what multiplies the normed latent (a source's
    # ``apply_mla_qkv_lora_rescale``: sqrt(hidden / rank)); 1: nothing does
    kv_scale: float = 1.0
    # a second kind of layer in one stack names itself here ("window"):
    # its device ops are accounted under ``decoder/lm/attn/<segment>/``
    segment: str = ""

    @property
    def qk(self) -> int:
        return self.nope + self.rope

    def named_scope(self, name: str):
        """``jax.named_scope(name)``: every call site writes the scope a
        layer of the first kind is accounted under, in full (the rule
        files under ``benchmark/scopes`` are held against those literals);
        a record with a ``segment`` puts it behind ``decoder/lm/attn``, so
        ``.../attn/q`` becomes ``.../attn/window/q`` and no rule written
        for the one kind takes the other's ops."""
        if self.segment:
            name = name.replace(ATTN_SCOPE, ATTN_SCOPE + "/" + self.segment, 1)
        return jax.named_scope(name)


def widths(config: Config) -> Widths:
    c = config
    return Widths(
        heads=c.num_attention_heads, kv_rank=c.kv_lora_rank, nope=c.qk_nope_head_dim,
        rope=c.qk_rope_head_dim, v=c.v_head_dim, theta=c.rope_theta, eps=c.norm_eps,
    )


def init_params(rng: jax.Array, config: Config) -> Params:
    """``lm_common.init_stack``'s tree over this stack's layers: latent
    attention with an uncompressed query in every one, ``norm``."""
    c = config
    H, nh, rank = c.hidden_size, c.num_attention_heads, c.kv_lora_rank

    def layer_params(layer, linear, ones):
        return {
            "operator_norm": ones(H), "ffn_norm": ones(H),
            "self_attn": {
                "q_proj": linear(H, nh * _qk_dim(c)),
                "kv_a_proj": linear(H, rank + c.qk_rope_head_dim),
                "kv_a_layernorm": ones(rank),
                "kv_b_proj": linear(rank, nh * (c.qk_nope_head_dim + c.v_head_dim)),
                "o_proj": linear(nh * c.v_head_dim, H),
            },
        }

    return lm_common.init_stack(rng, c, layer_params, keys_per_layer=12, norm="norm")


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def _partner(x: jnp.ndarray) -> jnp.ndarray:
    """The rope's signed swap along the last axis: ``[2i] <- -x[2i+1]``,
    ``[2i+1] <- x[2i]``, so that a pair turns as ``x * cos + partner * sin``.
    Each element meets its partner by a roll along d, so no array has a
    minor dimension of 2 (a ``[..., d/2, 2]`` view of the pairs costs a
    768-row step a third of its attention on a v5e: PERF.md section 6)."""
    even = jnp.arange(x.shape[-1]) % 2 == 0
    return jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))


def _rope_tables(positions: jnp.ndarray, theta: float, d: int, lead: int = 0):
    """(cos, sin) float32 [S, lead + d] of the interleaved convention: the
    pair (2i, 2i+1) of the last d lanes turns by ``pos * theta^(-2i/d)``;
    1 and 0 on the ``lead`` lanes before them, which do not turn."""
    inv = jnp.repeat(1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)), 2)
    angle = positions.astype(jnp.float32)[:, None] * inv                            # [S, d]
    still = ((0, 0), (lead, 0))
    return (
        jnp.pad(jnp.cos(angle), still, constant_values=1.0),
        jnp.pad(jnp.sin(angle), still),
    )


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [..., S, heads, d] float32, positions [S]: the interleaved
    convention, the pair (x[2i], x[2i+1]) turned by ``pos * theta^(-2i/d)``,
    its partner met by ``_partner``'s rolls.  The form of one token's rows
    (the steps) and of the one rotary key; a whole sequence's queries take
    ``_swapped_columns`` instead."""
    cos, sin = _rope_tables(positions, theta, x.shape[-1])
    return x * cos[:, None] + _partner(x) * sin[:, None]


def _swapped_columns(w_rope: jnp.ndarray, lead: int = 0) -> jnp.ndarray:
    """w_rope [..., d]: a map's rotary columns -> ``W_r P`` [..., lead + d],
    the same columns swapped in pairs with one sign flipped (``_partner`` of
    them) behind ``lead`` columns of zeros.  ``partner(u W_r) = u (W_r P)``:
    the same dot products, so after the same rounding the same numbers, and
    a product makes the rope's partner over whole lanes where a roll of the
    product's 64-wide output would.  Made once a program from the weights
    as loaded; no leaf of the tree."""
    return jnp.pad(_partner(w_rope), ((0, 0),) * (w_rope.ndim - 1) + ((lead, 0),))


def _queries(m: Params, w: Widths, h: jnp.ndarray, positions: jnp.ndarray):
    """h [..., S, H] normed -> (q_nope [..., S, nh, nope], q_rope
    [..., S, nh, rope] rotated), bfloat16.  One product, the rotary part
    split off and rolled: the form of a step's rows, where reading ``W_q``
    bounds the time and more columns would cost more than the rope does."""
    with w.named_scope("decoder/lm/attn/q"):
        q = mm(h, m["q_proj"]).reshape(h.shape[:-1] + (w.heads, w.qk))
        q_nope, q_rope = q[..., : w.nope], q[..., w.nope:]
        q_rope = _rope(q_rope.astype(jnp.float32), positions, w.theta)
        return q_nope, q_rope.astype(jnp.bfloat16)


def _sequence_queries(m: Params, w: Widths, h: jnp.ndarray, positions: jnp.ndarray):
    """``_queries`` for whole sequences, the same numbers: ``W_q``'s nope
    columns, its rotary columns and their signed swap as three products
    over the heads' flat width, so the rotation is one multiply-add over
    ``[.., nh * rope]`` (cos and sin tiled over the heads) and nothing is
    split or rolled at a 64-wide minor dimension."""
    nh, nope, rope = w.heads, w.nope, w.rope
    with w.named_scope("decoder/lm/attn/q"):
        w_q = m["q_proj"].reshape(-1, nh, nope + rope)
        flat = lambda a: a.reshape(a.shape[0], -1)  # noqa: E731
        q_nope = mm(h, flat(w_q[..., :nope]))
        q_rope = mm(h, flat(w_q[..., nope:])).astype(jnp.float32)
        partner = mm(h, flat(_swapped_columns(w_q[..., nope:]))).astype(jnp.float32)
        cos, sin = (jnp.tile(t, (1, nh)) for t in _rope_tables(positions, w.theta, rope))
        q_rope = (q_rope * cos + partner * sin).astype(jnp.bfloat16)
        by_head = h.shape[:-1] + (nh, -1)
        return q_nope.reshape(by_head), q_rope.reshape(by_head)


def _latents(m: Params, w: Widths, h: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """h [..., S, H] normed -> ``[c ; k_rope]`` [..., S, rank + rope]
    bfloat16: the normed latent (times ``kv_scale`` where the kind has
    one) and the rotated key all heads share."""
    with w.named_scope("decoder/lm/attn/latent"):
        raw = mm(h, m["kv_a_proj"])
        latent = rms_norm(raw[..., : w.kv_rank], m["kv_a_layernorm"], w.eps)
        if w.kv_scale != 1.0:
            latent = latent * w.kv_scale
        k_rope = _rope(
            raw[..., None, w.kv_rank:].astype(jnp.float32), positions, w.theta
        )[..., 0, :]
        return jnp.concatenate([latent, k_rope], axis=-1).astype(jnp.bfloat16)


def _kv_b(m: Params, w: Widths) -> jnp.ndarray:
    """``W_kvb`` [rank, nh, nope + v]: per head its key map then its value map."""
    return m["kv_b_proj"].reshape(w.kv_rank, w.heads, w.nope + w.v)


def attend_expanded(m: Params, config: Config, h: jnp.ndarray):
    """h [B, S, H] normed, at positions 0..S-1 -> (the attention's output
    [B, S, H], the latents [B, S, rank + rope]): keys and values made from
    the latents, causal."""
    c = widths(config)
    B, S, _ = h.shape
    rank, nope = c.kv_rank, c.nope
    positions = jnp.arange(S)
    q_nope, q_rope = _sequence_queries(m, c, h, positions)
    latents = _latents(m, c, h, positions)
    with jax.named_scope("decoder/lm/attn/expand"):
        kv = jnp.einsum(
            "bsc,chd->bshd", latents[..., :rank], _kv_b(m, c),
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)
        k_nope, v = kv[..., :nope], kv[..., nope:]
    with jax.named_scope("decoder/lm/attn/scores"):
        scores = jnp.einsum(
            "bshd,bthd->bhst", q_nope, k_nope, preferred_element_type=jnp.float32
        ) + jnp.einsum(
            "bshd,btd->bhst", q_rope, latents[..., rank:], preferred_element_type=jnp.float32
        )
        causal = positions[:, None] >= positions[None, :]
        scores = jnp.where(causal, scores * (c.qk ** -0.5), -jnp.inf)
        # the softmax's division after the weighted sum, over [B, S, nh, v]
        # and not over the [B, nh, S, S] weights: the same float32 sum
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        ctx = jnp.einsum(
            "bhst,bthd->bshd", weights.astype(jnp.bfloat16), v,
            preferred_element_type=jnp.float32,
        ) / jnp.sum(weights, axis=-1).transpose(0, 2, 1)[..., None]
        ctx = ctx.astype(jnp.bfloat16)
    with jax.named_scope("decoder/lm/attn/out"):
        return mm(ctx.reshape(B, S, -1), m["o_proj"]), latents


def attend_absorbed(
    m: Params, config: Config, h: jnp.ndarray, prefix: jnp.ndarray,
    suffix: jnp.ndarray, t: jnp.ndarray,
):
    """One token a row through the latent cache.  h [R, H] normed, at
    position N + t; prefix [B, N, rank + rope], the latents of each IMAGE,
    read in place by its K = R // B rows; suffix [R, T, rank + rope], each
    row's own, written at t here.  Returns (the attention's output [R, H],
    the suffix with this token's latent in).  One softmax across prefix
    and suffix; neither is expanded."""
    c = widths(config)
    R = h.shape[0]
    B, N, _ = prefix.shape
    K, T = R // B, suffix.shape[1]
    nh, rank, nope = c.heads, c.kv_rank, c.nope
    position = (N + t)[None]
    q_nope, q_rope = _queries(m, c, h[:, None], position)
    suffix = jax.lax.dynamic_update_slice(
        suffix, _latents(m, c, h[:, None], position), (0, t, 0)
    )
    w = _kv_b(m, c)
    with jax.named_scope("decoder/lm/attn/absorb"):
        q_lat = jnp.einsum(
            "rhd,chd->rhc", q_nope[:, 0], w[..., :nope], preferred_element_type=jnp.float32
        ).astype(jnp.bfloat16)
    with jax.named_scope("decoder/lm/attn/scores"):
        q = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)             # [R, nh, rank + rope]
        s_pre = jnp.einsum(
            "bkhc,bnc->bkhn", q.reshape(B, K, nh, -1), prefix,
            preferred_element_type=jnp.float32,
        ).reshape(R, nh, N)
        s_suf = jnp.einsum("rhc,rtc->rht", q, suffix, preferred_element_type=jnp.float32)
        s_suf = jnp.where(jnp.arange(T) <= t, s_suf, -jnp.inf)
        probs = jax.nn.softmax(
            jnp.concatenate([s_pre, s_suf], axis=-1) * (c.qk ** -0.5), axis=-1
        ).astype(jnp.bfloat16)
        mixed = jnp.einsum(
            "bkhn,bnc->bkhc", probs[..., :N].reshape(B, K, nh, N), prefix[..., :rank],
            preferred_element_type=jnp.float32,
        ).reshape(R, nh, rank) + jnp.einsum(
            "rht,rtc->rhc", probs[..., N:], suffix[..., :rank],
            preferred_element_type=jnp.float32,
        )
    with jax.named_scope("decoder/lm/attn/absorb"):
        ctx = jnp.einsum(
            "rhc,chd->rhd", mixed.astype(jnp.bfloat16), w[..., nope:],
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)
    with jax.named_scope("decoder/lm/attn/out"):
        return mm(ctx.reshape(R, -1), m["o_proj"]), suffix


# ---------------------------------------------------------------------------
# whole sequences: teacher forcing and the prefill
# ---------------------------------------------------------------------------


def sequence_forward(lm: Params, config: Config, x: jnp.ndarray):
    """x [B, S, H] bfloat16 at positions 0..S-1 -> (hidden after the last
    layer [B, S, H], the sequence's latents per layer (a ``LatentCache``
    of ``[B, S, rank + rope]``), tokens per expert [moe layers, E],
    experts chosen [B, S, moe layers * k])."""
    c = config
    B, S, _ = x.shape
    latents, counts, routes = [], [], []
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        h = rms_norm(x, p["operator_norm"], c.norm_eps)
        y, kept = attend_expanded(p["self_attn"], c, h)
        x = x + y
        latents.append(kept)
        x, sizes, experts, _ = _ffn(p, c, i, x)
        if sizes is not None:
            counts.append(sizes)
            routes.append(experts)
    return (
        x, LatentCache(tuple(latents)), lm_common.stack_counts(counts),
        lm_common.join_routes(routes, (B, S)),
    )


def _head(lm: Params, config: Config, x: jnp.ndarray) -> jnp.ndarray:
    """[..., H] -> float32 logits [..., V]: the final norm, then the
    head's own map (the embedding's where the two are tied)."""
    with jax.named_scope("decoder/lm/head"):
        h = rms_norm(x, lm["norm"], config.norm_eps).astype(jnp.bfloat16)
        if "lm_head" in lm:
            return jnp.einsum(
                "...h,hv->...v", h, lm["lm_head"], preferred_element_type=jnp.float32
            )
        return jnp.einsum(
            "...h,vh->...v", h, lm["embed_tokens"], preferred_element_type=jnp.float32
        )


def teacher_forced(
    params: Params, config: Config, contexts: jnp.ndarray, sentences: jnp.ndarray,
) -> jnp.ndarray:
    """logits [B, T, V]: the input at caption step t is sentences[:, t-1]
    (``<start>`` = 0 at t = 0), after the N prefix positions."""
    lm = params["lm"]
    x = lm_common.sequence_inputs(params, contexts, sentences)
    hidden, _, _, _ = sequence_forward(lm, config, x)
    return _head(lm, config, hidden[:, contexts.shape[1]:])


def prefill(params: Params, config: Config, contexts: jnp.ndarray):
    """The N prefix positions of each image, once, in the expanded form:
    (the prefix's latents ``[B, N, rank + rope]`` a layer, the tokens per
    expert, the experts every position chose [B, N, moe layers * k])."""
    _, state, counts, routes = sequence_forward(
        params["lm"], config, lm_common.prefix(params, contexts)
    )
    return state, counts, routes


# ---------------------------------------------------------------------------
# one token through the latent cache
# ---------------------------------------------------------------------------


def start_beams(config: Config, prefix: LatentCache, K: int, max_len: int, tile) -> LatentCache:
    """The per-beam cache of the K beams of each image before the first
    step: an empty suffix of ``max_len`` latents per layer and an empty
    record of routes.  Nothing of the prefix is per beam (``tile`` is for
    a decoder whose prefix leaves some)."""
    c = config
    rows = prefix.latents[0].shape[0] * K
    width = c.kv_lora_rank + c.qk_rope_head_dim
    return LatentCache(
        latents=tuple(
            jnp.zeros((rows, max_len, width), jnp.bfloat16) for _ in range(c.num_hidden_layers)
        ),
        routes=lm_common.empty_routes(c, rows, max_len),
    )


def step(
    params: Params, config: Config, prefix: LatentCache, cache: LatentCache,
    counters: StepCounters, last_word: jnp.ndarray,
):
    """One token for each of R = B*K beams in the absorbed form.  prefix:
    the per-image latents; cache: the beams' own; last_word [R] int32 at
    position N + t.  Returns (cache, counters, logits [R, V] float32)."""
    c = config
    lm = params["lm"]
    x = lm_common.embed(lm, last_word)                      # [R, H]
    latents, counts, routes = [], [], []
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        h = rms_norm(x, p["operator_norm"], c.norm_eps)
        y, suffix = attend_absorbed(
            p["self_attn"], c, h, prefix.latents[i], cache.latents[i], counters.t
        )
        x = x + y
        latents.append(suffix)
        x, sizes, experts, _ = _ffn(p, c, i, x)
        if sizes is not None:
            counts.append(sizes)
            routes.append(experts)
    counters, taken = lm_common.record_step(counters, cache.routes, counts, routes)
    return LatentCache(tuple(latents), taken), counters, _head(lm, c, x)
