"""dots3-note-prev's block (``model_type: "dots3_note"``) as the caption
decoder — pure-functional JAX.

A stack that MIXES two kinds of ``models/glm_moe_dsa.py``'s block, each at
widths of its own (``Config.layer_types``).  For a layer of kind k with
``(nh, r_q, r_kv, d_n, d_r, d_v, theta)`` = full ``(num_attention_heads,
q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
rope_theta)``, sliding the ``swa_*`` fields; ``H = hidden_size``;
``u = RMSNorm(x)``:

    qr      = RMSNorm(u W_qa) * a_q            a_q  = sqrt(H / r_q)   (mla_lora_rescale; else 1)
    q       = qr W_qb  -> per head [q_n (d_n) ; q_r (d_r)],  q_r turned (interleaved pairs, theta_k)
    [c' ; k_r'] = u W_kva ;  c = RMSNorm(c') * a_kv ,  a_kv = sqrt(H / r_kv) ;  k_r = rope(k_r')   one a token
    [k_n ; v]   = c W_kvb     per head
    s[t, j] = (q_n[t,h] . k_n[j,h] + q_r[t,h] . k_r[j]) * (d_n + d_r)^-0.5
    seen    : full    j in S_t, the min(index_topk, t + 1) positions j <= t of largest I[t, j]
                      (glm_moe_dsa's indexer, from qr and u; EVERY full layer has its own)
              sliding t - sliding_window_size < j <= t
    o[t,h]  = sum_seen softmax(s[t, .])[j] v[j,h]
    g[t]    = sigmoid(u[t] W_g)   in R^nh ;   o[t,h] <- g[t,h] * o[t,h]          (attention_gate "headwise")
    x      <- x + concat_h(o) W_o
    x      <- x + FFN(RMSNorm(x)):  the first num_dense_layers a SwiGLU; after them the sigmoid router over
              num_experts (+ selection bias, top-k, weights / (sum + 1e-20) x routed_scaling_factor) over
              the experts HELD + one shared SwiGLU

The indexer reads ``qr`` after ``a_q``: a positive scale of ``qr``
multiplies a row of ``I`` by one constant, so the selection is the same
either way.  This module holds only what is its own: the two records of
widths, the window layer's two forms, the stack that tells the kinds apart
and the cache that keeps a window; queries, latents, expansion, the
absorbed step, the gate, the indexer and the selection are
``glm_moe_dsa``'s (called with one record a kind), the expert layer
``lm_common``'s.

Forms.  Whole sequences go one image at a time and one block of queries
at a time, expanded, as ``glm_moe_dsa``'s.  A sliding layer's block of
queries meets the keys of its BAND alone (from ``sliding_window_size - 1``
before the block's first query to its last: 1,024 keys a block of 512 at a
window of 513): on the TPU through ``ops/flash_prefill.py`` with its
window bound (key tiles wholly below the band neither fetched nor
computed), elsewhere, and where it is differentiated, in ``lax`` blocks.
One token through the cache is ABSORBED in both kinds; a sliding layer's
row at position p attends p - (window - 1) ... p: those of the image from
the prefix's kept TAIL (read in place per image, never tiled), the rest
from its own suffix.

The cache (a ``glm_moe_dsa.DsaCache``, its leaves of two kinds side by
side): a full layer keeps per image the prefix's latents ``[B, N, 576]``
and indexer keys; a sliding layer keeps per image only what a step can
still see, the prefix's LAST ``window - 1`` latents ``[B, 512, 1088]``;
per beam each layer's suffix ``[B*K, T, ..]`` (T below the window: kept
whole) and the two records, all moved by the search's reorder, which
looks at no leaf's width.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from ..ops import flash_prefill
from . import glm_moe_dsa as dsa
from . import lm_common
from .deepseek_v3 import _head, _latents
from .glm_moe_dsa import DsaCache, Widths, _sum_pairs
from .lm_common import Params, layer_name, rms_norm

# a sliding layer's device ops: under decoder/lm/attn/window/ (the mixer
# still, in a segment of their own: ``Widths.named_scope``)
WINDOW_SEGMENT = "window"


class Counters(NamedTuple):
    """``glm_moe_dsa.DsaCounters`` and what the window layers count."""

    t: jnp.ndarray
    moe_counts: jnp.ndarray
    step_visits: jnp.ndarray
    pairs: jnp.ndarray      # [2, 6]: the prefill, the steps: ``glm_moe_dsa._sum_pairs``
    attended: jnp.ndarray   # [2] positions attended, positions visible (steps, full layers)
    window: jnp.ndarray     # [2] the same of the steps' sliding layers
    # [2, 2] full layers, sliding layers: the prefill's query blocks
    # through the fused kernel, in all
    fused: jnp.ndarray


def _sliding(config: Config, layer: int) -> bool:
    return config.layer_types[layer] == "sliding_attention"


def _full_layers(config: Config):
    return [i for i in range(config.num_hidden_layers) if not _sliding(config, i)]


def _kept(config: Config, positions: int) -> int:
    """Latents of a sliding layer's prefix that a later token can still
    see: its last ``sliding_window_size - 1``, or all it has."""
    return min(positions, config.sliding_window_size - 1)


def widths(config: Config) -> Tuple[Widths, Widths]:
    """(a full layer's numbers, a sliding layer's)."""
    c = config
    rescale = lambda rank: (c.hidden_size / rank) ** 0.5 if c.mla_lora_rescale else 1.0  # noqa: E731
    full = dataclasses.replace(
        dsa.widths(c), q_scale=rescale(c.q_lora_rank), kv_scale=rescale(c.kv_lora_rank)
    )
    sliding = Widths(
        heads=c.swa_num_attention_heads, q_rank=c.swa_q_lora_rank, kv_rank=c.swa_kv_lora_rank,
        nope=c.swa_qk_nope_head_dim, rope=c.swa_qk_rope_head_dim, v=c.swa_v_head_dim,
        theta=c.swa_rope_theta, eps=c.norm_eps, q_scale=rescale(c.swa_q_lora_rank),
        kv_scale=rescale(c.swa_kv_lora_rank), segment=WINDOW_SEGMENT,
    )
    return full, sliding


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, config: Config) -> Params:
    """``glm_moe_dsa.init_params``'s tree and draws, each layer's attention
    at its kind's widths: an indexer in every full layer, a ``gate_proj``
    [H, heads] in both kinds where ``attention_gate`` is "headwise"."""
    c = config
    kinds, gate = widths(c), c.attention_gate == "headwise"
    return lm_common.init_stack(
        rng, c, lambda i, linear, ones: dsa.layer_params(
            c.hidden_size, kinds[_sliding(c, i)], linear, ones, indexer=not _sliding(c, i), gate=gate
        ), keys_per_layer=20, norm="norm",
    )


# ---------------------------------------------------------------------------
# a sliding layer: whole sequences over the band, one token over the tail
# ---------------------------------------------------------------------------


def attend_window(
    m: Params, w: Widths, window: int, h: jnp.ndarray, fused: bool = False, swapped=None,
):
    """h [S, H] normed, ONE sequence at positions 0..S-1 -> (the
    attention's output [S, H], the latents [S, rank + rope]): the expanded
    form, each query over itself and the ``window - 1`` positions before
    it.  ``fused``: ``ops/flash_prefill.py``'s kernel with its window bound;
    else ``lax`` blocks, each against its band's keys alone."""
    S = h.shape[0]
    positions = jnp.arange(S)
    _, q = dsa._queries(m, w, h, positions, by_head=True, swapped=swapped)
    latents = _latents(m, w, h, positions)
    keys, values = dsa._expand(m, w, latents)
    scale = w.qk ** -0.5
    with w.named_scope("decoder/lm/attn/scores"):
        if fused:
            ctx = flash_prefill.flash_prefill(
                q, keys, values, None, scale=scale, window=window,
                interpret=jax.default_backend() != "tpu",
            )
        else:
            lows, masks = lm_common.causal_blocks(S, window)
            ctx = lm_common.attend_blocks(q, keys, values, masks, scale, lows)
    return dsa._gated_out(m, w, h, ctx), latents


def attend_window_step(
    m: Params, w: Widths, window: int, h: jnp.ndarray, tail: jnp.ndarray,
    suffix: jnp.ndarray, N: int, t: jnp.ndarray,
):
    """One token a row through a sliding layer's cache.  h [R, H] normed,
    at position N + t; tail [B, L, W]: the LAST L latents of each image's
    N-position prefix, read in place by its K = R // B rows; suffix
    [R, T, W], each row's own, written at t here.  Returns (the attention's
    output [R, H], the suffix, the positions a row attends).  The absorbed
    form (``glm_moe_dsa._absorbed``) under the band: position p sees
    p - (window - 1) ... p, of the tail those it has not slid past, one
    mask for all rows."""
    L, T = tail.shape[1], suffix.shape[1]
    position = (N + t)[None]
    _, q = dsa._queries(m, w, h[:, None], position)
    suffix = jax.lax.dynamic_update_slice(
        suffix, _latents(m, w, h[:, None], position), (0, t, 0)
    )
    # tail entry j is position N - L + j, suffix entry s position N + s
    own = jnp.arange(T)
    seen = jnp.concatenate([jnp.arange(L) > L + t - window, (own <= t) & (own > t - window)])
    ctx = dsa._absorbed(m, w, q, tail, suffix, seen[None])
    return dsa._gated_out(m, w, h, ctx), suffix, jnp.sum(seen, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# whole sequences, one image at a time
# ---------------------------------------------------------------------------


def _one_sequence(
    lm: Params, config: Config, x: jnp.ndarray, tail: int, fused: bool = False, swapped=None,
):
    """x [S, H] -> (hidden of the last ``tail`` positions, what each layer
    keeps of the sequence (a full layer its latents [S, 576], a sliding
    layer its last ``_kept`` [.., 1088]), indexer keys per full layer,
    tokens per expert [moe layers, E], experts chosen [S, moe layers * k],
    pairs [6]).  ``swapped``: every layer's ``_swapped_query_map``, or None."""
    c = config
    S = x.shape[0]
    full, sliding = widths(c)
    latents, index_keys, counts, routes, held = [], [], [], [], []
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        h = rms_norm(x, p["operator_norm"], c.norm_eps)
        lifted = None if swapped is None else swapped[i]
        if _sliding(c, i):
            y, kept = attend_window(
                p["self_attn"], sliding, c.sliding_window_size, h, fused, lifted
            )
            kept = kept[S - _kept(c, S):]
        else:
            y, kept, keys, _ = dsa.attend_sequence(p["self_attn"], full, h, None, fused, lifted)
            index_keys.append(keys)
        x = x + y
        latents.append(kept)
        x, sizes, experts, pairs = lm_common.ffn(p, c, i, x, dsa._SUM_EPS)
        if sizes is not None:
            counts.append(sizes), routes.append(experts), held.append(pairs)
    return (
        x[S - tail:], tuple(latents), tuple(index_keys), lm_common.stack_counts(counts),
        lm_common.join_routes(routes, (S,)), _sum_pairs(held),
    )


def sequence_forward(
    lm: Params, config: Config, x: jnp.ndarray, tail: int = 0, fused: bool = False,
):
    """x [B, S, H] bfloat16 -> ``_one_sequence``'s results, image by image,
    as ``glm_moe_dsa.sequence_forward``'s: the layers' swapped query maps
    made once, outside the loop over the images, each at its kind's widths."""
    kinds = widths(config)
    swapped = []
    for i in range(config.num_hidden_layers):
        w = kinds[_sliding(config, i)]
        with w.named_scope("decoder/lm/attn/q"):
            swapped.append(dsa._swapped_query_map(lm["layers"][layer_name(i)]["self_attn"], w))
    swapped = tuple(swapped)
    hidden, latents, index_keys, counts, routes, pairs = jax.lax.map(
        lambda one: _one_sequence(lm, config, one, tail, fused, swapped), x
    )
    return (
        hidden, DsaCache(latents, index_keys), jnp.sum(counts, axis=0), routes,
        jnp.sum(pairs, axis=0),
    )


def teacher_forced(
    params: Params, config: Config, contexts: jnp.ndarray, sentences: jnp.ndarray,
) -> jnp.ndarray:
    """logits [B, T, V]: the input at caption step t is sentences[:, t-1]
    (``<start>`` = 0 at t = 0), after the N prefix positions."""
    lm = params["lm"]
    x = lm_common.sequence_inputs(params, contexts, sentences)
    hidden = sequence_forward(lm, config, x, tail=sentences.shape[1])[0]
    return _head(lm, config, hidden)


def prefill(params: Params, config: Config, contexts: jnp.ndarray):
    """The N prefix positions of each image, once: (what the steps keep of
    them, per image: a full layer's latents and indexer keys whole, a
    sliding layer's tail; (tokens per expert, pairs, query blocks through
    the fused kernel and in all, by kind) for ``init_counters``; the experts
    every position chose [B, N, moe layers * k]).  The fused kernel where
    ``glm_moe_dsa.prefill`` takes it."""
    x = lm_common.prefix(params, contexts)
    S = x.shape[1]
    if S != config.num_ctx:
        raise ValueError(f"a prefix of {S} positions where Config.num_ctx is {config.num_ctx}")
    fused = flash_prefill.available() and S % lm_common.QUERY_BLOCK == 0
    _, state, counts, routes, pairs = sequence_forward(params["lm"], config, x, fused=fused)
    blocks, full = len(lm_common.query_blocks(S)), len(_full_layers(config))
    by_kind = blocks * jnp.array([full, config.num_hidden_layers - full], jnp.int32)
    return state, (counts, pairs, jnp.stack([by_kind * fused, by_kind], axis=1)), routes


# ---------------------------------------------------------------------------
# one token through the cache
# ---------------------------------------------------------------------------


def init_counters(prefill_counts, max_len: int) -> Counters:
    """Step 0's counters, the prefill's counts already in."""
    counts, pairs, fused = prefill_counts
    base = lm_common.init_counters(counts, max_len)
    none = jnp.zeros((2,), jnp.int32)
    return Counters(
        *base, pairs=jnp.stack([pairs, jnp.zeros_like(pairs)]), attended=none, window=none,
        fused=fused,
    )


def start_beams(config: Config, prefix: DsaCache, K: int, max_len: int, tile) -> DsaCache:
    """The per-beam cache of the K beams of each image before the first
    step: an empty suffix of ``max_len`` latents a layer, as wide as the
    layer's kind keeps them, and of indexer keys a full layer; an empty
    record of routes and of chosen positions.  Nothing of the prefix is per
    beam.  A suffix is kept whole, all ``max_len`` steps of it, and masked
    by the window like the tail (a caption is shorter than the window; the
    ring a longer generation wants is the serve path's to bring)."""
    c = config
    rows = prefix.latents[0].shape[0] * K
    zeros = lambda w: jnp.zeros((rows, max_len, w), jnp.bfloat16)  # noqa: E731
    kinds = widths(c)
    full = len(_full_layers(c))
    return DsaCache(
        latents=tuple(
            zeros(kinds[_sliding(c, i)].kv_rank + kinds[_sliding(c, i)].rope)
            for i in range(c.num_hidden_layers)
        ),
        index_keys=tuple(zeros(c.index_head_dim) for _ in range(full)),
        routes=lm_common.empty_routes(c, rows, max_len),
        selected=jnp.zeros((rows, max_len * full * dsa._chosen_width(c, max_len)), jnp.int32),
    )


def step(
    params: Params, config: Config, prefix: DsaCache, cache: DsaCache,
    counters: Counters, last_word: jnp.ndarray,
):
    """One token for each of R = B*K beams.  prefix: what ``prefill`` kept
    per image; cache: the beams' own; last_word [R] int32 at position
    N + t.  Returns (cache, counters, logits [R, V] float32)."""
    c = config
    lm = params["lm"]
    x = lm_common.embed(lm, last_word)                      # [R, H]
    R, N, t = x.shape[0], c.num_ctx, counters.t
    full, sliding = widths(c)
    latents, index_keys, counts, routes, held, records = [], [], [], [], [], []
    attended, in_window = jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32)
    visible = R * (N + t + 1)
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        h = rms_norm(x, p["operator_norm"], c.norm_eps)
        if _sliding(c, i):
            y, lat, seen = attend_window_step(
                p["self_attn"], sliding, c.sliding_window_size, h, prefix.latents[i],
                cache.latents[i], N, t,
            )
            in_window = in_window + jnp.stack([R * seen, visible]).astype(jnp.int32)
        else:
            f = len(index_keys)
            y, (lat, keys), chosen = dsa.attend_step(
                p["self_attn"], full, h, (prefix.latents[i], prefix.index_keys[f]),
                (cache.latents[i], cache.index_keys[f]), t, None,
            )
            index_keys.append(keys)
            records.append(jnp.where(chosen[1], chosen[0], -1))
            attended = attended + jnp.stack(
                [jnp.sum(chosen[1], dtype=jnp.int32), visible]
            ).astype(jnp.int32)
        x = x + y
        latents.append(lat)
        x, sizes, experts, pairs = lm_common.ffn(p, c, i, x, dsa._SUM_EPS)
        if sizes is not None:
            counts.append(sizes), routes.append(experts), held.append(pairs)
    with jax.named_scope("decoder/lm/attn/select"):
        selected = lm_common.write_at_step(cache.selected, jnp.concatenate(records, axis=-1), t)
    base, taken = lm_common.record_step(
        lm_common.StepCounters(t, counters.moe_counts, counters.step_visits),
        cache.routes, counts, routes, visited=[h.visited for h in held] or None,
    )
    counters = Counters(
        *base, pairs=counters.pairs.at[1].add(_sum_pairs(held)),
        attended=counters.attended + attended, window=counters.window + in_window,
        fused=counters.fused,
    )
    return (
        DsaCache(tuple(latents), tuple(index_keys), taken, selected), counters,
        _head(lm, c, x),
    )


def report(config: Config, prefix: DsaCache, state, B: int, K: int, T: int) -> dict:
    """What this decoder adds to ``BeamResult.decoder_stats``: ``prefix``
    what the steps closed over per image, ``state`` the search's final
    ``StepState``.  ``glm_moe_dsa.report``'s entries, and the window
    layers'."""
    c = config
    full = len(_full_layers(c))
    # the bytes of the sliding layers' own leaves, per image and per beam,
    # as ``state_bytes`` counts every leaf: a layer that kept its whole
    # prefix would show here whatever ``sliding_window_size`` says
    window_bytes = sum(
        leaves[i].size * leaves[i].dtype.itemsize
        for leaves in (prefix.latents, state.beam.latents)
        for i in range(c.num_hidden_layers) if _sliding(c, i)
    )
    return {
        "step_selected": state.beam.selected.reshape(B, K, T, full, -1),
        "moe_pairs": state.shared.pairs[:, :3],
        "moe_combine": state.shared.pairs[:, 3:],
        "dsa_attended": state.shared.attended,
        # [2] positions attended, positions visible (steps, sliding layers)
        "swa_attended": state.shared.window,
        # [2] over both kinds, as glm_moe_dsa reports it; [2, 2] by kind
        # (full, sliding): blocks through the fused kernel, blocks in all
        "prefill_fused_blocks": jnp.sum(state.shared.fused, axis=0),
        "prefill_fused_blocks_by_kind": state.shared.fused,
        # of ``state_bytes``: what the window layers hold, the rest the full layers'
        "state_bytes_window": jnp.float32(window_bytes),
    }
