"""GLM-5.2's block (``model_type: "glm_moe_dsa"``) as the caption decoder —
pure-functional JAX.

It is ``models/deepseek_v3.py``'s block (latent attention, small experts
beside a shared one, an untied head; the image as the first N positions of
one causal sequence) with three things of its own, and this module holds
only those; the latent, the rope, ``W_kvb``'s two halves and the head are
imported from there, the expert layer from ``models/lm_common.py``.

* a compressed query: ``qr = q_a_layernorm(u W_qa)`` (``q_lora_rank``
  wide), ``q = qr W_qb``, per head ``q_nope`` and ``q_rope`` (rotated);
  ``v_head_dim`` need not equal ``qk_nope_head_dim``;
* learned sparse attention (DeepSeek-V3.2's, "DSA").  A small INDEXER
  scores every visible position for every query,

      qI = qr W_qI          (index_n_heads x index_head_dim)
      kI = LayerNorm(u W_kI)  (index_head_dim; weight and bias, eps 1e-6)
      w  = u W_w * index_n_heads^-0.5 * index_head_dim^-0.5
      I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),   s <= t

  the first ``qk_rope_head_dim`` of each indexer head and of ``kI`` turned
  by the same interleaved rope, and the attention's softmax runs over
  ``S_t``, the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, .]``, alone.  ``Config.indexer_types`` says which layers compute a
  selection (``"full"``) and which reuse the last one computed
  (``"shared"``: no indexer parameters, IndexShare).  The indexer runs in
  bfloat16 with float32 accumulation (the source's float8 and its Hadamard
  rotation of ``qI`` and ``kI``, orthogonal and so without effect on
  ``qI . kI`` in exact arithmetic, are not taken);
* an expert layer that holds a share of its experts (``lm_common.ffn``)
  and reports what it held.

Forms.  Whole sequences (``teacher_forced``, ``prefill``) go one image at
a time (``lax.map``) and one block of ``lm_common.QUERY_BLOCK`` queries at
a time against the keys up to the block's end, in the EXPANDED form
(``lm_common.attend_blocks``): a block's scores are
``[heads, block, keys]``, and no ``[.., S, S]`` array per head exists.
The prefill, on the TPU, hands that attention to
``ops/flash_prefill.py``'s kernel, which keeps each tile of scores in
VMEM (the same arithmetic; the ``lax`` blocks are what is differentiated
and what runs anywhere else).  The selection there is a MASK: ``causal & (I[t, s] >= the
index_topk-th largest of row t)``, the threshold found exactly by 32
counting passes over the floats' order-preserving bits (``_kth_largest``;
no sort); a block whose keys number ``index_topk`` or fewer attends all it
sees and computes no scores of the indexer.  One token through the cache
(``step``) takes ONE ``lax.top_k`` a row over the indexer's scores of the
image's prefix keys and the row's own suffix keys and runs the ABSORBED
form over the chosen latents alone, the choice a mask over the image's
prefix (read in place once per image, never tiled, never gathered: its K
beams choose more positions between them than it has) and over the row's
suffix: no key or value is expanded in a step.

The cache: per IMAGE the prefix's latents ``[B, N, 576]`` a layer and the
indexer's keys ``[B, N, 128]`` a ``full`` layer; per BEAM a suffix of each
(``[B*K, T, ..]``), the record of routes and the record of chosen
positions, all moved by the search's per-parent reorder.  The chosen
indices go from a ``full`` layer to the ``shared`` layers after it inside
one step and are not carried across steps.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from ..ops import flash_prefill
from . import deepseek_v3, lm_common
from .deepseek_v3 import _head, _kv_b, _latents, _rope, _rope_tables, _swapped_columns
from .lm_common import Params, layer_name, mm, rms_norm
from .lm_common import sum_pairs as _sum_pairs

_INDEX_NORM_EPS = 1e-6      # the indexer key's LayerNorm
_SUM_EPS = 1e-20            # DeepSeek-V3's router, in the sum of chosen scores


class DsaCache(NamedTuple):
    """Per layer the latents ``[c ; k_rope]``, per ``full`` layer the
    indexer's keys.  As the prefix's: ``[B, N, ..]``, per image, closed
    over by the step.  As the beams' own: ``[B*K, T, ..]`` and the two
    records, every leaf moved by the search's reorder."""

    latents: Tuple[jnp.ndarray, ...]
    index_keys: Tuple[jnp.ndarray, ...]
    routes: Any = None      # lm_common.empty_routes; None for the prefix's
    # [R, T * full layers * k] int32 (step-major): the positions the beam's
    # own tokens attended, step by step; -1 where fewer than k were visible
    selected: Any = None


class DsaCounters(NamedTuple):
    """``lm_common.StepCounters`` and what this stack counts besides."""

    t: jnp.ndarray
    moe_counts: jnp.ndarray
    step_visits: jnp.ndarray
    # [2, 6] int32, the prefill then the steps: pairs held here, pairs
    # routed, pairs over the rows | rows the combine fetched, expert-layer
    # calls whose combine went through the kernel, calls in all
    pairs: jnp.ndarray
    attended: jnp.ndarray   # [2] int32: positions attended, positions visible (steps, full layers)
    fused: jnp.ndarray      # [2] int32: the prefill's query blocks through the fused kernel, in all


@dataclasses.dataclass(frozen=True, kw_only=True)
class Widths(deepseek_v3.Widths):
    """``deepseek_v3.Widths`` and what this block's attention reads
    besides: the compressed query's bottleneck and, in a layer with an
    indexer, the indexer's numbers."""

    q_rank: int
    # what multiplies the normed bottleneck, as ``kv_scale`` the latent.
    # The indexer reads the bottleneck AFTER it: a positive constant times
    # a row of I[t, .] leaves the row's order, and so the selection, alone
    q_scale: float = 1.0
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0


def widths(config: Config) -> Widths:
    c = config
    return Widths(
        **dataclasses.asdict(deepseek_v3.widths(c)), q_rank=c.q_lora_rank,
        index_heads=c.index_n_heads, index_dim=c.index_head_dim, index_topk=c.index_topk,
    )


def _full_layers(config: Config):
    return [i for i, kind in enumerate(config.indexer_types) if kind == "full"]


def _chosen_width(config: Config, max_len: int) -> int:
    """Positions a step's row attends: ``index_topk``, or all there can be."""
    return min(config.index_topk, config.num_ctx + max_len)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def layer_params(H: int, w: Widths, linear, ones, indexer: bool, gate: bool = False) -> Params:
    """ONE layer's norms and its attention's leaves at the widths ``w``
    (``lm_common.init_stack``'s ``linear`` and ``ones``): the compressed
    query, the latent, an indexer's maps in a layer that has one, a
    headwise ``gate_proj`` [H, heads] in a stack that gates."""
    m = {
        "q_a_proj": linear(H, w.q_rank),
        "q_a_layernorm": ones(w.q_rank),
        "q_b_proj": linear(w.q_rank, w.heads * w.qk),
        "kv_a_proj": linear(H, w.kv_rank + w.rope),
        "kv_a_layernorm": ones(w.kv_rank),
        "kv_b_proj": linear(w.kv_rank, w.heads * (w.nope + w.v)),
        "o_proj": linear(w.heads * w.v, H),
    }
    if indexer:
        m["indexer"] = {
            "wq_b": linear(w.q_rank, w.index_heads * w.index_dim),
            "wk": linear(H, w.index_dim),
            "k_norm_weight": ones(w.index_dim),
            "k_norm_bias": jnp.zeros((w.index_dim,), jnp.bfloat16),
            "weights_proj": linear(H, w.index_heads),
        }
    if gate:
        m["gate_proj"] = linear(H, w.heads)
    return {"operator_norm": ones(H), "ffn_norm": ones(H), "self_attn": m}


def init_params(rng: jax.Array, config: Config) -> Params:
    """``lm_common.init_stack``'s tree over this stack's layers: an indexer
    in the ``full`` ones, ``norm``."""
    c, w = config, widths(config)
    return lm_common.init_stack(
        rng, c, lambda i, linear, ones: layer_params(
            c.hidden_size, w, linear, ones, indexer=c.indexer_types[i] == "full"
        ), keys_per_layer=20, norm="norm",
    )


# ---------------------------------------------------------------------------
# the compressed query and the indexer
# ---------------------------------------------------------------------------


def _turned_lanes(w: Widths) -> int:
    """The lanes at a head's end that a whole sequence's rope rewrites: the
    rotary part widened to whole tiles of 128 lanes, or the head where it
    is narrower than that (the tests' widths)."""
    return min(w.qk, -(-w.rope // 128) * 128)


def _swapped_query_map(m: Params, w: Widths) -> jnp.ndarray:
    """``W_qb``'s rotary columns under the rope's signed swap,
    [q_lora_rank, nh, turned lanes], zero on the nope lanes among them:
    ``qr`` times it is the partner of ``q``'s rotary part, in place."""
    w_qb = m["q_b_proj"].reshape(w.q_rank, w.heads, -1)
    return _swapped_columns(w_qb[..., w.nope:], lead=_turned_lanes(w) - w.rope)


def _queries(
    m: Params, w: Widths, h: jnp.ndarray, positions: jnp.ndarray, by_head=False,
    swapped=None,
):
    """h [..., S, H] normed -> (qr [..., S, q_lora_rank], the normed
    bottleneck the indexer reads too; q [..., S, nh, nope + rope], its rope
    part rotated), bfloat16.  As written: one product, the rotary part
    split off, rolled and concatenated back; the form of a step's rows,
    where reading ``W_qb`` bounds the time.  ``by_head`` (h [S, H], one
    whole sequence): q [nh, S, nope + rope], each head's rows together,
    written so by the product itself, and the same numbers with no roll
    and nothing cut inside a tile of lanes: the rope's partner is a second
    product, ``qr`` times ``swapped`` (``_swapped_query_map``; made here
    where the caller has not made it once for all its sequences), and the
    head's last ``_turned_lanes`` turn by one multiply-add, cos 1 and sin
    0 on the nope lanes among them, written back over ``q`` in place."""
    nh, nope, rope = w.heads, w.nope, w.rope
    with w.named_scope("decoder/lm/attn/q"):
        qr = rms_norm(mm(h, m["q_a_proj"]), m["q_a_layernorm"], w.eps)
        if w.q_scale != 1.0:
            qr = qr * w.q_scale
        qr = qr.astype(jnp.bfloat16)
        if by_head:
            if swapped is None:
                swapped = _swapped_query_map(m, w)
            q, partner = (
                jnp.einsum("sr,rhd->hsd", qr, w_qb, preferred_element_type=jnp.float32)
                .astype(jnp.bfloat16)
                for w_qb in (m["q_b_proj"].reshape(w.q_rank, nh, -1), swapped)
            )
            turned = swapped.shape[-1]
            still = nope + rope - turned
            cos, sin = _rope_tables(positions, w.theta, rope, lead=turned - rope)
            q_turned = (
                q[..., still:].astype(jnp.float32) * cos + partner.astype(jnp.float32) * sin
            ).astype(jnp.bfloat16)
            return qr, jax.lax.dynamic_update_slice(q, q_turned, (0, 0, still))
        q = mm(qr, m["q_b_proj"]).reshape(h.shape[:-1] + (nh, nope + rope))
        q_rope = _rope(q[..., nope:].astype(jnp.float32), positions, w.theta)
        return qr, jnp.concatenate([q[..., :nope], q_rope.astype(jnp.bfloat16)], axis=-1)


def _index_rope(x: jnp.ndarray, positions: jnp.ndarray, w: Widths) -> jnp.ndarray:
    """x [..., S, heads, index_head_dim] float32: its first
    ``qk_rope_head_dim`` numbers turned as the attention's rotary key is."""
    return jnp.concatenate(
        [_rope(x[..., :w.rope], positions, w.theta), x[..., w.rope:]], axis=-1
    )


def _index_maps(ix: Params, widths: Widths, h: jnp.ndarray, qr: jnp.ndarray, positions):
    """h [..., S, H] normed, qr its query bottleneck -> (qI [..., S, nI, dI]
    bfloat16, kI [..., S, dI] bfloat16, w [..., S, nI] float32)."""
    c = widths
    nI, dI = c.index_heads, c.index_dim
    with jax.named_scope("decoder/lm/attn/index"):
        qI = mm(qr, ix["wq_b"]).reshape(h.shape[:-1] + (nI, dI)).astype(jnp.float32)
        qI = _index_rope(qI, positions, c).astype(jnp.bfloat16)
        k = mm(h, ix["wk"]).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + _INDEX_NORM_EPS)
        k = k * ix["k_norm_weight"].astype(jnp.float32) + ix["k_norm_bias"].astype(jnp.float32)
        kI = _index_rope(k[..., None, :], positions, c)[..., 0, :].astype(jnp.bfloat16)
        w = jnp.dot(
            h.astype(jnp.bfloat16), ix["weights_proj"], preferred_element_type=jnp.float32
        ) * (nI ** -0.5 * dI ** -0.5)
        return qI, kI, w


def _index_scores(qI: jnp.ndarray, kI: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """qI [..., S, nI, dI], kI [..., L, dI], w [..., S, nI] -> I [..., S, L]
    float32: ``sum_j w[s, j] * relu(qI[s, j] . kI[l])``."""
    with jax.named_scope("decoder/lm/attn/index"):
        dots = jnp.einsum("...sjd,...ld->...jsl", qI, kI, preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * jnp.swapaxes(w, -1, -2)[..., None], axis=-3)


def _ordered_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float32 (no NaN) -> uint32 whose unsigned order is the floats'."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(u: jnp.ndarray, k: int) -> jnp.ndarray:
    """u [..., L] uint32 -> [...] the k-th largest of each row, exactly:
    the largest threshold that k of the row reach, found bit by bit from
    the top in 32 counting passes (no sort; a row with fewer than k above
    its least value gets that value)."""

    def bit(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(u >= trial[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, trial, found)

    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))


def _select_mask(scores: jnp.ndarray, causal: jnp.ndarray, k: int) -> jnp.ndarray:
    """scores [S, L] float32, causal [S, L] -> the positions each query
    attends [S, L]: the visible ones whose score reaches the k-th largest
    visible score of its row (all of them where fewer than k are visible)."""
    with jax.named_scope("decoder/lm/attn/select"):
        u = _ordered_bits(jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf))
        return causal & (u >= _kth_largest(u, k)[..., None])


# ---------------------------------------------------------------------------
# whole sequences, one image at a time: the expanded form in blocks
# ---------------------------------------------------------------------------


def _one_mask(masks, S: int, k: int):
    """The blocks' masks as the fused kernel reads them: those that select
    (more than ``k`` keys visible), each widened to S keys, as ONE
    [queries from the first such block on, S] array; None where no block
    selects."""
    selecting = [jnp.pad(m, ((0, 0), (0, S - m.shape[1]))) for m in masks if m.shape[1] > k]
    return jnp.concatenate(selecting) if selecting else None


def _expand(m: Params, w: Widths, latents: jnp.ndarray):
    """latents [S, rank + rope] -> (keys [nh, S, nope + rope], values
    [nh, S, v]) bfloat16, head-major as ``_queries(by_head=True)``'s q.
    The rotary key all heads share is ADDED into lanes that ``W_kvb``'s key
    half, widened by zero columns, leaves at zero: keys come out of one
    product whole, the numbers a concatenation would hold."""
    rank, nope, rope = w.kv_rank, w.nope, w.rope
    with w.named_scope("decoder/lm/attn/expand"):
        kv_b = _kv_b(m, w)
        keys = (
            jnp.einsum(
                "sc,chd->hsd", latents[:, :rank],
                jnp.pad(kv_b[..., :nope], ((0, 0), (0, 0), (0, rope))),
                preferred_element_type=jnp.float32,
            ) + jnp.pad(latents[:, rank:], ((0, 0), (nope, 0))).astype(jnp.float32)
        ).astype(jnp.bfloat16)
        values = jnp.einsum(
            "sc,chd->hsd", latents[:, :rank], kv_b[..., nope:],
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)
    return keys, values


def _gated_out(m: Params, w: Widths, h: jnp.ndarray, ctx: jnp.ndarray) -> jnp.ndarray:
    """ctx [rows, nh * v], the heads' outputs side by side -> [rows, H]
    through ``W_o``; where the layer has a headwise gate (``gate_proj``
    [H, nh], Qiu et al., arXiv:2505.06708), head h of row t is first
    multiplied by ``sigmoid(u[t] W_g)[h]``, u = h the layer's normed
    input."""
    if "gate_proj" in m:
        with w.named_scope("decoder/lm/attn/gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.bfloat16), m["gate_proj"], preferred_element_type=jnp.float32
            ))
            ctx = (
                ctx.reshape(-1, w.heads, w.v).astype(jnp.float32) * gate[..., None]
            ).astype(jnp.bfloat16).reshape(ctx.shape)
    with w.named_scope("decoder/lm/attn/out"):
        return mm(ctx, m["o_proj"])


def attend_sequence(
    m: Params, widths: Widths, h: jnp.ndarray, masks, fused: bool = False, swapped=None,
):
    """h [S, H] normed, ONE sequence at positions 0..S-1 -> (the
    attention's output [S, H], the latents [S, rank + rope], the indexer's
    keys [S, dI] or None, the blocks' masks).  ``masks``: None in a layer
    with an indexer (it makes them), else those of the last such layer:
    one [block, keys up to the block's end] a block of queries.  ``fused``:
    scores, mask, softmax and weighted sum in ``ops/flash_prefill.py``'s
    kernel (the same arithmetic with the scores in VMEM; no gradient), not
    block by block in ``lax``.  ``swapped``: the layer's
    ``_swapped_query_map`` where the caller made it once for all its
    sequences; the queries come head-major with their rope's partner out
    of a product (``_queries(by_head=True)``), for kernel and ``lax``
    blocks alike."""
    c = widths
    S, _ = h.shape
    positions = jnp.arange(S)
    qr, q = _queries(m, c, h, positions, by_head=True, swapped=swapped)
    latents = _latents(m, c, h, positions)
    keys, values = _expand(m, c, latents)
    index_keys = None
    if masks is None:
        qI, index_keys, w = _index_maps(m["indexer"], c, h, qr, positions)
        masks = []
        for a, b in lm_common.query_blocks(S):
            causal = positions[a:b, None] >= positions[None, :b]
            if b <= c.index_topk:           # every visible position is among the best
                masks.append(causal)
            else:
                scores = _index_scores(qI[a:b], index_keys[:b], w[a:b])
                masks.append(_select_mask(scores, causal, c.index_topk))
    scale = c.qk ** -0.5
    with c.named_scope("decoder/lm/attn/scores"):
        if fused:
            ctx = flash_prefill.flash_prefill(
                q, keys, values, _one_mask(masks, S, c.index_topk), scale=scale,
                interpret=jax.default_backend() != "tpu",
            )
        else:
            ctx = lm_common.attend_blocks(q, keys, values, masks, scale)
    return _gated_out(m, c, h, ctx), latents, index_keys, masks


def _one_sequence(
    lm: Params, config: Config, x: jnp.ndarray, tail: int, fused: bool = False, swapped=None,
):
    """x [S, H] -> (hidden of the last ``tail`` positions, latents per
    layer, indexer keys per full layer, tokens per expert [moe layers, E],
    experts chosen [S, moe layers * k], pairs [6]).  ``swapped``: every
    layer's ``_swapped_query_map``, or None (each layer makes its own)."""
    c = config
    S = x.shape[0]
    latents, index_keys, counts, routes, held = [], [], [], [], []
    masks, w = None, widths(c)
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        h = rms_norm(x, p["operator_norm"], c.norm_eps)
        full = c.indexer_types[i] == "full"
        y, kept, keys, masks = attend_sequence(
            p["self_attn"], w, h, None if full else masks, fused,
            swapped=None if swapped is None else swapped[i],
        )
        x = x + y
        latents.append(kept)
        if full:
            index_keys.append(keys)
        x, sizes, experts, pairs = lm_common.ffn(p, c, i, x, _SUM_EPS)
        if sizes is not None:
            counts.append(sizes), routes.append(experts), held.append(pairs)
    return (
        x[S - tail:], tuple(latents), tuple(index_keys), lm_common.stack_counts(counts),
        lm_common.join_routes(routes, (S,)), _sum_pairs(held),
    )


def sequence_forward(
    lm: Params, config: Config, x: jnp.ndarray, tail: int = 0, fused: bool = False,
):
    """x [B, S, H] bfloat16 -> ``_one_sequence``'s results, image by image:
    (hidden [B, tail, H], the sequences' state (a ``DsaCache`` of
    ``[B, S, ..]`` leaves), tokens per expert [moe layers, E], experts
    chosen [B, S, moe layers * k], pairs [6]).  What depends on the
    weights alone is made here, outside the loop over the images: the
    layers' ``_swapped_query_map``."""
    with jax.named_scope("decoder/lm/attn/q"):
        swapped = tuple(
            _swapped_query_map(lm["layers"][layer_name(i)]["self_attn"], widths(config))
            for i in range(config.num_hidden_layers)
        )
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
    """The N prefix positions of each image, once: (the prefix's latents
    and indexer keys, per image; (tokens per expert, pairs, query blocks
    through the fused kernel and in all) for ``init_counters``; the experts
    every position chose [B, N, moe layers * k]).  Inference only, so its
    attention takes the fused kernel where there is one (the TPU) and the
    sequence is whole query blocks; else the ``lax`` blocks, as
    ``teacher_forced`` always does (it is differentiated)."""
    x = lm_common.prefix(params, contexts)
    S = x.shape[1]
    fused = flash_prefill.available() and S % lm_common.QUERY_BLOCK == 0
    _, state, counts, routes, pairs = sequence_forward(params["lm"], config, x, fused=fused)
    blocks = len(lm_common.query_blocks(S))
    return state, (counts, pairs, jnp.array([blocks * fused, blocks], jnp.int32)), routes


# ---------------------------------------------------------------------------
# one token through the cache: indexer, top-k, the absorbed form under its mask
# ---------------------------------------------------------------------------


def init_counters(prefill_counts, max_len: int) -> DsaCounters:
    """Step 0's counters, the prefill's counts already in."""
    counts, pairs, fused = prefill_counts
    base = lm_common.init_counters(counts, max_len)
    return DsaCounters(
        *base, pairs=jnp.stack([pairs, jnp.zeros_like(pairs)]),
        attended=jnp.zeros((2,), jnp.int32), fused=fused,
    )


def start_beams(config: Config, prefix: DsaCache, K: int, max_len: int, tile) -> DsaCache:
    """The per-beam cache of the K beams of each image before the first
    step: empty suffixes of ``max_len`` latents a layer and indexer keys a
    ``full`` layer, an empty record of routes and of chosen positions.
    Nothing of the prefix is per beam."""
    c = config
    rows = prefix.latents[0].shape[0] * K
    width = c.kv_lora_rank + c.qk_rope_head_dim
    zeros = lambda w: jnp.zeros((rows, max_len, w), jnp.bfloat16)  # noqa: E731
    full = len(_full_layers(c))
    return DsaCache(
        latents=tuple(zeros(width) for _ in range(c.num_hidden_layers)),
        index_keys=tuple(zeros(c.index_head_dim) for _ in range(full)),
        routes=lm_common.empty_routes(c, rows, max_len),
        selected=jnp.zeros((rows, max_len * full * _chosen_width(c, max_len)), jnp.int32),
    )


def _choose(scores: jnp.ndarray, k: int):
    """scores [R, L] float32, -inf where not visible -> (positions [R, k]
    int32, which of them are visible [R, k], the same choice as a mask
    [R, L]): ONE top-k a row.  The mask holds the visible positions whose
    score reaches the top-k's least value: the k positions themselves,
    unless two float32 scores tie exactly there."""
    with jax.named_scope("decoder/lm/attn/select"):
        values, positions = jax.lax.top_k(scores, k)
        attend = (scores >= values[:, -1:]) & (scores > -jnp.inf)
        return positions.astype(jnp.int32), values > -jnp.inf, attend


def _absorbed(m: Params, w: Widths, q: jnp.ndarray, pre_lat, suf_lat, attend) -> jnp.ndarray:
    """The absorbed form for one token a row.  q [R, 1, nh, nope + rope];
    pre_lat [B, L, rank + rope], latents of each IMAGE, read in place by
    its K = R // B rows; suf_lat [R, T, rank + rope], each row's own;
    attend [R or 1, L + T]: the positions a row attends.  Returns the
    heads' outputs [R, nh * v]: one softmax across the attended prefix and
    suffix positions; no key or value is expanded."""
    R, _, nh, _ = q.shape
    B, L, _ = pre_lat.shape
    K = R // B
    rank, nope = w.kv_rank, w.nope
    kv_b = _kv_b(m, w)
    with w.named_scope("decoder/lm/attn/absorb"):
        q_lat = jnp.einsum(
            "rhd,chd->rhc", q[:, 0, :, :nope], kv_b[..., :nope],
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)
    with w.named_scope("decoder/lm/attn/scores"):
        qc = jnp.concatenate([q_lat, q[:, 0, :, nope:]], axis=-1)        # [R, nh, rank + rope]
        s_pre = jnp.einsum(
            "bkhc,bnc->bkhn", qc.reshape(B, K, nh, -1), pre_lat,
            preferred_element_type=jnp.float32,
        ).reshape(R, nh, L)
        s_suf = jnp.einsum("rhc,rtc->rht", qc, suf_lat, preferred_element_type=jnp.float32)
        scores = jnp.where(
            attend[:, None], jnp.concatenate([s_pre, s_suf], axis=-1), -jnp.inf
        ) * (w.qk ** -0.5)
        probs = jax.nn.softmax(scores, axis=-1).astype(jnp.bfloat16)
        mixed = jnp.einsum(
            "bkhn,bnc->bkhc", probs[..., :L].reshape(B, K, nh, L), pre_lat[..., :rank],
            preferred_element_type=jnp.float32,
        ).reshape(R, nh, rank) + jnp.einsum(
            "rht,rtc->rhc", probs[..., L:], suf_lat[..., :rank],
            preferred_element_type=jnp.float32,
        )
    with w.named_scope("decoder/lm/attn/absorb"):
        return jnp.einsum(
            "rhc,chd->rhd", mixed.astype(jnp.bfloat16), kv_b[..., nope:],
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16).reshape(R, -1)


def attend_step(
    m: Params, widths: Widths, h: jnp.ndarray, prefix, suffix, t: jnp.ndarray,
    chosen: Optional[tuple],
):
    """One token a row through the cache.  h [R, H] normed, at position
    N + t.  prefix: (latents [B, N, W], indexer keys [B, N, dI] or None),
    per IMAGE, read in place by its K = R // B rows; suffix: the same per
    row over T positions, written at t here.  ``chosen``: None in a layer
    with an indexer, else the last such layer's ``_choose``.  Returns (the
    attention's output [R, H], the suffix, chosen).  The absorbed form over
    the latents themselves (``_absorbed``); the choice enters as a mask
    over the image's prefix (its K beams choose K x index_topk > N
    positions between them: reading the prefix once per image moves fewer
    bytes than gathering each row's own, and on the chip a gather of 49,152
    rows ran at a sixteenth of the memory's rate: PERF.md section 6)."""
    c = widths
    R = h.shape[0]
    pre_lat, pre_keys = prefix
    suf_lat, suf_keys = suffix
    B, N, _ = pre_lat.shape
    K, T = R // B, suf_lat.shape[1]
    position = (N + t)[None]
    qr, q = _queries(m, c, h[:, None], position)
    suf_lat = jax.lax.dynamic_update_slice(
        suf_lat, _latents(m, c, h[:, None], position), (0, t, 0)
    )
    if chosen is None:
        qI, kI, w = _index_maps(m["indexer"], c, h[:, None], qr, position)
        suf_keys = jax.lax.dynamic_update_slice(suf_keys, kI, (0, t, 0))
        nI, dI = qI.shape[-2:]
        over_prefix = _index_scores(
            qI.reshape(B, K, nI, dI), pre_keys, w.reshape(B, K, nI)
        ).reshape(R, N)
        over_suffix = _index_scores(qI, suf_keys, w)[:, 0]
        over_suffix = jnp.where(jnp.arange(T) <= t, over_suffix, -jnp.inf)
        chosen = _choose(
            jnp.concatenate([over_prefix, over_suffix], axis=-1), min(c.index_topk, N + T)
        )
    ctx = _absorbed(m, c, q, pre_lat, suf_lat, chosen[2])
    return _gated_out(m, c, h, ctx), (suf_lat, suf_keys), chosen


def step(
    params: Params, config: Config, prefix: DsaCache, cache: DsaCache,
    counters: DsaCounters, last_word: jnp.ndarray,
):
    """One token for each of R = B*K beams.  prefix: the per-image latents
    and indexer keys; cache: the beams' own; last_word [R] int32 at
    position N + t.  Returns (cache, counters, logits [R, V] float32)."""
    c = config
    lm = params["lm"]
    x = lm_common.embed(lm, last_word)                      # [R, H]
    N = prefix.latents[0].shape[1]
    latents, index_keys, counts, routes, held, records = [], [], [], [], [], []
    chosen, attended, full = None, jnp.zeros((2,), jnp.int32), 0
    w = widths(c)
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        h = rms_norm(x, p["operator_norm"], c.norm_eps)
        indexes = c.indexer_types[i] == "full"
        y, (lat, keys), chosen = attend_step(
            p["self_attn"], w, h,
            (prefix.latents[i], prefix.index_keys[full] if indexes else None),
            (cache.latents[i], cache.index_keys[full] if indexes else None),
            counters.t, None if indexes else chosen,
        )
        if indexes:
            index_keys.append(keys)
            records.append(jnp.where(chosen[1], chosen[0], -1))
            attended = attended + jnp.stack([
                jnp.sum(chosen[1], dtype=jnp.int32), x.shape[0] * (N + counters.t + 1),
            ]).astype(jnp.int32)
            full += 1
        x = x + y
        latents.append(lat)
        x, sizes, experts, pairs = lm_common.ffn(p, c, i, x, _SUM_EPS)
        if sizes is not None:
            counts.append(sizes), routes.append(experts), held.append(pairs)
    with jax.named_scope("decoder/lm/attn/select"):
        selected = lm_common.write_at_step(
            cache.selected, jnp.concatenate(records, axis=-1), counters.t
        )
    base, taken = lm_common.record_step(
        lm_common.StepCounters(counters.t, counters.moe_counts, counters.step_visits),
        cache.routes, counts, routes, visited=[h.visited for h in held] or None,
    )
    counters = DsaCounters(
        *base, pairs=counters.pairs.at[1].add(_sum_pairs(held)),
        attended=counters.attended + attended, fused=counters.fused,
    )
    return (
        DsaCache(tuple(latents), tuple(index_keys), taken, selected), counters,
        _head(lm, c, x),
    )


def report(config: Config, prefix: DsaCache, state, B: int, K: int, T: int) -> dict:
    """What this decoder adds to ``BeamResult.decoder_stats``: ``prefix``
    what the steps closed over per image, ``state`` the search's final
    ``StepState``."""
    full = len(_full_layers(config))
    return {
        # [B, K, T, full layers, k]: the positions each LIVE beam's tokens
        # attended, step by step along its own ancestry (-1: not visible)
        "step_selected": state.beam.selected.reshape(B, K, T, full, -1),
        # [2, 3] the prefill, the steps: pairs held here, routed, over the rows
        "moe_pairs": state.shared.pairs[:, :3],
        # [2, 3] the prefill, the steps: rows of the grouped products the
        # combine fetched, expert-layer calls through its kernel, calls
        "moe_combine": state.shared.pairs[:, 3:],
        # [2] positions attended, positions visible (steps, full layers)
        "dsa_attended": state.shared.attended,
        # [2] of a layer and image: the prefill's query blocks whose scores
        # stayed in the fused kernel, query blocks in all
        "prefill_fused_blocks": state.shared.fused,
    }
