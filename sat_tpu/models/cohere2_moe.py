"""Cohere's ``cohere2_moe`` block (``command-a-plus-05-2026``) as the
caption decoder — pure-functional JAX.

The encoder's grid goes through the connector and becomes the first N
positions of ONE causal sequence (raster order), then ``<start>``, then
the caption.  ``H = hidden_size``, ``nh`` query heads and ``nkv``
key/value heads of ``d = head_dim``, ``group = nh / nkv``; a layer of kind
k in {sliding_attention, full_attention} (``Config.layer_types``) is a
PARALLEL block: one norm, attention and feed-forward both on the normed
input, one residual add:

    u        = (x - mean(x)) / sqrt(var(x) + eps) * w_ln         over H, float32, no bias: the layer's ONE norm
    q = u W_q  [nh, d] ;  k = u W_k  [nkv, d] ;  v = u W_v  [nkv, d]      no bias, no q/k norm
    sliding :  q, k <- rope(q), rope(k)   interleaved pairs (x_2i, x_2i+1), rope_theta, all d dims,
               position = the index in the one causal sequence
    full    :  nothing: no positional term
    s[t, j]  = q[t, h] . k[j, h // group] * d^-0.5
    seen     : full  j <= t ;   sliding  t - sliding_window_size < j <= t   (the query and the window - 1 before it)
    a[t, h]  = sum_seen softmax(s[t, .])[j] v[j, h // group]
    p        = sigmoid(u W_r) over num_experts ;  r = top-k(p) ;  w_e = p_e / sum_{e in r} p_e    float32, no bias, no factor
    routed   = sum_{e in r, e HELD} w_e W2_e (silu(u W1_e) * (u W3_e))
    shared   = 1/n sum_{s < n} W2_s (silu(u W1_s) * (u W3_s))              n = n_shared_experts, AVERAGED
    x       <- x + concat_h(a) W_o + routed + shared
    logits   = LayerNorm_f(x_last) E^T * logit_scale                       E the tied embedding

This module holds only what is its own: the parallel block, the two kinds
of grouped-query layer in their two forms, the cache of two lengths and the
tied head.  Connector, embedding, LayerNorm, products, the router and the
expert layer at a held share (``moe_experts``, on the normed ``u``) are
``lm_common``'s, as is the ``lax`` form of a whole sequence's attention;
the rope's tables and its signed swap ``deepseek_v3``'s (the same
interleaved convention).  The shared branch is
kept as ONE SwiGLU ``n * moe_intermediate_size`` wide (``shared/w1``,
``w3`` side by side, ``w2`` stacked), whose output is the n experts' sum,
divided by n after the product: the same sum as n experts apart, one
product where n would stand.

Forms.  Whole sequences go one image at a time (``lax.map``).  A sliding
layer's queries and keys turn as ``x * cos + partner(x) * sin``.  The
rope covers the whole head and d = 128 is one register's lanes, so a whole
sequence's partner is taken from the product it already is, head-major
``[heads, S, d]``, by a product with the ``[d, d]`` signed permutation
(exact: one nonzero term a sum; no second product by ``W_q`` and ``W_k``,
no roll, slice or relayout of a lane); one token's rows take the roll.
Attention on the TPU runs in ``ops/flash_prefill.py``'s kernel in its
GROUPED form (keys and values ``[nkv, S, d]``, never replicated over the
group; ``window=`` in the sliding layers, none in the full ones, no mask);
elsewhere, and where it is differentiated, in ``lax`` blocks of
``lm_common.QUERY_BLOCK`` queries, a sliding layer's against its band's
keys alone.
One token through the cache is lfm2's grouped form: every beam of an image
reads the image's keys and values in place (``bkhgd,bnhd->bkhgn``), never
tiled over the beams.

The cache (``GqaCache``): per layer keys and values ``[.., kv * d]``.  Per
image (closed over by the step): a full layer keeps the whole prefix
``[B, N, kv * d]``, a sliding layer only what a step can still see, the
prefix's LAST ``sliding_window_size - 1`` positions.  Per beam: each
layer's suffix ``[B*K, T, kv * d]`` (T below the window: kept whole and
masked; the ring a longer generation wants is the serve path's to bring)
and the record of routes, moved by the search's reorder, which looks at no
leaf's length.  A sliding layer's row at position p attends
``p - (window - 1) ... p``: those below N from the kept tail, the rest
from its own suffix.

Precision: ``lm_common``'s.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from ..ops import flash_prefill
from . import lm_common
from .deepseek_v3 import _partner, _rope, _rope_tables
from .lm_common import Params, layer_name, layer_norm, mm
from .lm_common import sum_pairs as _sum_pairs

_SUM_EPS = 0.0          # the source divides the chosen scores by their sum, nothing added


class GqaCache(NamedTuple):
    """Per layer keys and values ``[rows, positions, kv * d]``.  As the
    prefix's: per image, closed over by the step; a full layer's N
    positions long, a sliding layer's ``_kept``.  As the beams' own:
    ``[B*K, T, kv * d]`` and the record of routes, reordered by parent."""

    keys: Tuple[jnp.ndarray, ...]
    values: Tuple[jnp.ndarray, ...]
    routes: Any = None      # [R, T * layers * k] int32: ``lm_common.empty_routes``


class Counters(NamedTuple):
    """``lm_common.StepCounters`` and what this stack counts besides."""

    t: jnp.ndarray
    moe_counts: jnp.ndarray
    step_visits: jnp.ndarray
    pairs: jnp.ndarray      # [2, 6]: the prefill, the steps: ``lm_common.sum_pairs``
    window: jnp.ndarray     # [2] positions attended, positions visible (steps, sliding layers)
    # [2, 2] full layers, sliding layers: the prefill's query blocks
    # through the fused kernel, in all
    fused: jnp.ndarray


def _sliding(config: Config, layer: int) -> bool:
    return config.layer_types[layer] == "sliding_attention"


def _turns(config: Config, layer: int) -> bool:
    """Whether a layer's queries and keys turn by their position: a sliding
    layer's do; a full layer has no positional term."""
    return _sliding(config, layer)


def _head_dim(config: Config) -> int:
    return config.head_dim or config.hidden_size // config.num_attention_heads


def _kept(config: Config, positions: int) -> int:
    """Positions of a sliding layer's prefix that a later token can still
    see: its last ``sliding_window_size - 1``, or all it has."""
    return min(positions, config.sliding_window_size - 1)


def _scope(config: Config, layer: int, part: str):
    """A layer's attention ops: ``decoder/lm/attn/<part>``, a sliding
    layer's in the segment ``window`` (as dots3_note's)."""
    segment = "window/" if _sliding(config, layer) else ""
    return jax.named_scope(f"decoder/lm/attn/{segment}{part}")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, config: Config) -> Params:
    """``lm_common.init_stack``'s tree over this stack's layers: ONE norm a
    layer and grouped-query attention with no bias, ``norm``."""
    c = config
    H, d, nh, kv = c.hidden_size, _head_dim(c), c.num_attention_heads, c.num_key_value_heads

    def layer_params(layer, linear, ones):
        return {
            "input_norm": ones(H),
            "self_attn": {
                "q_proj": linear(H, nh * d), "k_proj": linear(H, kv * d),
                "v_proj": linear(H, kv * d), "o_proj": linear(nh * d, H),
            },
        }

    return lm_common.init_stack(
        rng, c, layer_params, keys_per_layer=12, norm="norm", connector_first=True
    )


# ---------------------------------------------------------------------------
# the block's two branches
# ---------------------------------------------------------------------------


def _experts(p: Params, config: Config, u: jnp.ndarray):
    """The feed-forward branch on the block's normed input u [T, H]: (what
    it adds [T, H] float32, tokens per expert [E], experts chosen [T, k],
    ``HeldPairs``): the routed experts held here and the shared experts'
    mean."""
    return lm_common.moe_experts(
        p["feed_forward"], config, u, _SUM_EPS, shared_mean_of=config.n_shared_experts or 1
    )


def _block(p: Params, config: Config, x: jnp.ndarray, attend):
    """The PARALLEL block over x [T, H]: ONE norm, both branches on the
    normed input, ONE residual add.  ``attend(u) -> (a [T, H], what the
    layer keeps)`` is the attention branch in the caller's form (a whole
    sequence, or one token through the cache).  Returns (the stream after
    the block, what ``attend`` kept, tokens per expert [E], experts chosen
    [T, k], ``HeldPairs``)."""
    with jax.named_scope("decoder/lm/norm"):
        u = layer_norm(x, p["input_norm"], config.norm_eps).astype(jnp.bfloat16)
    a, kept = attend(u)
    y, sizes, experts, pairs = _experts(p, config, u)
    with jax.named_scope("decoder/lm/residual"):
        x = (x.astype(jnp.float32) + a.astype(jnp.float32) + y).astype(x.dtype)
    return x, kept, sizes, experts, pairs


def _sequence_qkv(m: Params, config: Config, layer: int, u: jnp.ndarray):
    """u [S, H] normed, ONE sequence at positions 0..S-1 -> q [nh, S, d],
    k, v [kv, S, d] bfloat16, head-major as the kernel takes them; a
    sliding layer's q and k turned, ``x * cos + partner(x) * sin`` in
    float32, rounded once.  Three products a layer (q, k, v): the partner
    comes from the product x itself, ``x @ P`` inside each head's d lanes,
    P the ``[d, d]`` signed permutation (``_partner`` of the identity:
    entries 0, 1, -1), so every sum has ONE nonzero term and the partner is
    exact on the bfloat16 x.  ``deepseek_v3._sequence_queries`` multiplies
    by the map's swapped columns instead, and is right to: its rope covers
    64 lanes of a head, a third of ``W_q``'s columns, and what costs there
    is a 64-wide minor dimension.  Here the rope covers the WHOLE head, so
    that product would be all of ``W_q`` and ``W_k`` a second time (6.8 ms
    a layer and image on a v5e for q, where this swap with its
    multiply-add takes 1.3; the rolls of ``_partner`` on the whole
    ``[nh, S, d]`` 6.2: PERF.md section 6), and d = 128 is one vector
    register's lanes: the small product moves nothing across them."""
    c = config
    H = u.shape[-1]
    d, nh, kv = _head_dim(c), c.num_attention_heads, c.num_key_value_heads

    def product(name, n):
        w = m[name].reshape(H, n, d)
        return jnp.einsum("sh,hnd->nsd", u, w, preferred_element_type=jnp.float32).astype(jnp.bfloat16)

    with _scope(c, layer, "qkv"):
        q, k, v = product("q_proj", nh), product("k_proj", kv), product("v_proj", kv)
    if not _turns(c, layer):
        return q, k, v
    with _scope(c, layer, "rope"):
        cos, sin = _rope_tables(jnp.arange(u.shape[0]), c.rope_theta, d)
        swap = _partner(jnp.eye(d, dtype=jnp.bfloat16))

        def turned(x):
            partner = jnp.einsum("nsd,de->nse", x, swap, preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) * cos + partner * sin).astype(jnp.bfloat16)

        return turned(q), turned(k), v


def attend_sequence(m: Params, config: Config, layer: int, u: jnp.ndarray, fused: bool = False):
    """The attention branch over ONE sequence u [S, H] (normed, positions
    0..S-1) -> (its output [S, H], (keys [S, kv * d], values [S, kv * d])).
    ``fused``: ``ops/flash_prefill.py``'s kernel, grouped, with its window
    bound in a sliding layer; else the ``lax`` blocks."""
    c = config
    d = _head_dim(c)
    S = u.shape[0]
    q, k, v = _sequence_qkv(m, c, layer, u)
    window = c.sliding_window_size if _sliding(c, layer) else None
    with _scope(c, layer, "scores"):
        if fused:
            ctx = flash_prefill.flash_prefill(
                q, k, v, None, scale=d ** -0.5, window=window,
                interpret=jax.default_backend() != "tpu",
            )
        else:
            lows, masks = lm_common.causal_blocks(S, window)
            ctx = lm_common.attend_blocks(q, k, v, masks, d ** -0.5, lows)
    with _scope(c, layer, "out"):
        flat = lambda x: jnp.swapaxes(x, 0, 1).reshape(S, -1)  # noqa: E731
        return mm(ctx, m["o_proj"]), (flat(k), flat(v))


def attend_step(
    m: Params, config: Config, layer: int, u: jnp.ndarray, prefix, suffix, t: jnp.ndarray,
):
    """One token a row through a layer's cache.  u [R, H] normed, at
    position N + t; prefix (keys, values) [B, L, kv * d]: what the layer
    kept of each image's N-position prefix (a full layer all of it, a
    sliding layer its last L), read in place by the image's K = R // B
    rows; suffix (keys, values) [R, T, kv * d], each row's own, written at t
    here.  Returns (the attention's output [R, H], the suffix, the
    positions a row attends).  A sliding layer's position p sees
    p - (window - 1) ... p: of the kept tail those it has not slid past,
    one mask for all rows."""
    c = config
    d, nh, kv = _head_dim(c), c.num_attention_heads, c.num_key_value_heads
    g = nh // kv
    R = u.shape[0]
    (pk, pv), (sk, sv) = prefix, suffix
    B, L = pk.shape[:2]
    K, T, N = R // B, sk.shape[1], c.num_ctx
    with _scope(c, layer, "qkv"):
        q = mm(u, m["q_proj"]).reshape(R, 1, nh, d)
        k = mm(u, m["k_proj"]).reshape(R, 1, kv, d)
        v = mm(u, m["v_proj"]).reshape(R, 1, kv * d)
    own = jnp.arange(T)
    if _turns(c, layer):
        with _scope(c, layer, "rope"):
            position = (N + t)[None]
            q = _rope(q.astype(jnp.float32), position, c.rope_theta).astype(jnp.bfloat16)
            k = _rope(k.astype(jnp.float32), position, c.rope_theta).astype(jnp.bfloat16)
    if _sliding(c, layer):
        # tail entry j is position N - L + j, suffix entry s position N + s
        window = c.sliding_window_size
        seen_pre = jnp.arange(L) > L + t - window
        seen_own = (own <= t) & (own > t - window)
    else:
        seen_pre, seen_own = jnp.ones((L,), bool), own <= t
    with _scope(c, layer, "scores"):
        sk = jax.lax.dynamic_update_slice(sk, k.reshape(R, 1, kv * d), (0, t, 0))
        sv = jax.lax.dynamic_update_slice(sv, v, (0, t, 0))
        q = q.reshape(B, K, kv, g, d)
        # every beam of an image reads that image's prefix in place
        s_pre = jnp.einsum(
            "bkhgd,bnhd->bkhgn", q, pk.reshape(B, L, kv, d), preferred_element_type=jnp.float32
        )
        s_own = jnp.einsum(
            "bkhgd,bkthd->bkhgt", q, sk.reshape(B, K, T, kv, d),
            preferred_element_type=jnp.float32,
        )
        scores = jnp.concatenate(
            [jnp.where(seen_pre, s_pre, -jnp.inf), jnp.where(seen_own, s_own, -jnp.inf)], axis=-1
        )
        probs = jax.nn.softmax(scores * (d ** -0.5), axis=-1).astype(jnp.bfloat16)
        ctx = jnp.einsum(
            "bkhgn,bnhd->bkhgd", probs[..., :L], pv.reshape(B, L, kv, d),
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "bkhgt,bkthd->bkhgd", probs[..., L:], sv.reshape(B, K, T, kv, d),
            preferred_element_type=jnp.float32,
        )
    with _scope(c, layer, "out"):
        out = mm(ctx.astype(jnp.bfloat16).reshape(R, nh * d), m["o_proj"])
    attended = jnp.sum(seen_pre, dtype=jnp.int32) + jnp.sum(seen_own, dtype=jnp.int32)
    return out, ((sk, sv), attended)


# ---------------------------------------------------------------------------
# whole sequences, one image at a time
# ---------------------------------------------------------------------------


def _one_sequence(lm: Params, config: Config, x: jnp.ndarray, tail: int, fused: bool = False):
    """x [S, H] -> (hidden of the last ``tail`` positions, the keys and the
    values each layer keeps of the sequence (a full layer all S, a sliding
    layer its last ``_kept``), tokens per expert [layers, E], experts
    chosen [S, layers * k], pairs [6])."""
    c = config
    S = x.shape[0]
    keys, values, counts, routes, held = [], [], [], [], []
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        x, (k, v), sizes, experts, pairs = _block(
            p, c, x, lambda u: attend_sequence(p["self_attn"], c, i, u, fused)  # noqa: B023
        )
        first = S - _kept(c, S) if _sliding(c, i) else 0
        keys.append(k[first:]), values.append(v[first:])
        counts.append(sizes), routes.append(experts), held.append(pairs)
    return (
        x[S - tail:], tuple(keys), tuple(values), lm_common.stack_counts(counts),
        lm_common.join_routes(routes, (S,)), _sum_pairs(held),
    )


def sequence_forward(
    lm: Params, config: Config, x: jnp.ndarray, tail: int = 0, fused: bool = False,
):
    """x [B, S, H] bfloat16 -> ``_one_sequence``'s results, image by image:
    (hidden [B, tail, H], the sequences' state (a ``GqaCache`` of
    ``[B, .., kv * d]`` leaves), tokens per expert [layers, E], experts
    chosen [B, S, layers * k], pairs [6])."""
    hidden, keys, values, counts, routes, pairs = jax.lax.map(
        lambda one: _one_sequence(lm, config, one, tail, fused), x
    )
    return (
        hidden, GqaCache(keys, values), jnp.sum(counts, axis=0), routes, jnp.sum(pairs, axis=0),
    )


def _head(lm: Params, config: Config, x: jnp.ndarray) -> jnp.ndarray:
    """[..., H] -> float32 logits [..., V]: the final LayerNorm, the tied
    embedding, ``logit_scale``."""
    with jax.named_scope("decoder/lm/head"):
        h = layer_norm(x, lm["norm"], config.norm_eps).astype(jnp.bfloat16)
        logits = jnp.einsum(
            "...h,vh->...v", h, lm["embed_tokens"], preferred_element_type=jnp.float32
        )
        return logits if config.logit_scale == 1.0 else logits * config.logit_scale


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
    them, per image: a full layer's keys and values whole, a sliding
    layer's tail; (tokens per expert, pairs, query blocks through the fused
    kernel and in all, by kind) for ``init_counters``; the experts every
    position chose [B, N, layers * k]).  The fused kernel on the TPU (or
    under the tests' hook) where the prefix is whole blocks of queries."""
    x = lm_common.prefix(params, contexts)
    S = x.shape[1]
    if S != config.num_ctx:
        raise ValueError(f"a prefix of {S} positions where Config.num_ctx is {config.num_ctx}")
    fused = flash_prefill.available() and S % lm_common.QUERY_BLOCK == 0
    _, state, counts, routes, pairs = sequence_forward(params["lm"], config, x, fused=fused)
    sliding = sum(_sliding(config, i) for i in range(config.num_hidden_layers))
    by_kind = len(lm_common.query_blocks(S)) * jnp.array([config.num_hidden_layers - sliding, sliding], jnp.int32)
    return state, (counts, pairs, jnp.stack([by_kind * fused, by_kind], axis=1)), routes


# ---------------------------------------------------------------------------
# one token through the cache
# ---------------------------------------------------------------------------


def init_counters(prefill_counts, max_len: int) -> Counters:
    """Step 0's counters, the prefill's counts already in."""
    counts, pairs, fused = prefill_counts
    base = lm_common.init_counters(counts, max_len)
    return Counters(
        *base, pairs=jnp.stack([pairs, jnp.zeros_like(pairs)]),
        window=jnp.zeros((2,), jnp.int32), fused=fused,
    )


def start_beams(config: Config, prefix: GqaCache, K: int, max_len: int, tile) -> GqaCache:
    """The per-beam cache of the K beams of each image before the first
    step: an empty suffix of ``max_len`` keys and values a layer and an
    empty record of routes.  Nothing of the prefix is per beam."""
    c = config
    rows = prefix.keys[0].shape[0] * K
    width = c.num_key_value_heads * _head_dim(c)
    empty = tuple(
        jnp.zeros((rows, max_len, width), jnp.bfloat16) for _ in range(c.num_hidden_layers)
    )
    return GqaCache(keys=empty, values=empty, routes=lm_common.empty_routes(c, rows, max_len))


def step(
    params: Params, config: Config, prefix: GqaCache, cache: GqaCache,
    counters: Counters, last_word: jnp.ndarray,
):
    """One token for each of R = B*K beams.  prefix: what ``prefill`` kept
    per image; cache: the beams' own; last_word [R] int32 at position
    N + t.  Returns (cache, counters, logits [R, V] float32)."""
    c = config
    lm = params["lm"]
    x = lm_common.embed(lm, last_word)                      # [R, H]
    R, t = x.shape[0], counters.t
    keys, values, counts, routes, held = [], [], [], [], []
    in_window = jnp.zeros((2,), jnp.int32)
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        x, ((k, v), attended), sizes, experts, pairs = _block(
            p, c, x, lambda u: attend_step(  # noqa: B023
                p["self_attn"], c, i, u, (prefix.keys[i], prefix.values[i]),
                (cache.keys[i], cache.values[i]), t,
            ),
        )
        if _sliding(c, i):
            in_window = in_window + jnp.stack([R * attended, R * (c.num_ctx + t + 1)])
        keys.append(k), values.append(v)
        counts.append(sizes), routes.append(experts), held.append(pairs)
    base, taken = lm_common.record_step(
        lm_common.StepCounters(t, counters.moe_counts, counters.step_visits),
        cache.routes, counts, routes, visited=[h.visited for h in held],
    )
    counters = Counters(
        *base, pairs=counters.pairs.at[1].add(_sum_pairs(held)),
        window=counters.window + in_window.astype(jnp.int32), fused=counters.fused,
    )
    return GqaCache(tuple(keys), tuple(values), taken), counters, _head(lm, c, x)


def report(config: Config, prefix: GqaCache, state, B: int, K: int, T: int) -> dict:
    """What this decoder adds to ``BeamResult.decoder_stats``: ``prefix``
    what the steps closed over per image, ``state`` the search's final
    ``StepState``."""
    c = config
    # the bytes of the sliding layers' own leaves, per image and per beam,
    # as ``state_bytes`` counts every leaf: a layer that kept its whole
    # prefix would show here whatever ``sliding_window_size`` says
    window_bytes = sum(
        leaves[i].size * leaves[i].dtype.itemsize
        for cache in (prefix, state.beam) for leaves in (cache.keys, cache.values)
        for i in range(c.num_hidden_layers) if _sliding(c, i)
    )
    return {
        # [prefill | steps, held | routed | over] pairs; the combine's
        # [prefill | steps, rows fetched | calls through the kernel | calls]
        "moe_pairs": state.shared.pairs[:, :3],
        "moe_combine": state.shared.pairs[:, 3:],
        # [2] positions attended, positions visible (steps, sliding layers)
        "swa_attended": state.shared.window,
        # [2] over both kinds; [2, 2] by kind (full, sliding): blocks
        # through the fused kernel, blocks in all
        "prefill_fused_blocks": jnp.sum(state.shared.fused, axis=0),
        "prefill_fused_blocks_by_kind": state.shared.fused,
        # of ``state_bytes``: what the window layers hold, the rest the full layers'
        "state_bytes_window": jnp.float32(window_bytes),
    }
