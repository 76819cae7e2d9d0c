"""Qwen's ``qwen3_next`` block (``Qwen3-Next-80B-A3B-Instruct``) as the
caption decoder — pure-functional JAX.

The encoder's grid goes through the connector and becomes the first N
positions of ONE causal sequence (raster order), then ``<start>``, then
the caption.  ``H = hidden_size``; all norms, gates, softmaxes and the
recurrent state in float32.  A layer of kind k in {linear_attention,
full_attention} (``Config.layer_types``) is a serial pre-norm block:

    norm(x; w)  = x / sqrt(mean(x^2) + eps) * (1 + w)             every RMSNorm of the model but the gated one
    layer l     : x <- x + mixer_l(norm(x; w1)) ;  x <- x + moe(norm(x; w2))

    Gated DeltaNet (nk key heads, nv value heads, dk, dv; r = nv / nk; u the normed input):
    u W_qkvz is laid out PER KEY HEAD: [q dk | k dk | v r x dv | z r x dv] ;  u W_ba per key head [b r | a r]
    c           = silu(causal depthwise conv over time, L taps, no bias, of concat(q [nk dk], k [nk dk], v [nv dv]))
    q, k, v     = split(c) ;  a key head h serves value heads r h .. r h + r - 1
    q           = q / sqrt(sum(q^2) + 1e-6) * dk^-0.5 ;  k = k / sqrt(sum(k^2) + 1e-6)        per head
    beta        = sigmoid(b) ;  g = -exp(A_log) * softplus(a + dt_bias)                        per value head
    per value head, S in R^[dk, dv], S = 0 before position 0:
        S      <- exp(g_t) S
        d_t     = beta_t (v_t - S^T k_t)
        S      <- S + k_t d_t^T
        o_t     = S^T q_t
    y_t         = w_n * o_t / sqrt(mean(o_t^2) + eps) * silu(z_t)       per value head over dv; w_n plain, not 1 + w
    mixer       = concat_heads(y_t) W_out                                [nv dv] -> H
    state a row : S [nv, dk, dv] float32 ; the last L - 1 positions of concat(q, k, v) before the conv

    gated full attention (nh query / nkv key-value heads of d = head_dim, group = nh / nkv):
    u W_q per head [query d | gate d] ;  k = u W_k [nkv, d] ;  v = u W_v [nkv, d] ;  no bias
    q = norm(q; w_q), k = norm(k; w_k) over d ;  rope (rotate-half, rope_theta) on the FIRST
    partial_rotary_factor x d lanes of a head, the rest pass
    a[t, h]     = sum_{j <= t} softmax_j(q[t, h] . k[j, h // group] * d^-0.5) v[j, h // group]
    mixer       = (concat_h(a) * sigmoid(gate)) W_o

    expert layer (u the normed input): ``lm_common.moe_experts`` under ``scoring_func`` "softmax"
    (p = softmax(u W_r) over all experts, top-k, the chosen over their sum, no bias, no factor) with ONE
    shared expert times sigmoid(u w_g) (``shared_expert_gate``)
    logits      = norm(x_last; w_f) W_head                               untied, the held rows

This module holds only what is its own: the two mixers in their two forms,
the ``(1 + w)`` norm, the cache of three kinds and the untied head.
Connector, embedding, products, the router and the expert layer at a held
share are ``lm_common``'s.

Forms.  A Gated DeltaNet layer over a whole sequence (``prefill``,
``teacher_forced``) runs the CHUNKED form of the recurrence in chunks of
``gdn_chunk.CHUNK`` = 64 positions, as the public ``torch_chunk_gated_delta_rule``
(``ops/gdn_chunk.py`` has the equations): within a chunk the decay-masked
``K_beta K^T`` strictly below the diagonal is solved by (blocked) forward
substitution for the corrected values and keys; between chunks S is carried
with the chunk's total decay.  Its products are float32 at ``HIGHEST``, S
float32, in both of its forms.  ``prefill`` on the TPU (heads of whole lane
tiles: ``gdn_chunk.takes``) runs it as ONE Pallas kernel a layer, a
(sequence, key head) a program: q, k and v are read where the conv left
them (the heads' l2 norms are the kernel's), a chunk's scores, its solve
and S stay in VMEM, the 196 positions are three chunks of 64 and one of 8.
Everywhere else it is the ``lax`` form (whole-batch einsums, a scan over
the chunks; a sequence pads to whole chunks with ``beta = 0``, ``g = 0``,
``k = 0``, which leave S as it was): every other backend, heads the kernel
refuses, and ``teacher_forced`` on every backend, because ``train_lm``
differentiates it and a ``pallas_call`` has no transpose.  One
token a row (``step``) is the recurrence itself (``ops/gdn_step.py``): S
float32; its products with k and q are float32 multiplies and sums on the
vector unit, exact (no matrix unit, no rounding of an operand): ``S^T k``
and ``S^T q`` of the decayed state ride ONE pass over S, and
``o_t = exp(g) S^T q + (k . q) d_t`` is the updated state's product by q
without a third.  On the TPU that pass is one kernel that reads a row's S
where its PARENT's row lies and writes it in place: S is read once and
written once a layer and step; elsewhere ``lax`` gathers S by the same
sources first.

The cache (``HybridCache``).  Per image, closed over by the step: a full
layer's keys and values of the prefix ``[B, N, nkv * d]``, read in place by
the image's beams (lfm2's grouped step).  Per beam, moved by the search's
one tree-wide reorder: a DeltaNet layer's conv taps ``[R, L - 1, conv
width]``, a full layer's suffix keys and values ``[R, T, nkv * d]``, the
record of routes.  Per beam and NOT moved (``AT_SOURCE``): a DeltaNet
layer's S ``[R, nv, dk, dv]`` float32 (it and the taps start as the
prefix's, tiled a row a beam): S does not grow with the sequence and is
rewritten whole by every token, it IS the step's traffic, so the search
hands over ``source`` (the row each slot's beam descends from) and the
update reads S there.

Precision: ``lm_common``'s (bfloat16 parameters and products with float32
accumulation, a bfloat16 residual stream; norms, softmax, the router and
the gates in float32); ``A_log`` and ``dt_bias`` are float32 leaves; the
recurrent state is ``STATE_DTYPE`` = float32, as the public modelling code
keeps it.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from ..ops import gdn_chunk as gdn_chunk_op
from ..ops import gdn_step as gdn_step_op
from . import lm_common
from .lm_common import Params, layer_name, mm
from .lm_common import sum_pairs as _sum_pairs

_SUM_EPS = 0.0          # the source divides the chosen scores by their sum, nothing added
_L2_EPS = 1e-6          # the public Gated DeltaNet layer's, under the root of a head's sum of squares
STATE_DTYPE = jnp.float32       # what a row's S is kept in between steps
# sequences a pass of a whole-sequence forward: 32 x 196 positions x 10
# choices are 62,720 pairs, the most whose combine ``ops/moe_combine.py``
# takes (65,536 rows), and a quarter of a batch's temporaries
SEQUENCE_BLOCK = 32
# images whose live beam 0 hands its final S back with the results
# (``report``: what a check compares with a reference's state)
REPORT_STATE_IMAGES = 8


class HybridCache(NamedTuple):
    """Three kinds of leaf in one tree.  As the prefix's ``[B, ...]``:
    ``state`` and ``conv`` are where the beams start (tiled by
    ``start_beams``, not read by a step), ``keys`` and ``values`` the
    per-image cache the steps close over.  As the beams' own ``[B*K, ...]``:
    all of it; the search moves every leaf to the slot it gave its beam but
    ``state`` (``AT_SOURCE``), which stays in the slots the last step wrote
    and is read at ``source``."""

    state: Tuple[jnp.ndarray, ...]      # per DeltaNet layer [R, nv, dk, dv] STATE_DTYPE
    conv: Tuple[jnp.ndarray, ...]       # per DeltaNet layer [R, L - 1, conv width]: before the conv
    keys: Tuple[jnp.ndarray, ...]       # per full layer [R, positions, nkv * d]
    values: Tuple[jnp.ndarray, ...]
    routes: Any = None      # [R, T * layers * k] int32: ``lm_common.empty_routes``
    source: Any = None      # [R] int32: the row of ``state`` each row descends from (the search's)


# the fields of the beams' cache that the search leaves where they lie
# (``decoders.StepState.at_source``): a step computes
# ``new[r] = f(old[source[r]], inputs[r])``
AT_SOURCE = ("state",)


class Counters(NamedTuple):
    """``lm_common.StepCounters`` and what this stack counts besides."""

    t: jnp.ndarray
    moe_counts: jnp.ndarray
    step_visits: jnp.ndarray
    pairs: jnp.ndarray      # [2, 6]: the prefill, the steps: ``lm_common.sum_pairs``
    # [3] float32: state updates (a layer and step) through ``ops/gdn_step.py``'s
    # kernel, state updates in all, rows whose source was another slot
    fold: jnp.ndarray
    # [2] float32: the prefill's DeltaNet layers through ``ops/gdn_chunk.py``'s kernel, in all
    chunk: jnp.ndarray


def _linear(config: Config, layer: int) -> bool:
    return config.layer_types[layer] == "linear_attention"


def _gdn_dims(config: Config):
    """(nk, nv, dk, dv, value heads a key head, the conv's width)."""
    c = config
    nk, nv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    return nk, nv, dk, dv, nv // nk, 2 * nk * dk + nv * dv


def _rotary(config: Config) -> int:
    """Lanes of a full layer's head that the rope turns: the first of them."""
    return int(config.partial_rotary_factor * config.head_dim)


def norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm whose weight is kept about ZERO: times ``1 + w``; float32."""
    return lm_common.rms_norm(x, 1.0 + weight.astype(jnp.float32), eps)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, config: Config) -> Params:
    """``lm_common.init_stack``'s tree over this stack's layers, the norms'
    weights at zero (``1 + w``), the decay at ``A = 1``, ``dt_bias = 1``."""
    c = config
    H, d, nh, kv = c.hidden_size, c.head_dim, c.num_attention_heads, c.num_key_value_heads
    nk, nv, dk, dv, r, width = _gdn_dims(c)

    def zeros(n):
        return jnp.zeros((n,), jnp.bfloat16)

    def layer_params(layer, linear, ones):
        p: Params = {"input_layernorm": zeros(H), "post_attention_layernorm": zeros(H)}
        if _linear(c, layer):
            p["linear_attn"] = {
                "in_proj_qkvz": linear(H, 2 * nk * dk + 2 * nv * dv),
                "in_proj_ba": linear(H, 2 * nv),
                "conv1d": linear(c.linear_conv_kernel_dim, width),
                "A_log": jnp.zeros((nv,), jnp.float32),
                "dt_bias": jnp.ones((nv,), jnp.float32),
                "norm": ones(dv),
                "out_proj": linear(nv * dv, H),
            }
        else:
            p["self_attn"] = {
                "q_proj": linear(H, nh * 2 * d), "k_proj": linear(H, kv * d),
                "v_proj": linear(H, kv * d), "o_proj": linear(nh * d, H),
                "q_norm": zeros(d), "k_norm": zeros(d),
            }
        return p

    tree = lm_common.init_stack(rng, c, layer_params, keys_per_layer=16, norm="norm", connector_first=True)
    tree["lm"]["norm"] = zeros(H)
    return tree


# ---------------------------------------------------------------------------
# Gated DeltaNet: what both forms share
# ---------------------------------------------------------------------------


def _gdn_inputs(m: Params, config: Config, u: jnp.ndarray):
    """u [..., H] normed -> (what goes through the conv: concat(q, k, v)
    [..., conv width] bfloat16; z [..., nv, dv]; beta, g [..., nv]
    float32), un-laced from the maps' per-key-head layout."""
    nk, nv, dk, dv, r, _ = _gdn_dims(config)
    lead = u.shape[:-1]
    with jax.named_scope("decoder/lm/attn/gdn/proj"):
        qkvz = mm(u, m["in_proj_qkvz"]).reshape(lead + (nk, 2 * dk + 2 * r * dv))
        ba = jnp.dot(
            u, m["in_proj_ba"].astype(jnp.bfloat16), preferred_element_type=jnp.float32
        ).reshape(lead + (nk, 2 * r))
        mixed = jnp.concatenate([
            qkvz[..., :dk].reshape(lead + (nk * dk,)),
            qkvz[..., dk:2 * dk].reshape(lead + (nk * dk,)),
            qkvz[..., 2 * dk:2 * dk + r * dv].reshape(lead + (nv * dv,)),
        ], axis=-1)
        z = qkvz[..., 2 * dk + r * dv:].reshape(lead + (nv, dv))
    with jax.named_scope("decoder/lm/attn/gdn/gates"):
        b, a = ba[..., :r].reshape(lead + (nv,)), ba[..., r:].reshape(lead + (nv,))
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(m["A_log"].astype(jnp.float32)) * jax.nn.softplus(a + m["dt_bias"].astype(jnp.float32))
    return mixed, z, beta, g


def _gdn_heads(config: Config, conv: jnp.ndarray):
    """conv [..., conv width] float32 (after the conv and its silu) -> q, k
    [..., nk, dk] (each head over its root of squares; q times dk^-0.5),
    v [..., nv, dv], float32."""
    nk, nv, dk, dv, _, _ = _gdn_dims(config)
    lead = conv.shape[:-1]
    with jax.named_scope("decoder/lm/attn/gdn/gates"):
        q = conv[..., :nk * dk].reshape(lead + (nk, dk))
        k = conv[..., nk * dk:2 * nk * dk].reshape(lead + (nk, dk))
        v = conv[..., 2 * nk * dk:].reshape(lead + (nv, dv))
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + _L2_EPS) * (dk ** -0.5)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + _L2_EPS)
    return q, k, v


def _gdn_output(m: Params, config: Config, o: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """o [..., nv, dv] float32, z the same shape -> the mixer's output
    [..., H] bfloat16: the gated norm over dv, then ``out_proj``."""
    with jax.named_scope("decoder/lm/attn/gdn/norm"):
        y = lm_common.rms_norm(o, m["norm"], config.norm_eps) * jax.nn.silu(z.astype(jnp.float32))
        y = y.astype(jnp.bfloat16).reshape(o.shape[:-2] + (-1,))
    with jax.named_scope("decoder/lm/attn/gdn/proj"):
        return mm(y, m["out_proj"])


# ---------------------------------------------------------------------------
# Gated DeltaNet over a whole sequence: the chunked rule (``ops/gdn_chunk.py``)
# ---------------------------------------------------------------------------


def gdn_sequence(m: Params, config: Config, u: jnp.ndarray, fused: bool = False):
    """A Gated DeltaNet mixer over whole sequences u [B, S, H] (normed,
    positions 0..S-1) -> (its output [B, S, H], the state after the last
    position [B, nv, dk, dv] ``STATE_DTYPE``, the last L - 1 positions of
    what goes through the conv [B, L - 1, conv width]).  ``fused``: the
    chunked rule in ``ops/gdn_chunk.py``'s kernel (the caller has asked
    ``takes``), else in ``lax``."""
    c = config
    L = c.linear_conv_kernel_dim
    S = u.shape[1]
    mixed, z, beta, g = _gdn_inputs(m, c, u)
    with jax.named_scope("decoder/lm/attn/gdn/conv"):
        padded = jnp.pad(mixed, ((0, 0), (L - 1, 0), (0, 0)))                       # [B, S + L - 1, width]
        conv = jax.nn.silu(sum(
            padded[:, j:j + S].astype(jnp.float32) * m["conv1d"][j].astype(jnp.float32) for j in range(L)
        ))
    if fused:       # the heads' l2 norms are the kernel's: it reads q, k and v where the conv left them
        with jax.named_scope("decoder/lm/attn/gdn/scan"):
            o, state = gdn_chunk_op.gdn_chunk_kernel(
                conv, g, beta, heads=_gdn_dims(c)[:4], eps=_L2_EPS, dtype=STATE_DTYPE,
                interpret=jax.default_backend() != "tpu",
            )
    else:
        q, k, v = _gdn_heads(c, conv)
        with jax.named_scope("decoder/lm/attn/gdn/scan"):
            o, state = gdn_chunk_op.gdn_chunk_lax(q, k, v, g, beta)
    return _gdn_output(m, c, o, z), state.astype(STATE_DTYPE), padded[:, S:]


def gdn_step(
    m: Params, config: Config, u: jnp.ndarray, state: jnp.ndarray, taps: jnp.ndarray, source: jnp.ndarray, K: int,
):
    """One token a row through a Gated DeltaNet mixer: u [R, H] normed,
    ``taps`` [R, L - 1, conv width] the rows' own; ``state`` [R, nv, dk,
    dv] as the last step wrote it, row r's at ``source[r]`` (one of its
    image's K rows) -> (its output [R, H], the state and the taps after the
    token, whether ``ops/gdn_step.py``'s kernel made the update)."""
    c = config
    nk, nv, dk, dv, r, _ = _gdn_dims(c)
    mixed, z, beta, g = _gdn_inputs(m, c, u)
    with jax.named_scope("decoder/lm/attn/gdn/conv"):
        window = jnp.concatenate([taps, mixed[:, None]], axis=1)                     # [R, L, width], oldest first
        conv = jax.nn.silu(jnp.sum(window.astype(jnp.float32) * m["conv1d"].astype(jnp.float32), axis=1))
    q, k, v = _gdn_heads(c, conv)
    with jax.named_scope("decoder/lm/attn/gdn/state"):
        state, o, fused = gdn_step_op.gdn_step(state, source, q, k, v, beta, jnp.exp(g), K=K, dtype=STATE_DTYPE)
    return _gdn_output(m, c, o, z), state, window[:, 1:], fused


# ---------------------------------------------------------------------------
# gated full attention
# ---------------------------------------------------------------------------


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, rotary: int) -> jnp.ndarray:
    """x [..., S, heads, d] float32, positions [S]: rotate-half over the
    FIRST ``rotary`` lanes of a head; the rest pass."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    freqs = positions.astype(jnp.float32)[:, None] * inv[None, :]                   # [S, rotary / 2]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], axis=-1)[:, None, :]
    turned, passed = x[..., :rotary], x[..., rotary:]
    half = jnp.concatenate([-turned[..., rotary // 2:], turned[..., :rotary // 2]], axis=-1)
    return jnp.concatenate([turned * cos + half * sin, passed], axis=-1)


def _qkv(m: Params, config: Config, u: jnp.ndarray, positions: jnp.ndarray):
    """u [..., S, H] normed -> q [..., S, nh, d], k, v [..., S, kv, d]
    bfloat16 (q, k normed over the head and partly turned) and the output's
    gate [..., S, nh * d] float32 (its sigmoid taken)."""
    c = config
    d, nh, kv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
    lead = u.shape[:-1]
    with jax.named_scope("decoder/lm/attn/full/qkv"):
        qg = mm(u, m["q_proj"]).reshape(lead + (nh, 2 * d))
        q, gate = qg[..., :d], qg[..., d:]
        k = mm(u, m["k_proj"]).reshape(lead + (kv, d))
        v = mm(u, m["v_proj"]).reshape(lead + (kv, d))
        q, k = norm(q, m["q_norm"], c.norm_eps), norm(k, m["k_norm"], c.norm_eps)
    with jax.named_scope("decoder/lm/attn/full/rope"):
        q = _rope(q, positions, c.rope_theta, _rotary(c)).astype(jnp.bfloat16)
        k = _rope(k, positions, c.rope_theta, _rotary(c)).astype(jnp.bfloat16)
    with jax.named_scope("decoder/lm/attn/full/gate"):
        gate = jax.nn.sigmoid(gate.astype(jnp.float32)).reshape(lead + (nh * d,))
    return q, k, v, gate


def _gated_out(m: Params, ctx: jnp.ndarray, gate: jnp.ndarray) -> jnp.ndarray:
    """ctx [..., nh * d] float32 times the gate, through ``o_proj``."""
    with jax.named_scope("decoder/lm/attn/full/gate"):
        return mm((ctx * gate).astype(jnp.bfloat16), m["o_proj"])


def attend_sequence(m: Params, config: Config, u: jnp.ndarray):
    """A full layer over whole sequences u [B, S, H] (normed, positions
    0..S-1) -> (its output [B, S, H], keys [B, S, kv * d], values)."""
    c = config
    B, S, _ = u.shape
    d, nh, kv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
    positions = jnp.arange(S)
    q, k, v, gate = _qkv(m, c, u, positions)
    with jax.named_scope("decoder/lm/attn/full/scores"):
        scores = jnp.einsum(
            "bshgd,bthd->bhgst", q.reshape(B, S, kv, nh // kv, d), k, preferred_element_type=jnp.float32
        ) * (d ** -0.5)
        causal = positions[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum(
            "bhgst,bthd->bshgd", probs.astype(jnp.bfloat16), v, preferred_element_type=jnp.float32
        ).reshape(B, S, nh * d)
    return _gated_out(m, ctx, gate), k.reshape(B, S, kv * d), v.reshape(B, S, kv * d)


def attend_step(m: Params, config: Config, u: jnp.ndarray, prefix, suffix, t: jnp.ndarray):
    """One token a row through a full layer's cache (lfm2's grouped step):
    u [R, H] normed at position N + t; prefix (keys, values) [B, N, kv * d]
    per image, read in place by the image's K = R // B rows; suffix (keys,
    values) [R, T, kv * d], each row's own, written at t here -> (the
    layer's output [R, H], the suffix)."""
    c = config
    d, nh, kv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
    g = nh // kv
    R = u.shape[0]
    (pk, pv), (sk, sv) = prefix, suffix
    B, N = pk.shape[:2]
    K, T = R // B, sk.shape[1]
    q, k, v, gate = _qkv(m, c, u[:, None], (N + t)[None])
    with jax.named_scope("decoder/lm/attn/full/scores"):
        sk = jax.lax.dynamic_update_slice(sk, k.reshape(R, 1, kv * d), (0, t, 0))
        sv = jax.lax.dynamic_update_slice(sv, v.reshape(R, 1, kv * d), (0, t, 0))
        q = q.reshape(B, K, kv, g, d)
        s_pre = jnp.einsum(
            "bkhgd,bnhd->bkhgn", q, pk.reshape(B, N, kv, d), preferred_element_type=jnp.float32
        )
        s_own = jnp.einsum(
            "bkhgd,bkthd->bkhgt", q, sk.reshape(B, K, T, kv, d), preferred_element_type=jnp.float32
        )
        s_own = jnp.where(jnp.arange(T) <= t, s_own, -jnp.inf)
        probs = jax.nn.softmax(
            jnp.concatenate([s_pre, s_own], axis=-1) * (d ** -0.5), axis=-1
        ).astype(jnp.bfloat16)
        ctx = jnp.einsum(
            "bkhgn,bnhd->bkhgd", probs[..., :N], pv.reshape(B, N, kv, d), preferred_element_type=jnp.float32
        ) + jnp.einsum(
            "bkhgt,bkthd->bkhgd", probs[..., N:], sv.reshape(B, K, T, kv, d), preferred_element_type=jnp.float32
        )
    return _gated_out(m, ctx.reshape(R, nh * d), gate[:, 0]), (sk, sv)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def _experts(p: Params, config: Config, x: jnp.ndarray):
    """The block's second half over x [..., H]: x + the expert layer on
    ``norm(x; post_attention_layernorm)`` -> (x, tokens per expert [E],
    experts chosen [..., k], ``HeldPairs``)."""
    with jax.named_scope("decoder/lm/moe/route"):
        u = norm(x, p["post_attention_layernorm"], config.norm_eps).astype(jnp.bfloat16)
    y, counts, experts, pairs = lm_common.moe_experts(
        p["feed_forward"], config, u.reshape(-1, u.shape[-1]), _SUM_EPS
    )
    with jax.named_scope("decoder/lm/moe/combine"):
        x = x + y.reshape(x.shape).astype(x.dtype)
    return x, counts, experts.reshape(x.shape[:-1] + (-1,)), pairs


def _mixer_input(p: Params, config: Config, x: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("decoder/lm/attn/norm"):
        return norm(x, p["input_layernorm"], config.norm_eps).astype(jnp.bfloat16)


def _add(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("decoder/lm/attn/residual"):
        return x + y


def sequence_forward(lm: Params, config: Config, x: jnp.ndarray, fused: bool = False):
    """x [B, S, H] bfloat16 at positions 0..S-1 -> (hidden after the last
    layer [B, S, H], the sequences' ``HybridCache`` (a DeltaNet layer's
    final state and taps, a full layer's keys and values of every
    position), tokens per expert [layers, E], experts chosen
    [B, S, layers * k], pairs [6]), ``SEQUENCE_BLOCK`` sequences at a time
    where B is whole blocks of them.  ``fused``: ``gdn_sequence``'s."""
    B = x.shape[0]
    if B <= SEQUENCE_BLOCK or B % SEQUENCE_BLOCK:
        return _sequence_block(lm, config, x, fused)
    blocks = x.reshape((B // SEQUENCE_BLOCK, SEQUENCE_BLOCK) + x.shape[1:])
    hidden, cache, counts, routes, pairs = jax.lax.map(lambda one: _sequence_block(lm, config, one, fused), blocks)
    whole = lambda y: y.reshape((B,) + y.shape[2:])  # noqa: E731
    return (
        whole(hidden), jax.tree_util.tree_map(whole, cache), jnp.sum(counts, axis=0), whole(routes),
        jnp.sum(pairs, axis=0),
    )


def _sequence_block(lm: Params, config: Config, x: jnp.ndarray, fused: bool):
    """``sequence_forward`` over one block of sequences x [b, S, H]."""
    c = config
    B, S, _ = x.shape
    state, conv, keys, values, counts, routes, held = [], [], [], [], [], [], []
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        u = _mixer_input(p, c, x)
        if _linear(c, i):
            y, s, taps = gdn_sequence(p["linear_attn"], c, u, fused)
            state.append(s), conv.append(taps)
        else:
            y, k, v = attend_sequence(p["self_attn"], c, u)
            keys.append(k), values.append(v)
        x, sizes, experts, pairs = _experts(p, c, _add(x, y))
        counts.append(sizes), routes.append(experts), held.append(pairs)
    cache = HybridCache(tuple(state), tuple(conv), tuple(keys), tuple(values))
    return x, cache, lm_common.stack_counts(counts), lm_common.join_routes(routes, (B, S)), _sum_pairs(held)


def _head(lm: Params, config: Config, x: jnp.ndarray) -> jnp.ndarray:
    """[..., H] -> float32 logits [..., V]: the final norm, the untied head."""
    with jax.named_scope("decoder/lm/head"):
        h = norm(x, lm["norm"], config.norm_eps).astype(jnp.bfloat16)
        return jnp.einsum("...h,hv->...v", h, lm["lm_head"], preferred_element_type=jnp.float32)


def teacher_forced(
    params: Params, config: Config, contexts: jnp.ndarray, sentences: jnp.ndarray,
) -> jnp.ndarray:
    """logits [B, T, V]: the input at caption step t is sentences[:, t-1]
    (``<start>`` = 0 at t = 0), after the N prefix positions.  The chunked
    rule stays in ``lax`` here on every backend: it is differentiated."""
    lm = params["lm"]
    N = contexts.shape[1]
    x = lm_common.sequence_inputs(params, contexts, sentences)
    hidden = sequence_forward(lm, config, x)[0]
    return _head(lm, config, hidden[:, N:])


def prefill(params: Params, config: Config, contexts: jnp.ndarray):
    """The N prefix positions of each image, once: (the prefix's
    ``HybridCache`` over ``[B, ...]`` rows, (tokens per expert, pairs) for
    ``init_counters``, the experts every position chose
    [B, N, layers * k]).  The DeltaNet layers' chunked rule takes
    ``ops/gdn_chunk.py``'s kernel where there is one (the TPU, these
    heads) and the ``lax`` form elsewhere."""
    nk, nv, dk, dv, _, _ = _gdn_dims(config)
    fused = gdn_chunk_op.takes(contexts.shape[1], nk, nv, dk, dv)
    _, cache, counts, routes, pairs = sequence_forward(
        params["lm"], config, lm_common.prefix(params, contexts), fused
    )
    layers = len(cache.state)
    return cache, (counts, pairs, jnp.array([layers * fused, layers], jnp.float32)), routes


# ---------------------------------------------------------------------------
# one token through the cache
# ---------------------------------------------------------------------------


def init_counters(prefill_counts, max_len: int) -> Counters:
    """Step 0's counters, the prefill's counts already in."""
    counts, pairs, chunk = prefill_counts
    base = lm_common.init_counters(counts, max_len)
    return Counters(
        *base, pairs=jnp.stack([pairs, jnp.zeros_like(pairs)]), fold=jnp.zeros((3,), jnp.float32), chunk=chunk,
    )


def start_beams(config: Config, prefix: HybridCache, K: int, max_len: int, tile) -> HybridCache:
    """The per-beam cache of the K beams of each image before the first
    step: the prefix's states and taps ``tile``d to a row a beam (they
    start per image and then differ per beam), an empty suffix of
    ``max_len`` keys and values a full layer, an empty record of routes,
    every row its own source."""
    c = config
    rows = jax.tree_util.tree_leaves(prefix)[0].shape[0] * K
    width = c.num_key_value_heads * c.head_dim
    empty = tuple(jnp.zeros((rows, max_len, width), jnp.bfloat16) for _ in prefix.keys)
    return HybridCache(
        state=tuple(tile(x, K) for x in prefix.state), conv=tuple(tile(x, K) for x in prefix.conv),
        keys=empty, values=empty, routes=lm_common.empty_routes(c, rows, max_len),
        source=jnp.arange(rows, dtype=jnp.int32),
    )


def step(
    params: Params, config: Config, prefix: HybridCache, cache: HybridCache,
    counters: Counters, last_word: jnp.ndarray,
):
    """One token for each of R = B*K beams.  prefix: what ``prefill`` kept
    per image (its keys and values are read, its states and taps are not);
    cache: the beams' own, every leaf in its row but ``state``, which lies
    as the last step wrote it and is read at ``cache.source``; the cache
    returned holds every row's state in its row (``source`` the identity,
    until the search says where the next step's rows come from);
    last_word [R] int32 at position N + t.  Returns (cache, counters,
    logits [R, V] float32)."""
    c = config
    lm = params["lm"]
    x = lm_common.embed(lm, last_word)                      # [R, H]
    t = counters.t
    R = last_word.shape[0]
    K = R // jax.tree_util.tree_leaves(prefix)[0].shape[0]
    state, conv, keys, values, counts, routes, held, fused = [], [], [], [], [], [], [], []
    for i in range(c.num_hidden_layers):
        p = lm["layers"][layer_name(i)]
        u = _mixer_input(p, c, x)
        if _linear(c, i):
            j = len(state)
            y, s, taps, kernel = gdn_step(p["linear_attn"], c, u, cache.state[j], cache.conv[j], cache.source, K)
            state.append(s), conv.append(taps), fused.append(kernel)
        else:
            j = len(keys)
            y, (k, v) = attend_step(
                p["self_attn"], c, u, (prefix.keys[j], prefix.values[j]), (cache.keys[j], cache.values[j]), t
            )
            keys.append(k), values.append(v)
        x, sizes, experts, pairs = _experts(p, c, _add(x, y))
        counts.append(sizes), routes.append(experts), held.append(pairs)
    base, taken = lm_common.record_step(
        lm_common.StepCounters(t, counters.moe_counts, counters.step_visits),
        cache.routes, counts, routes, visited=[h.visited for h in held],
    )
    rows = jnp.arange(R, dtype=cache.source.dtype)
    moved = jnp.sum(cache.source != rows)
    counters = Counters(
        *base, pairs=counters.pairs.at[1].add(_sum_pairs(held)),
        fold=counters.fold + jnp.stack([jnp.float32(sum(fused)), jnp.float32(len(fused)), moved.astype(jnp.float32)]),
        chunk=counters.chunk,
    )
    cache = HybridCache(tuple(state), tuple(conv), tuple(keys), tuple(values), taken, rows)
    return cache, counters, _head(lm, c, x)


def _bytes(leaves) -> int:
    return sum(x.size * x.dtype.itemsize for x in leaves)


def report(config: Config, prefix: HybridCache, state, B: int, K: int, T: int) -> dict:
    """What this decoder adds to ``BeamResult.decoder_stats``: ``prefix``
    what ``prefill`` kept per image, ``state`` the search's final
    ``StepState``, its ``beam`` the whole ``HybridCache``: ``beam.state``
    as the last step wrote it, ``beam.source`` where the search's last
    choice put each beam's."""
    beam = state.beam
    recurrent = _bytes(beam.state) + _bytes(beam.conv)
    rows = min(B, REPORT_STATE_IMAGES)
    first = beam.source.reshape(B, K)[:rows, 0]             # live beam 0's row of the state, image by image
    return {
        # [prefill | steps, held | routed | over] pairs; the combine's
        # [prefill | steps, rows fetched | calls through the kernel | calls]
        "moe_pairs": state.shared.pairs[:, :3],
        "moe_combine": state.shared.pairs[:, 3:],
        # what the steps hold: per image the full layers' keys and values
        # (the prefix's states and taps only START the beams: nothing holds
        # them through the loop), per beam the whole tree
        "state_bytes": jnp.float32(
            _bytes(prefix.keys) + _bytes(prefix.values) + _bytes(jax.tree_util.tree_leaves(beam._replace(source=None)))
        ),
        # of that, S and the conv taps of the per-beam tree
        "state_bytes_recurrent": jnp.float32(recurrent),
        # [images, DeltaNet layers, nv, dk, dv]: S of live beam 0 of the
        # batch's first images as the last step left it (after the words
        # of that beam's own ancestry): a gather of these rows alone
        "final_state": jnp.stack(
            [s[first] for s in beam.state], axis=1
        ).astype(jnp.float32) if beam.state else jnp.zeros((rows, 0), jnp.float32),
        # [state updates through ``ops/gdn_step.py``'s kernel, state
        # updates in all (a layer and step), rows whose source was another
        # slot] over the steps
        "gdn_fold": state.shared.fold,
        # [the prefill's DeltaNet layers whose chunked rule ran in
        # ``ops/gdn_chunk.py``'s kernel, DeltaNet layers in all]
        "gdn_chunk": state.shared.chunk,
    }
