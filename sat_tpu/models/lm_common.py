"""What the language-model caption decoders share (``models/lfm2.py``,
``models/deepseek_v3.py``, the stacks built on them and
``models/cohere2_moe.py``): the connector and the embedding, RMSNorm and
LayerNorm, the bfloat16 product, SwiGLU, the dense ffn, the mixture of experts (router,
the sort by expert, the grouped product, the weighted un-sort, a shared
expert where the layer has one), the counters a step carries and the
record of chosen experts.  Each stack keeps what is its own: its sequence
mixers, its cache and its head.

Precision: parameters of a stack in bfloat16 (the sources'), matmuls
bfloat16 x bfloat16 with float32 accumulation, the residual stream
bfloat16; norms, softmax and the whole router (product, sigmoid, bias,
choice: a ``hidden_size x num_experts`` product at ``HIGHEST``, so that
near-ties do not flip against the float32 reference) in float32.

The expert layer: sigmoid scores (a softmax over all the experts where
``Config.scoring_func`` says so), the ``num_experts_per_tok`` largest of
``score + expert_bias`` chosen, the scores at the chosen (the bias selects
and never weighs) divided by their sum + ``sum_eps`` (the one constant in
which the sources' routers differ: an argument), times
``routed_scaling_factor``.  No capacity and no dropped token: the routed
(token, expert) pairs are sorted by expert and go through a grouped
product (``grouped_matmul``), which computes those pairs and no others.
A layer whose ``feed_forward`` holds a ``shared`` SwiGLU sends every token
through it too, beside the routed sum (times ``sigmoid(u w_g)``, ``w_g``
the leaf ``shared/gate``, where ``Config.shared_expert_gate``).  The expert layer proper
(``moe_experts``) takes an already normed input and returns what it adds,
so that a block with ONE norm and ONE add for attention and feed-forward
alike can call it; ``moe_ffn`` is the serial block's: its own norm before,
the residual add after; ``ffn`` a serial stack's one entry a layer, dense
or expert.

An expert layer may hold a SHARE of its experts (``Config.experts_held``
from ``Config.first_expert``: one chip's of an expert-parallel
deployment): it routes over all ``num_experts``, computes the pairs that
land on its own and leaves the others out; what the absent experts would
add is another chip's to compute and nobody's here.  Every expert held
(``experts_held`` 0) is the plain case of the same lines: every pair lands
here, and the rows are the pairs.

Also here, because every stack repeated them: the draws of a stack's
parameters (``init_stack``; a stack gives one layer's mixer leaves and
norms) and the blocked ``lax`` attention of a whole sequence
(``attend_blocks``: the tests' reference of ``ops/flash_prefill.py``, the
form off the chip and the one that is differentiated).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..config import Config
from ..ops import moe_combine

Params = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST


class StepCounters(NamedTuple):
    """What a step carries besides the beams' state: never reordered."""

    t: jnp.ndarray                  # () int32 caption steps taken
    moe_counts: jnp.ndarray         # [moe layers, E] int32 tokens routed
    # [moe layers, T] int32: experts that took a token at each step (an
    # expert no row chose is not read: what a step's grouped products had
    # to fetch is this many experts' maps; at step 0 every row holds
    # ``<start>``, so few are)
    step_visits: jnp.ndarray


def is_moe(config: Config, layer: int) -> bool:
    return layer >= config.num_dense_layers


def held_experts(config: Config) -> int:
    """How many of ``num_experts`` this chip holds (``experts_held``; 0: all)."""
    return config.experts_held or config.num_experts


def layer_name(layer: int) -> str:
    return f"{layer:02d}"


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """float32 in and out of the statistics; the caller casts."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """LayerNorm with a weight and no bias (the mean taken out, then the
    variance's root): float32 in and out of the statistics; the caller
    casts."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def mm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """bfloat16 operands, float32 accumulation, bfloat16 result."""
    return jnp.dot(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.bfloat16)


def swiglu(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)).astype(jnp.bfloat16)


def route(p: Params, config: Config, h: jnp.ndarray, sum_eps: float):
    """h [T, H] normed -> (experts [T, k] int32, weights [T, k] float32),
    all of it in float32."""
    c = config
    logits = jnp.dot(
        h.astype(jnp.float32), p["gate"].astype(jnp.float32), precision=HIGHEST
    )
    scores = jax.nn.softmax(logits, axis=-1) if c.scoring_func == "softmax" else jax.nn.sigmoid(logits)
    choose = scores + p["expert_bias"] if c.use_expert_bias else scores
    _, experts = jax.lax.top_k(choose, c.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if c.norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + sum_eps)
    return experts.astype(jnp.int32), weights * c.routed_scaling_factor


# (k, n) of a grouped product -> its (m, k, n) tiles for a step's few rows
# an expert and for a prefill's many, each timed on a v5e at the published
# widths (PERF.md section 6): 2048 x 1792 experts PR 44, 2048 x 768 PR 30;
# 6144 x 2048 PR 32, not timed against others: the default's 512-row tile
# does not fit the kernel's 16 MB beside a 2048 x 1024 tile of a map, and
# an image's share here is ~128 rows an expert, so row tiles stay small;
# 4096 x 4096 PR 43 (16 experts; ms a call: a step's 96 rows over 8 experts
# (128, 4096, 512) 0.381 | (32, 2048, 1024) 0.395 | (128, 2048, 1024) 0.426
# | (128, 2048, 512) 0.466; an image's 9,216 pairs in 36,864 rows
# (256, 2048, 1024) 3.075 | (256, 1024, 1024) 3.101 | (512, 512, 2048) 3.212
# | (512, 1024, 1024) 3.219 | (512, 2048, 512) 3.325 | (1024, 512, 1024)
# 4.577; the fall-back's (512, 2048, 1024) and (128, 4096, 1024) do not fit
# the kernel's 16 MB).
# 2048 x 1792 and 1792 x 2048, PR 44 (32 experts), timed inside their
# consumers (``lfm2.step`` whole at 768 rows, 3,072 pairs routed ~96 an
# expert; three layers of the prefill at 200,704 pairs), every candidate the
# kernel's 16 MB took; PR 26's tiles marked *.  The kernel computes a ragged
# last tile whole and MASKS both operands in every visit where k sits under
# a wider tile, so tiles that divide the product win; a step fetches an
# expert's map behind ONE visit's product, so it wants the map in one tile
# (row tiles past 96 do not fit beside it: 112 rows 16.09 MB).  ms a call,
# w1 / w3 a step: (96, 2048, 1792) 0.438 | (64, 2048, 1792) 0.444 |
# (80, 2048, 1792) 0.446 | (256, 2048, 896) 0.448 |
# (128, 2048, 896) 0.453 | (32, 2048, 1792) 0.455 | (64, 2048, 896) 0.471 |
# (128, 2048, 1024)* 0.472 | (32, 2048, 896) 0.493 | (64, 2048, 1024) 0.497
# | (256, 2048, 1024) 0.501 | (128, 2048, 512) 0.502 | (256, 2048, 256)
# 0.510 | (128, 2048, 256) 0.521 | (256, 2048, 512) 0.526 | (128, 1024, 896)
# 0.535 | (128, 1024, 1792) 0.535 | (64, 2048, 512) 0.538 | (32, 2048, 512)
# 0.579 | (64, 2048, 256) 0.580 | (32, 2048, 1024) 0.628 | (32, 2048, 256)
# 0.642 | (512, 2048, 896) 0.727 | (64, 1024, 1792) 0.761;
# w2 a step: (96, 1792, 2048) 0.439 | (64, 1792, 2048) 0.446 |
# (80, 1792, 2048) 0.447 | (256, 1792, 1024) 0.452 |
# (128, 1792, 1024) 0.458 | (256, 896, 2048) 0.467 | (128, 2048, 1024)*
# 0.474 | (64, 1792, 1024) 0.480 | (256, 1792, 512) 0.481 | (128, 1792, 512)
# 0.496 | (256, 896, 1024) 0.502 | (64, 1792, 512) 0.528 | (128, 896, 2048)
# 0.532 | (32, 1792, 2048) 0.539 | (128, 896, 1024) 0.539 | (32, 1792, 512)
# 0.563 | (256, 896, 512) 0.571 | (32, 1792, 1024) 0.580 | (128, 896, 512)
# 0.588 | (512, 1792, 1024) 0.729 | (64, 896, 2048) 0.760 | (64, 896, 1024)
# 0.771 | (64, 896, 512) 0.806 | (32, 896, 2048) 1.221 | (32, 896, 1024)
# 1.239 | (32, 896, 512) 1.294;
# w1 / w3 a prefill: (256, 2048, 896) 8.18 | (512, 2048, 896) 8.25 |
# (512, 512, 1792) 9.18 | (512, 1024, 896) 9.21 | (256, 2048, 1024) 9.32 |
# (512, 2048, 512)* 9.58 | (1024, 512, 896) 9.65 | (512, 1024, 1024) 10.14 |
# (512, 2048, 256) 10.18 | (256, 1024, 1792) 10.26 | (256, 1024, 1024) 12.09;
# w2 a prefill: (256, 1792, 1024) 8.24 | (512, 1792, 1024) 8.28 |
# (512, 1792, 512) 8.42 | (256, 2048, 1024) 9.30 | (512, 896, 1024) 9.34 |
# (512, 2048, 512)* 9.56 | (1024, 896, 512) 9.95 | (512, 1024, 1024) 10.01 |
# (256, 896, 2048) 10.35 | (256, 1024, 1024) 13.07
# 2048 x 512 and 512 x 2048, PR 47 (256 experts held of 512), timed inside
# their consumers (the four layers' expert halves of ``qwen3_next.step`` at
# 384 rows: 1,920 pairs here, 7.5 an expert; three layers' over a prefill
# pass of 32 x 196 positions: 31,360 pairs here), ``scripts/gmm_tile_sweep.py
# --stack qwen3_next``; the fall-back's tiles marked *.  A step fetches 256
# maps of 2 MB a product behind a handful of rows each: 0.736 ms is 89% of
# the memory's rate for those bytes, and the row tile hardly matters; a
# prefill's 122 rows an expert want a row tile near them, not 512.  ms a
# call, w1 / w3 a step: (128, 2048, 512)* 0.737 | (64, 2048, 512) 0.743 |
# (128, 2048, 256) 0.751 | (32, 2048, 512) 0.758 | (128, 1024, 512) 0.764 |
# (32, 2048, 256) 0.793 | (256, 2048, 512) 0.800 | (16, 2048, 512) 0.804 |
# (32, 1024, 512) 0.852; w2 a step: (128, 512, 2048) 0.736 | (64, 512, 2048)
# 0.743 | (32, 512, 2048) 0.760 | (128, 512, 1024)* 0.764 | (256, 512, 2048)
# 0.803 | (16, 512, 2048) 0.807 | (32, 512, 1024) 0.809 | (32, 256, 2048)
# 0.851 | (128, 512, 512) 0.905; w1 / w3 a prefill: (256, 2048, 512) 1.203 |
# (128, 2048, 512) 1.243 | (64, 2048, 512) 1.271 | (256, 2048, 256) 1.316 |
# (256, 1024, 512) 1.642 | (128, 1024, 512) 1.761 | (512, 2048, 512)* 1.785;
# w2 a prefill: (64, 512, 2048) 1.363 | (256, 512, 2048) 1.366 |
# (128, 512, 2048) 1.412 | (256, 512, 1024) 1.485 | (128, 512, 1024) 1.607 |
# (512, 512, 2048) 1.855 | (512, 512, 1024)* 1.940
_GMM_TILES = {
    (6144, 2048): ((128, 2048, 1024), (256, 2048, 1024)),
    (2048, 6144): ((128, 2048, 1024), (256, 2048, 1024)),
    (2048, 1792): ((96, 2048, 1792), (256, 2048, 896)),
    (1792, 2048): ((96, 1792, 2048), (256, 1792, 1024)),
    (2048, 768): ((128, 2048, 768), (256, 2048, 768)),
    (768, 2048): ((128, 768, 2048), (512, 768, 2048)),
    (4096, 4096): ((128, 4096, 512), (256, 2048, 1024)),
    (2048, 512): ((128, 2048, 512), (256, 2048, 512)),
    (512, 2048): ((128, 512, 2048), (256, 512, 2048)),
}


def _gmm_tiling(pairs: int, k: int, n: int):
    """(m, k, n) tiles of the grouped-product kernel from the product's
    own shape.  The number of routed pairs tells its two regimes apart: a
    prefill's are compute-bound and want big tiles; a step's few rows an
    expert are bound by reading the experts' maps, and want the whole
    contraction in one tile.  A width that was timed takes the tiles that
    won; any other takes the contraction whole (up to 2048) and the widest
    output tile up to 1024 lanes that divides n, else n whole."""
    prefill = pairs >= 8192
    if (k, n) in _GMM_TILES:
        return _GMM_TILES[(k, n)][prefill]
    tn = next((t for t in (1024, 512, 256, 128) if n % t == 0), n)
    return (512 if prefill else 128, min(k, 2048), tn)


def grouped_matmul(rows: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray) -> jnp.ndarray:
    """rows [P, k] sorted by group, w [E, k, n], sizes [E] summing to P ->
    [P, n] bfloat16: row i times the map of ITS group, float32
    accumulation.  On the TPU the Pallas grouped-matmul kernel that ships
    with JAX (megablox ``gmm``: 2x XLA's own ``ragged_dot`` at both the
    step's and the prefill's shape on a v5e); elsewhere ``ragged_dot``."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(
            rows, w, sizes, preferred_element_type=jnp.float32
        ).astype(jnp.bfloat16)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    P = rows.shape[0]
    tiling = _gmm_tiling(P, w.shape[1], w.shape[2])
    pad = -P % tiling[0]            # the kernel wants whole row tiles
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, sizes, preferred_element_type=jnp.bfloat16, tiling=tiling)
    return out[:P] if pad else out


def shared_experts(f: Params, h: jnp.ndarray, mean_of: int = 1) -> jnp.ndarray:
    """The layer's shared branch for every token, float32: ONE SwiGLU as
    wide as all the shared experts side by side (``shared/w1``, ``w3``
    [H, n * I], ``w2`` [n * I, H]), whose output IS the sum of the n
    experts' outputs.  ``mean_of`` = n where the source averages them: the
    sum divided by n, once, after the product (exact where n is a power of
    two)."""
    with jax.named_scope("decoder/lm/moe/shared"):
        s = f["shared"]
        y = mm(swiglu(mm(h, s["w1"]), mm(h, s["w3"])), s["w2"]).astype(jnp.float32)
        return y if mean_of == 1 else y / mean_of


def shared_gate(f: Params, h: jnp.ndarray) -> jnp.ndarray:
    """What a GATED shared branch is multiplied by, a token: ``sigmoid(h
    w_g)`` [T, 1] float32, ``w_g`` the leaf ``shared/gate`` [H, 1]."""
    with jax.named_scope("decoder/lm/moe/shared"):
        return jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), f["shared"]["gate"].astype(jnp.float32), precision=HIGHEST
        ))


# a layer that holds a share of its experts sizes its grouped products for
# this many times the share a balanced router sends it
HELD_CAPACITY_FACTOR = 4


def held_pair_rows(config: Config, tokens: int) -> int:
    """Rows of a held share's grouped products for ``tokens`` tokens: the
    (token, expert) pairs that CAN land on the ``experts_held`` experts
    here.  A token's k experts are distinct, so at most
    ``tokens * min(k, held)`` do: the hard bound, and the rows of a step
    (whose rows may all hold one word, ``<start>`` at step 0, and then all
    choose alike).  Over many tokens a fitted router sends the share
    ``tokens * k * held / num_experts``; the rows are
    ``HELD_CAPACITY_FACTOR`` times that (never under 1024, never over the
    hard bound).  Pairs beyond the rows are left out and COUNTED
    (``HeldPairs.over``: it must read 0)."""
    c = config
    k, held = c.num_experts_per_tok, held_experts(c)
    share = -(-tokens * k * held // c.num_experts)
    return min(tokens * min(k, held), max(HELD_CAPACITY_FACTOR * share, 1024))


class HeldPairs(NamedTuple):
    """What a held share's layer did, int32 scalars."""

    held: jnp.ndarray       # pairs computed here
    routed: jnp.ndarray     # pairs routed, over all num_experts
    over: jnp.ndarray       # pairs that landed here beyond the rows: left out
    visited: jnp.ndarray    # experts here that took a token
    fetched: jnp.ndarray    # rows of the grouped products the combine read
    fused: jnp.ndarray      # 1 where the combine went through ops/moe_combine.py's kernel


def sum_pairs(held) -> jnp.ndarray:
    """[6] int32 of a list of ``HeldPairs``, one an expert-layer call:
    held, routed, over | fetched, calls through the combine's kernel, calls."""
    if not held:
        return jnp.zeros((6,), jnp.int32)
    return jnp.stack(
        [sum(getattr(h, name) for h in held) for name in ("held", "routed", "over", "fetched", "fused")]
        + [jnp.int32(len(held))]
    ).astype(jnp.int32)


def _combine_lax(out, order, weights, done):
    """``ops/moe_combine.py``'s contract by ``lax``: the sort inverted by a
    scatter over all T * k pairs, a row gathered for EVERY pair, the ones
    nothing wrote masked (not multiplied by a zero weight), summed over k."""
    (P, H), (T, k) = out.shape, weights.shape
    back = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32)
    )
    computed = (back < done).reshape(T, k)
    picked = out[jnp.minimum(back, P - 1)].reshape(T, k, H).astype(jnp.float32)
    return jnp.sum(jnp.where(computed[..., None], picked * weights[..., None], 0.0), axis=1)


def _combine_held(out, order, weights, done):
    """out [P, H] bfloat16 (the rows the grouped products wrote, sorted by
    expert, then rows nothing wrote), order [T * k] (row r < ``done`` is
    pair ``order[r]``'s), weights [T, k] float32 -> (y [T, H] float32: each
    token's computed pairs, weighed and summed; the rows fetched; whether
    the kernel ran).  The shapes and the backend choose: the kernel, which
    fetches the computed rows and no others, where ``moe_combine.takes``
    (on the TPU, a prefill's pairs); else the ``lax`` form, a row for every
    routed pair.  Both are differentiable, so no caller says which."""
    (P, H), (T, k) = out.shape, weights.shape
    if moe_combine.takes(T, k, H, P):
        return moe_combine.moe_combine(out, order, weights, done), done, jnp.int32(1)
    return _combine_lax(out, order, weights, done), jnp.int32(T * k), jnp.int32(0)


def moe_experts(f: Params, config: Config, h: jnp.ndarray, sum_eps: float, shared_mean_of: int = 1):
    """The expert layer on an already NORMED h [T, H] bfloat16, holding
    experts ``[first_expert, first_expert + experts_held)`` (all of them
    where ``experts_held`` is 0) -> (y [T, H] float32: the held experts'
    weighted sum, + the shared branch where the layer has one; tokens per
    expert over ALL ``num_experts`` [E] int32; experts chosen [T, k] int32;
    ``HeldPairs``).  No norm and no residual add: a serial block
    (``moe_ffn``) wraps it in its own, a parallel block shares one of each
    with its attention.  The router scores every expert; the pairs that
    land here are sorted by expert to the front of ``held_pair_rows`` rows
    and go through the grouped products, those and no others: no capacity
    among the experts here, nothing dropped; the pairs of experts
    elsewhere are left out, here as in the deployment's other chips'
    absence."""
    c = config
    T, H = h.shape
    k, E, held = c.num_experts_per_tok, c.num_experts, held_experts(c)
    P = held_pair_rows(c, T)
    with jax.named_scope("decoder/lm/moe/route"):
        experts, weights = route(f, c, h, sum_eps)
    with jax.named_scope("decoder/lm/moe/dispatch"):
        flat = experts.reshape(T * k)
        local = flat - c.first_expert
        here = (local >= 0) & (local < held)
        # pairs of the experts here first, by expert; the others after them
        order = jnp.argsort(jnp.where(here, local, held), stable=True)
        rows = h[order[:P] // k]                            # [P, H]
        counts = jnp.sum(
            flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32,
        )
        landed = jax.lax.dynamic_slice(counts, (c.first_expert,), (held,))
        # group sizes within the rows: what lies beyond them is cut off
        ends = jnp.minimum(jnp.cumsum(landed), P)
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        done = ends[-1]
    with jax.named_scope("decoder/lm/moe/experts"):
        hidden = swiglu(
            grouped_matmul(rows, f["w1"], sizes), grouped_matmul(rows, f["w3"], sizes)
        )
        out = grouped_matmul(hidden, f["w2"], sizes)
    with jax.named_scope("decoder/lm/moe/combine"):
        y, fetched, fused = _combine_held(out, order, weights, done)
    if "shared" in f:
        shared = shared_experts(f, h, shared_mean_of)
        y = y + (shared * shared_gate(f, h) if c.shared_expert_gate else shared)
    with jax.named_scope("decoder/lm/moe/combine"):
        stats = HeldPairs(
            held=done, routed=jnp.int32(T * k), over=jnp.sum(landed) - done,
            visited=jnp.sum(sizes > 0, dtype=jnp.int32), fetched=fetched, fused=fused,
        )
    return y, counts, experts, stats


def moe_ffn(p: Params, config: Config, x: jnp.ndarray, sum_eps: float):
    """The serial block's expert layer: x [T, H] -> (x +
    ``moe_experts(ffn_norm(x))`` [T, H], tokens per expert [E], experts
    chosen [T, k], ``HeldPairs``)."""
    with jax.named_scope("decoder/lm/moe/route"):
        h = rms_norm(x, p["ffn_norm"], config.norm_eps).astype(jnp.bfloat16)
    y, counts, experts, stats = moe_experts(p["feed_forward"], config, h, sum_eps)
    with jax.named_scope("decoder/lm/moe/combine"):
        return x + y.astype(x.dtype), counts, experts, stats


def dense_ffn(p: Params, config: Config, x: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("decoder/lm/dense_ffn"):
        f = p["feed_forward"]
        h = rms_norm(x, p["ffn_norm"], config.norm_eps).astype(jnp.bfloat16)
        return x + mm(swiglu(mm(h, f["w1"]), mm(h, f["w3"])), f["w2"])


def ffn(p: Params, config: Config, layer: int, x: jnp.ndarray, sum_eps: float):
    """A serial block's feed-forward, dense or expert layer: x [..., H] ->
    (y, tokens per expert [E], experts chosen [..., k], ``HeldPairs``), the
    last three None in a dense layer."""
    if not is_moe(config, layer):
        return dense_ffn(p, config, x), None, None, None
    y, counts, experts, stats = moe_ffn(p, config, x.reshape(-1, x.shape[-1]), sum_eps)
    return y.reshape(x.shape), counts, experts.reshape(x.shape[:-1] + (-1,)), stats


def ffn_params(config: Config, layer: int, linear, shared: bool = True) -> Params:
    """One layer's ``feed_forward`` leaves; ``linear(*shape)`` draws a map.
    ``shared``: an expert layer's ``shared`` SwiGLU,
    ``n_shared_experts * moe_intermediate_size`` wide (or
    ``shared_expert_intermediate_size`` where the source gives that), where
    the stack has that branch and the Config any such expert; its ``gate``
    [H, 1] where ``shared_expert_gate``."""
    c = config
    H, E, held = c.hidden_size, c.num_experts, held_experts(c)
    if not is_moe(c, layer):
        I = c.intermediate_size
        return {"w1": linear(H, I), "w3": linear(H, I), "w2": linear(I, H)}
    I = c.moe_intermediate_size
    f = {
        "gate": linear(H, E),
        "expert_bias": jnp.zeros((E,), jnp.float32),
        "w1": linear(held, H, I), "w3": linear(held, H, I), "w2": linear(held, I, H),
    }
    if not c.use_expert_bias:       # a router with no selection bias has no such leaf
        del f["expert_bias"]
    if shared and c.n_shared_experts:
        I = c.shared_expert_intermediate_size or c.n_shared_experts * c.moe_intermediate_size
        f["shared"] = {"w1": linear(H, I), "w3": linear(H, I), "w2": linear(I, H)}
        if c.shared_expert_gate:
            f["shared"]["gate"] = linear(H, 1)
    return f


def connector_params(key: jax.Array, config: Config) -> Params:
    """float32: it trains."""
    H = config.hidden_size
    return {
        "kernel": 0.02 * jax.random.normal(key, (config.dim_ctx, H), jnp.float32),
        "bias": jnp.zeros((H,), jnp.float32),
    }


def init_stack(
    rng: jax.Array, config: Config, layer_params, *, keys_per_layer: int, norm: str,
    shared: bool = True, connector_first: bool = False,
) -> Params:
    """{'connector': float32 (it trains), 'lm': the stack, bfloat16 but
    for ``expert_bias`` (a float32 buffer)}.  Normal(0.02) linear maps,
    unit norm weights: a starting point for the connector's training, not
    a source's weights (a checkpoint carries those).  The stack gives
    ``layer_params(layer, linear, ones)``: ONE layer's norms and its
    mixer's leaves (``linear(*shape)`` draws a map, ``ones(n)`` is a
    norm's weight); the layer's ``feed_forward`` (``ffn_params``), the
    embedding, the final norm under the stack's name for it, an untied
    ``lm_head`` and the connector are drawn here.  ``keys_per_layer`` (the
    stream is that many a layer + 4) and ``connector_first`` (its key
    before the embedding's, not after the head's) keep each stack's draws
    in the order they had when each stack made its own, so a seed gives
    the weights it gave."""
    c = config
    H = c.hidden_size
    keys = iter(jax.random.split(rng, keys_per_layer * c.num_hidden_layers + 4))

    def linear(*shape):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(jnp.bfloat16)

    def ones(n):
        return jnp.ones((n,), jnp.bfloat16)

    layers: Params = {}
    for i in range(c.num_hidden_layers):
        p = layer_params(i, linear, ones)
        p["feed_forward"] = ffn_params(c, i, linear, shared)
        layers[layer_name(i)] = p
    if connector_first:
        connector = connector_params(next(keys), c)
    lm: Params = {"embed_tokens": linear(c.vocabulary_size, H), norm: ones(H), "layers": layers}
    if not c.tie_word_embeddings:
        lm["lm_head"] = linear(H, c.vocabulary_size)
    if not connector_first:
        connector = connector_params(next(keys), c)
    return {"connector": connector, "lm": lm}


def prefix(params: Params, contexts: jnp.ndarray) -> jnp.ndarray:
    """The connector: grid [B, N, D] -> the prefix's embeddings [B, N, H]."""
    with jax.named_scope("decoder/lm/prefix"):
        p = params["connector"]
        y = jnp.dot(
            contexts.astype(jnp.bfloat16), p["kernel"].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return (y + p["bias"]).astype(jnp.bfloat16)


def embed(lm: Params, words: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("decoder/lm/embed"):
        return lm["embed_tokens"][words]


def sequence_inputs(params: Params, contexts: jnp.ndarray, sentences: jnp.ndarray):
    """[prefix; <start>; the sentence but its last word], embedded: the
    input at caption step t is sentences[:, t-1] (``<start>`` = 0 at
    t = 0), after the N prefix positions."""
    B = sentences.shape[0]
    words_in = jnp.concatenate(
        [jnp.zeros((B, 1), sentences.dtype), sentences[:, :-1]], axis=1
    )
    return jnp.concatenate(
        [prefix(params, contexts), embed(params["lm"], words_in)], axis=1
    )


# ---------------------------------------------------------------------------
# a whole sequence's attention by ``lax``, a block of queries at a time
# ---------------------------------------------------------------------------

# queries a block; a prefill whose sequence is whole blocks of them takes
# ``ops/flash_prefill.py``'s kernel on the TPU, and not these
QUERY_BLOCK = 512


def query_blocks(S: int):
    return [(a, min(a + QUERY_BLOCK, S)) for a in range(0, S, QUERY_BLOCK)]


def causal_blocks(S: int, window=None):
    """Per block of queries of a causal sequence: (the first key the block
    reaches back to, what its queries see as a mask [block, keys from there
    to the block's end]).  ``window``: a query sees itself and the
    ``window - 1`` positions before it, so a block's keys start that far
    before its first query; None: every position up to its own, from the
    first key."""
    lows, masks = [], []
    positions = jnp.arange(S)
    for a, b in query_blocks(S):
        low = 0 if window is None else max(a - (window - 1), 0)
        ahead = positions[a:b, None] - positions[None, low:b]
        lows.append(low)
        masks.append(ahead >= 0 if window is None else (ahead >= 0) & (ahead < window))
    return lows, masks


def attend_blocks(q, keys, values, masks, scale: float, lows=None) -> jnp.ndarray:
    """q [nh, S, d], keys [kv, S, d], values [kv, S, dv] -> [S, nh * dv]
    bfloat16: ``nh // kv`` query heads against each key/value head (grouped
    queries; kv = nh where every head has its own, as latent attention's
    expanded form).  A block of queries at a time against the keys up to
    the block's end, from ``lows``' entry for the block on (where a window
    leaves the earlier ones unseen by all of it; else from the first),
    under the block's entry of ``masks`` [block, those keys]; its float32
    scores ``[kv, group, block, keys]`` whole, one softmax a row."""
    nh, S, d = q.shape
    kv, dv = values.shape[0], values.shape[-1]
    q = q.reshape(kv, nh // kv, S, d)
    blocks = query_blocks(S)
    ctx = []
    for (a, b), mask, low in zip(blocks, masks, lows or (0,) * len(blocks)):
        scores = jnp.einsum(
            "hgsd,htd->hgst", q[:, :, a:b], keys[:, low:b], preferred_element_type=jnp.float32
        )
        scores = jnp.where(mask, scores * scale, -jnp.inf)
        # the softmax's division after the weighted sum, as the kernel's; a
        # group's heads go through the second product as rows of ONE head
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        block = jnp.einsum(
            "hqt,htd->hqd", weights.astype(jnp.bfloat16).reshape(kv, -1, b - low),
            values[:, low:b], preferred_element_type=jnp.float32,
        ).reshape(kv, nh // kv, b - a, dv) / jnp.sum(weights, axis=-1)[..., None]
        ctx.append(jnp.transpose(block, (2, 0, 1, 3)).astype(jnp.bfloat16).reshape(b - a, nh * dv))
    return jnp.concatenate(ctx, axis=0)


# ---------------------------------------------------------------------------
# counters and the record of routes
# ---------------------------------------------------------------------------


def stack_counts(counts) -> jnp.ndarray:
    return jnp.stack(counts) if counts else jnp.zeros((0, 0), jnp.int32)


def join_routes(routes, lead) -> jnp.ndarray:
    """Per expert layer [..., k] -> [..., moe layers * k] (layer-major)."""
    if not routes:
        return jnp.zeros(tuple(lead) + (0,), jnp.int32)
    return jnp.concatenate(routes, axis=-1)


def init_counters(prefill_counts: jnp.ndarray, max_len: int) -> StepCounters:
    """Step 0's counters, the prefill's tokens per expert already in."""
    return StepCounters(
        t=jnp.int32(0), moe_counts=prefill_counts,
        step_visits=jnp.zeros(prefill_counts.shape[:1] + (max_len,), jnp.int32),
    )


def empty_routes(config: Config, rows: int, max_len: int) -> jnp.ndarray:
    """[R, T * moe layers * k] int32 (step-major; one row of lanes a
    beam): the experts the beam's own tokens chose, step by step.  A
    per-beam leaf: it follows the beam through every reorder, so at the
    end a live beam holds the choices of ITS ancestry (what a
    teacher-forced pass over its caption would choose)."""
    n_moe = config.num_hidden_layers - config.num_dense_layers
    return jnp.zeros((rows, max_len * n_moe * config.num_experts_per_tok), jnp.int32)


def record_step(counters: StepCounters, taken: jnp.ndarray, counts, routes, visited=None):
    """After a step over R rows: (the counters with the step's tokens per
    expert ``counts`` (one [E] a moe layer) added and t advanced, the
    record ``taken`` with the step's choices ``routes`` (one [R, k] a moe
    layer) written at step t).  ``visited`` (one scalar a moe layer): the
    experts that took a token, where that is not every expert with a count
    (a layer that holds a share)."""
    t = counters.t
    moe_counts, step_visits = counters.moe_counts, counters.step_visits
    if counts:
        sizes = jnp.stack(counts)
        moe_counts = moe_counts + sizes
        step_visits = jnp.where(
            jnp.arange(step_visits.shape[1])[None, :] == t,
            (
                jnp.sum(sizes > 0, axis=1, dtype=jnp.int32) if visited is None
                else jnp.stack(visited)
            )[:, None], step_visits,
        )
    if routes:
        with jax.named_scope("decoder/lm/moe/route"):
            chosen = join_routes(routes, (taken.shape[0],))    # [R, moe layers * k]
            taken = write_at_step(taken, chosen, t)
    return StepCounters(t=t + 1, moe_counts=moe_counts, step_visits=step_visits), taken


def write_at_step(record: jnp.ndarray, now: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """record [R, T * w] (step-major lanes), now [R, w]: the record with
    ``now`` written at step t."""
    width = now.shape[1]
    steps = record.shape[1] // width
    at_t = jnp.arange(steps * width) // width == t
    return jnp.where(at_t[None, :], jnp.tile(now, (1, steps)), record)
