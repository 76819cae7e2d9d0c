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
* ffn: a dense SwiGLU in the first ``num_dense_layers`` layers, else a
  mixture of ``num_experts`` SwiGLU experts: sigmoid scores, the
  ``num_experts_per_tok`` largest of ``score + expert_bias`` chosen, the
  scores at the chosen (the bias selects and never weighs) divided by
  their sum + 1e-6, times ``routed_scaling_factor``.  No capacity and no
  dropped token: the routed (token, expert) pairs are sorted by expert
  and go through a grouped product (``grouped_matmul``), which computes
  those pairs and no others.
* after the last layer ``embedding_norm``; the head is tied to the
  embedding.

Precision: parameters of the stack in bfloat16 (the source's), matmuls
bfloat16 x bfloat16 with float32 accumulation, the residual stream
bfloat16; norms, softmax and the whole router (product, sigmoid, bias,
choice: a ``hidden_size x num_experts`` product at ``HIGHEST``, so that
near-ties do not flip against the float32 reference) in float32.

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

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..config import Config

Params = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST


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


class StepCounters(NamedTuple):
    """What a step carries besides the beams' state: never reordered."""

    t: jnp.ndarray                  # () int32 caption steps taken
    moe_counts: jnp.ndarray         # [moe layers, E] int32 tokens routed
    # [moe layers, T] int32: experts that took a token at each step (an
    # expert no row chose is not read: what a step's grouped products had
    # to fetch is this many experts' maps; at step 0 every row holds
    # ``<start>``, so few are)
    step_visits: jnp.ndarray


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _is_moe(config: Config, layer: int) -> bool:
    return layer >= config.num_dense_layers


def _head_dim(config: Config) -> int:
    return config.hidden_size // config.num_attention_heads


def layer_name(layer: int) -> str:
    return f"{layer:02d}"


def init_params(rng: jax.Array, config: Config) -> Params:
    """{'connector': float32 (it trains), 'lm': the stack, bfloat16 but
    for ``expert_bias`` (a float32 buffer)}.  Normal(0.02) linear maps,
    unit norm weights: a starting point for the connector's training, not
    the source's weights (a checkpoint carries those)."""
    c = config
    H, E = c.hidden_size, c.num_experts
    hd, kv = _head_dim(c), c.num_key_value_heads
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(rng, 8 * c.num_hidden_layers + 4))

    def linear(*shape):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(bf16)

    ones = lambda n: jnp.ones((n,), bf16)  # noqa: E731
    layers: Params = {}
    for i, kind in enumerate(c.layer_types):
        p: Params = {"operator_norm": ones(H), "ffn_norm": ones(H)}
        if kind == "conv":
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
        if _is_moe(c, i):
            I = c.moe_intermediate_size
            p["feed_forward"] = {
                "gate": linear(H, E),
                "expert_bias": jnp.zeros((E,), jnp.float32),
                "w1": linear(E, H, I), "w3": linear(E, H, I), "w2": linear(E, I, H),
            }
        else:
            I = c.intermediate_size
            p["feed_forward"] = {"w1": linear(H, I), "w3": linear(H, I), "w2": linear(I, H)}
        layers[layer_name(i)] = p
    return {
        "connector": {
            "kernel": 0.02 * jax.random.normal(next(keys), (c.dim_ctx, H), jnp.float32),
            "bias": jnp.zeros((H,), jnp.float32),
        },
        "lm": {
            "embed_tokens": linear(c.vocabulary_size, H),
            "embedding_norm": ones(H),
            "layers": layers,
        },
    }


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """float32 in and out of the statistics; the caller casts."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _mm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """bfloat16 operands, float32 accumulation, bfloat16 result."""
    return jnp.dot(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.bfloat16)


def _swiglu(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)).astype(jnp.bfloat16)


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


def _route(p: Params, config: Config, h: jnp.ndarray):
    """h [T, H] normed -> (experts [T, k] int32, weights [T, k] float32),
    all of it in float32."""
    c = config
    logits = jnp.dot(
        h.astype(jnp.float32), p["gate"].astype(jnp.float32), precision=HIGHEST
    )
    scores = jax.nn.sigmoid(logits)
    choose = scores + p["expert_bias"] if c.use_expert_bias else scores
    _, experts = jax.lax.top_k(choose, c.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if c.norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts.astype(jnp.int32), weights * c.routed_scaling_factor


def _gmm_tiling(pairs: int):
    """(m, k, n) tiles of the grouped-product kernel, chosen from the one
    thing that tells its two regimes apart, the number of routed pairs: a
    prefill's are compute-bound and want big tiles; a step's few rows an
    expert are bound by reading the experts' maps, and want the whole
    contraction in one tile.  Timed on a v5e at the published widths
    (PERF.md section 6, PR 26)."""
    return (512, 2048, 512) if pairs >= 8192 else (128, 2048, 1024)


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
    tiling = _gmm_tiling(P)
    pad = -P % tiling[0]            # the kernel wants whole row tiles
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, sizes, preferred_element_type=jnp.bfloat16, tiling=tiling)
    return out[:P] if pad else out


def moe_ffn(p: Params, config: Config, x: jnp.ndarray):
    """x [T, H] -> (x + experts' weighted sum [T, H], tokens per expert
    [E] int32, experts chosen [T, k] int32).  Only the T*k routed pairs
    are computed, grouped by expert; no capacity, nothing dropped."""
    c = config
    T, H = x.shape
    k, E = c.num_experts_per_tok, c.num_experts
    with jax.named_scope("decoder/lm/moe/route"):
        h = _rms_norm(x, p["ffn_norm"], c.norm_eps).astype(jnp.bfloat16)
        experts, weights = _route(p["feed_forward"], c, h)
    f = p["feed_forward"]
    with jax.named_scope("decoder/lm/moe/dispatch"):
        flat = experts.reshape(T * k)
        order = jnp.argsort(flat, stable=True)           # pairs, by expert
        rows = h[order // k]                                # [T*k, H]
        sizes = jnp.sum(
            flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32,
        )
    with jax.named_scope("decoder/lm/moe/experts"):
        hidden = _swiglu(
            grouped_matmul(rows, f["w1"], sizes), grouped_matmul(rows, f["w3"], sizes)
        )
        out = grouped_matmul(hidden, f["w2"], sizes)
    with jax.named_scope("decoder/lm/moe/combine"):
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32)
        )
        picked = out[back].reshape(T, k, H).astype(jnp.float32)
        y = jnp.sum(picked * weights[..., None], axis=1)
        return x + y.astype(x.dtype), sizes, experts


def dense_ffn(p: Params, config: Config, x: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("decoder/lm/dense_ffn"):
        f = p["feed_forward"]
        h = _rms_norm(x, p["ffn_norm"], config.norm_eps).astype(jnp.bfloat16)
        return x + _mm(_swiglu(_mm(h, f["w1"]), _mm(h, f["w3"])), f["w2"])


def _ffn(p: Params, config: Config, layer: int, x: jnp.ndarray):
    """x [..., H] -> (y, tokens per expert [E], experts chosen [..., k]),
    the last two None in a dense layer."""
    if not _is_moe(config, layer):
        return dense_ffn(p, config, x), None, None
    y, sizes, experts = moe_ffn(p, config, x.reshape(-1, x.shape[-1]))
    return y.reshape(x.shape), sizes, experts.reshape(x.shape[:-1] + (-1,))


def _conv_taps(window: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """window [..., L, H] (oldest first), taps [L, H] -> [..., H]."""
    return jnp.sum(window.astype(jnp.float32) * taps.astype(jnp.float32), axis=-2)


def _stack_counts(counts) -> jnp.ndarray:
    return jnp.stack(counts) if counts else jnp.zeros((0, 0), jnp.int32)


def _join_routes(routes, lead) -> jnp.ndarray:
    """Per expert layer [..., k] -> [..., moe layers * k] (layer-major)."""
    if not routes:
        return jnp.zeros(tuple(lead) + (0,), jnp.int32)
    return jnp.concatenate(routes, axis=-1)


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
        x, sizes, experts = _ffn(p, c, i, x)
        if sizes is not None:
            counts.append(sizes)
            routes.append(experts)
    state = BeamCache(tuple(conv_state), tuple(keys), tuple(values))
    return x, state, _stack_counts(counts), _join_routes(routes, (B, S))


def _prefix(params: Params, contexts: jnp.ndarray) -> jnp.ndarray:
    """The connector: grid [B, N, D] -> the prefix's embeddings [B, N, H]."""
    with jax.named_scope("decoder/lm/prefix"):
        p = params["connector"]
        y = jnp.dot(
            contexts.astype(jnp.bfloat16), p["kernel"].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return (y + p["bias"]).astype(jnp.bfloat16)


def _embed(lm: Params, words: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("decoder/lm/embed"):
        return lm["embed_tokens"][words]


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
    B, T = sentences.shape
    N = contexts.shape[1]
    words_in = jnp.concatenate(
        [jnp.zeros((B, 1), sentences.dtype), sentences[:, :-1]], axis=1
    )
    x = jnp.concatenate([_prefix(params, contexts), _embed(lm, words_in)], axis=1)
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


def init_counters(prefill_counts: jnp.ndarray, max_len: int) -> StepCounters:
    """Step 0's counters, the prefill's tokens per expert already in."""
    return StepCounters(
        t=jnp.int32(0), moe_counts=prefill_counts,
        step_visits=jnp.zeros(prefill_counts.shape[:1] + (max_len,), jnp.int32),
    )


def init_cache(config: Config, conv, rows: int, max_len: int) -> BeamCache:
    """The per-beam cache of ``rows`` beams before the first step: their
    conv states ``conv`` (the prefix's, tiled by the caller), an empty
    suffix of ``max_len`` keys and values per attention layer, and an
    empty record of routes."""
    c = config
    width = c.num_key_value_heads * _head_dim(c)
    n_attn = sum(kind == "full_attention" for kind in c.layer_types)
    n_moe = c.num_hidden_layers - c.num_dense_layers
    empty = tuple(jnp.zeros((rows, max_len, width), jnp.bfloat16) for _ in range(n_attn))
    routes = jnp.zeros((rows, max_len * n_moe * c.num_experts_per_tok), jnp.int32)
    return BeamCache(conv=tuple(conv), keys=empty, values=empty, routes=routes)


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
        x, sizes, experts = _ffn(p, c, i, x)
        if sizes is not None:
            counts.append(sizes)
            routes.append(experts)
    moe_counts, step_visits = counters.moe_counts, counters.step_visits
    if counts:
        sizes = jnp.stack(counts)
        moe_counts = moe_counts + sizes
        step_visits = jnp.where(
            jnp.arange(step_visits.shape[1])[None, :] == t,
            jnp.sum(sizes > 0, axis=1, dtype=jnp.int32)[:, None], step_visits,
        )
    taken = cache.routes
    if routes:
        with jax.named_scope("decoder/lm/moe/route"):
            chosen = _join_routes(routes, (R,))             # [R, moe layers * k]
            width = chosen.shape[1]
            steps = taken.shape[1] // width
            at_t = jnp.arange(steps * width) // width == t
            taken = jnp.where(at_t[None, :], jnp.tile(chosen, (1, steps)), taken)
    return (
        BeamCache(tuple(conv_state), tuple(keys), tuple(values), taken),
        StepCounters(t=t + 1, moe_counts=moe_counts, step_visits=step_visits),
        _head(lm, c, x),
    )
