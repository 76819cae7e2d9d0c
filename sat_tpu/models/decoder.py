"""Soft-attention LSTM caption decoder — pure-functional JAX.

Re-design of the reference's build_rnn / initialize / attend / decode
(/root/reference/model.py:190-459).  The reference unrolls 20 graph copies
in Python and, at inference, runs ONE step per sess.run round-trip; here the
decoder is a pure step function closed over an explicit parameter pytree, so

* training is a single ``lax.scan`` over time (one compiled program),
* beam search reuses the very same step function inside ``lax.scan`` fully
  on device (sat_tpu/ops/beam_search.py),
* the whole thing is trivially pjit/shard_map-compatible.

Semantics preserved from the reference:
* LSTM state initialized from the mean context via a 1- or 2-layer MLP
  (model.py:358-393), with fc dropout on the inputs;
* per-step soft attention, 1-layer additive logits (ctx→1 no-bias plus a
  position-specific h→num_ctx no-bias projection) or 2-layer tanh MLP
  (model.py:395-436), with fc dropout on both inputs;
* LSTM input = concat(attention context, word embedding) (model.py:277),
  TF1 LSTMCell gate order (i, j, f, o) with +1.0 forget-gate bias;
* DropoutWrapper semantics (model.py:232-236): fresh per-step masks on the
  LSTM input, emitted output, and the recurrent h (TF's default state
  filter exempts the cell state c);
* word logits from concat(output, context, word_embed) via a 1- or 2-layer
  MLP (model.py:438-459);
* teacher forcing: the step-t input word is sentences[:, t-1], step 0 gets
  the <start> index 0 (model.py:253,310).

Compute dtype: matmuls run in bfloat16 (MXU); softmax/log-softmax in fp32.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from ..nn.layers import DROPOUT_MASK
from ..nn.layers import dropout as _nn_dropout
from ..nn.layers import fc_kernel_init
from ..ops import pallas_attention

Params = Dict[str, Any]


class DecoderState(NamedTuple):
    """LSTM carry.  ``output`` is what the next attend/decode sees (the
    DropoutWrapper's *output*-dropout h); ``recurrent`` is what the next
    LSTM step consumes (the *state*-dropout h).  They are identical outside
    training — the split mirrors reference model.py:232-236,307-309 where
    last_output and last_state diverge under dropout."""

    memory: jnp.ndarray      # LSTM cell state c, [B, H]
    output: jnp.ndarray      # emitted h (feeds attend + decode), [B, H]
    recurrent: jnp.ndarray   # recurrent h (feeds the next LSTM step), [B, H]


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def _uniform(key, shape, scale):
    return fc_kernel_init(scale)(key, shape)


def _dense_params(key, d_in, d_out, scale, use_bias=True):
    p = {"kernel": _uniform(key, (d_in, d_out), scale)}
    if use_bias:
        p["bias"] = jnp.zeros((d_out,), jnp.float32)
    return p


def init_decoder_params(rng: jax.Array, config: Config) -> Params:
    """Build the decoder parameter pytree.  Leaf names mirror the reference
    TF scopes (word_embedding/weights, lstm/kernel, initialize/fc_a2, ...)
    so npy checkpoint import is a name rewrite, not a surgery."""
    c = config
    scale = c.fc_kernel_initializer_scale
    E, H, D, N, V = (
        c.dim_embedding,
        c.num_lstm_units,
        c.dim_ctx,
        c.num_ctx,
        c.vocabulary_size,
    )
    keys = iter(jax.random.split(rng, 16))
    p: Params = {}

    p["word_embedding"] = {"weights": _uniform(next(keys), (V, E), scale)}

    # TF1 LSTMCell layout: one kernel [(input_dim + H), 4H], gates (i,j,f,o)
    lstm_in = D + E
    p["lstm"] = {
        "kernel": _uniform(next(keys), (lstm_in + H, 4 * H), scale),
        "bias": jnp.zeros((4 * H,), jnp.float32),
    }

    if c.num_initialize_layers == 1:
        p["initialize"] = {
            "fc_a": _dense_params(next(keys), D, H, scale),
            "fc_b": _dense_params(next(keys), D, H, scale),
        }
    else:
        di = c.dim_initialize_layer
        p["initialize"] = {
            "fc_a1": _dense_params(next(keys), D, di, scale),
            "fc_a2": _dense_params(next(keys), di, H, scale),
            "fc_b1": _dense_params(next(keys), D, di, scale),
            "fc_b2": _dense_params(next(keys), di, H, scale),
        }

    if c.num_attend_layers == 1:
        p["attend"] = {
            "fc_a": _dense_params(next(keys), D, 1, scale, use_bias=False),
            "fc_b": _dense_params(next(keys), H, N, scale, use_bias=False),
        }
    else:
        da = c.dim_attend_layer
        p["attend"] = {
            "fc_1a": _dense_params(next(keys), D, da, scale),
            "fc_1b": _dense_params(next(keys), H, da, scale),
            "fc_2": _dense_params(next(keys), da, 1, scale, use_bias=False),
        }

    dec_in = H + D + E
    if c.num_decode_layers == 1:
        p["decode"] = {"fc": _dense_params(next(keys), dec_in, V, scale)}
    else:
        dd = c.dim_decode_layer
        p["decode"] = {
            "fc_1": _dense_params(next(keys), dec_in, dd, scale),
            "fc_2": _dense_params(next(keys), dd, V, scale),
        }
    return p


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _dense(p, x, activation=None, dtype=jnp.bfloat16):
    # dtype is the matmul compute dtype (bfloat16 on TPU → MXU)
    y = x.astype(dtype) @ p["kernel"].astype(dtype)
    if "bias" in p:
        y = y + p["bias"].astype(dtype)
    y = y.astype(jnp.float32)
    if activation == "tanh":
        y = jnp.tanh(y)
    return y


def _dropout(rng, x, rate, train):
    return _nn_dropout(x, rate, deterministic=not train, rng=rng)


def _l1(x):
    """L1 activity contribution of an activated layer output — TF1
    l1_regularizer semantics: Σ|x|, unnormalized (reference
    utils/nn.py:23-26,40-43; scale applied by the caller)."""
    return jnp.abs(x.astype(jnp.float32)).sum()


def lstm_step(
    p: Params,
    c: jnp.ndarray,
    h: jnp.ndarray,
    x: jnp.ndarray,
    dtype=jnp.bfloat16,
    forget_bias: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """TF1 LSTMCell: concat(x, h) @ kernel → (i, j, f, o).  Returns (c, h)."""
    z = jnp.concatenate([x, h], axis=-1).astype(dtype) @ p["kernel"].astype(dtype)
    z = z.astype(jnp.float32) + p["bias"]
    i, j, f, o = jnp.split(z, 4, axis=-1)
    new_c = jax.nn.sigmoid(f + forget_bias) * c + jax.nn.sigmoid(i) * jnp.tanh(j)
    new_h = jax.nn.sigmoid(o) * jnp.tanh(new_c)
    return new_c, new_h


@jax.named_scope("decoder/init")
def init_state(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    with_activity: bool = False,
) -> DecoderState:
    """LSTM state from the mean context (reference initialize, model.py:358-393).

    with_activity=True (static) returns (state, L1 of the tanh outputs)."""
    p = params["initialize"]
    rate = config.fc_drop_rate
    dt = jnp.dtype(config.compute_dtype)
    context_mean = contexts.mean(axis=1)
    act = jnp.float32(0)
    if train:
        k0, k1, k2 = jax.random.split(rng, 3)
        context_mean = _dropout(k0, context_mean, rate, train)
    if config.num_initialize_layers == 1:
        memory = _dense(p["fc_a"], context_mean, dtype=dt)
        output = _dense(p["fc_b"], context_mean, dtype=dt)
    else:
        ta = _dense(p["fc_a1"], context_mean, activation="tanh", dtype=dt)
        tb = _dense(p["fc_b1"], context_mean, activation="tanh", dtype=dt)
        act = _l1(ta) + _l1(tb)  # pre-dropout, as in TF (activity attaches
        # to the dense layer's output; dropout is a separate later layer)
        if train:
            ta = _dropout(k1, ta, rate, train)
            tb = _dropout(k2, tb, rate, train)
        memory = _dense(p["fc_a2"], ta, dtype=dt)
        output = _dense(p["fc_b2"], tb, dtype=dt)
    state = DecoderState(memory=memory, output=output, recurrent=output)
    return (state, act) if with_activity else state


def attend(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    output: jnp.ndarray,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    with_activity: bool = False,
) -> jnp.ndarray:
    """Soft attention over the context grid → alpha [B, N]
    (reference attend, model.py:395-436).

    The inference path delegates to precompute_attend +
    attend_with_precomputed so there is exactly ONE implementation of the
    inference math (the hoisted one beam search uses); only the
    training/dropout path lives here.

    with_activity=True (static) additionally returns the L1 activity sum
    of the tanh layer outputs (see compute_loss)."""
    p = params["attend"]
    rate = config.fc_drop_rate
    dt = jnp.dtype(config.compute_dtype)
    if not train:
        proj = precompute_attend(params, config, contexts)
        _, alpha = attend_with_precomputed(params, config, contexts, proj, output)
        return (alpha, jnp.float32(0)) if with_activity else alpha
    with jax.named_scope("decoder/attend"):
        kc, ko, kt = jax.random.split(rng, 3)
        contexts = _dropout(kc, contexts, rate, train)
        output = _dropout(ko, output, rate, train)
        act = jnp.float32(0)
        if config.num_attend_layers == 1:
            # ctx→1 per position (no bias) + position-specific h→N projection
            logits1 = _dense(p["fc_a"], contexts, dtype=dt)[..., 0]    # [B, N]
            logits2 = _dense(p["fc_b"], output, dtype=dt)              # [B, N]
            logits = logits1 + logits2
        else:
            t1 = _dense(p["fc_1a"], contexts, activation="tanh", dtype=dt)  # [B, N, da]
            t2 = _dense(p["fc_1b"], output, activation="tanh", dtype=dt)    # [B, da]
            # L1 activity sites: the tanh layer outputs, pre-dropout (the
            # reference attaches l1_regularizer only to activation≠None
            # layers, utils/nn.py:39-43 + model.py:417-429)
            act = _l1(t1) + _l1(t2)
            temp = t1 + t2[:, None, :]
            temp = _dropout(kt, temp, rate, train)
            logits = _dense(p["fc_2"], temp, dtype=dt)[..., 0]     # [B, N]
        alpha = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return (alpha, act) if with_activity else alpha


def attend_context(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    output: jnp.ndarray,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    with_activity: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`attend` and the weighted sum of the grid it feeds:
    (context [B, D], alpha [B, N], L1 activity sum).

    In training the pair is rebuilt in the backward pass from what the
    scan carries or closes over (the attend parameters, the grid,
    ``output``, the step's key) and from its dropout masks, the only
    values of it that are kept.  Left to itself the scan stacks over its
    T steps everything the chain's gradient reads: the dropped-out grid,
    the tanh layer and its sum after dropout, each [T, B, N, .] (4 GB of
    float32 at B=256, T=20, N=196, 512 wide), written by the forward loop
    and read back by the backward one.  The masks stay because they are
    an eighth of that and drawing their bits again costs more than
    reading them (PERF.md section 6, PR 29: this against nothing kept and
    against ``dots_saveable``, on the chip).  Same masks and same
    arithmetic: a loss or a gradient differs from the stacked version's
    only by how XLA fuses and sums the chain (float32: the order of a
    sum; bfloat16 on the chip: which roundings a fusion skips, 1e-5 of
    the loss).  prevent_cse off: a scan body is not subject to the CSE
    hazard checkpoint guards against."""

    def pair(p_attend, contexts, output, rng):
        out = attend(
            {"attend": p_attend}, config, contexts, output, train, rng,
            with_activity=with_activity,
        )
        alpha, act = out if with_activity else (out, jnp.float32(0))
        with jax.named_scope("decoder/attend"):
            context = (contexts * alpha[..., None]).sum(axis=1)  # [B, D]
        return context, alpha, act

    if train:
        pair = jax.checkpoint(
            pair,
            policy=jax.checkpoint_policies.save_only_these_names(DROPOUT_MASK),
            prevent_cse=False,
        )
    return pair(params["attend"], contexts, output, rng)


@jax.named_scope("decoder/attend")
def precompute_attend(
    params: Params, config: Config, contexts: jnp.ndarray
) -> jnp.ndarray:
    """Hoist the context-only half of the attention MLP out of the decode
    loop.  The reference recomputes fc_{a,1a}(contexts) at every one of the
    T×beam steps (model.py:262,395-436) although contexts never change
    during decoding; at inference (no dropout) the term is loop-invariant.

    Returns the 1-layer per-position logits [B, N] or the 2-layer
    tanh-activated features [B, N, da].
    """
    p = params["attend"]
    dt = jnp.dtype(config.compute_dtype)
    if config.num_attend_layers == 1:
        return _dense(p["fc_a"], contexts, dtype=dt)[..., 0]       # [B, N]
    return _dense(p["fc_1a"], contexts, activation="tanh", dtype=dt)  # [B,N,da]


@jax.named_scope("decoder/attend")
def attend_with_precomputed(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    ctx_proj: jnp.ndarray,
    output: jnp.ndarray,
    row_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inference-path attention using the hoisted ``ctx_proj``.

    ``contexts`` [B, N, D] and ``ctx_proj`` are per IMAGE; ``output``
    [B*K, H] is per step row, the K beams of an image adjacent (K read from
    the shapes; 1 where every row has a grid of its own: greedy, the slot
    pool's carry).  Returns (context [B*K, D], alpha [B*K, N]).  With
    use_pallas_attention the 2-layer combine runs as one fused Pallas
    kernel (add → matvec → softmax → weighted sum in a single VMEM
    residency of an image's grid for all its beams); the XLA path
    broadcasts the grid over the beams inside its fusions.

    row_mask: optional [B*K] bool — slot-pool geometry (the stepped decode
    batches dead slots alongside live ones).  False rows get zero
    scores/alpha/context so stale slot state can never emit a NaN; True
    rows are bitwise identical to the unmasked call.  Masking is applied
    identically on the Pallas and XLA paths so the two stay comparable.
    """
    p = params["attend"]
    dt = jnp.dtype(config.compute_dtype)
    B, N = contexts.shape[:2]
    rows = output.shape[0]
    K = rows // B
    if K * B != rows:
        raise ValueError(
            f"{rows} decoder rows over {B} context grids: the rows must be "
            "a whole number of beams per grid"
        )
    if config.num_attend_layers == 1:
        logits = (
            ctx_proj[:, None] + _dense(p["fc_b"], output, dtype=dt).reshape(B, K, N)
        )                                                           # [B, K, N]
    else:
        t2 = _dense(p["fc_1b"], output, activation="tanh", dtype=dt)  # [B*K, da]
        if config.use_pallas_attention:
            # Interpret mode is a test vehicle only — off TPU the XLA branch
            # below is the fast mathematically-identical fallback.
            if jax.default_backend() == "tpu" or pallas_attention.FORCE_INTERPRET:
                return pallas_attention.fused_attend(
                    ctx_proj, t2, p["fc_2"]["kernel"], contexts,
                    row_mask=row_mask,
                    compute_dtype=config.compute_dtype,
                    interpret=jax.default_backend() != "tpu",
                )
        temp = ctx_proj[:, None] + t2.reshape(B, K, 1, -1)         # [B, K, N, da]
        logits = _dense(p["fc_2"], temp, dtype=dt)[..., 0]          # [B, K, N]
    valid = None if row_mask is None else row_mask.reshape(B, K, 1)
    if valid is not None:
        logits = jnp.where(valid, logits, 0.0)
    alpha = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if valid is not None:
        alpha = jnp.where(valid, alpha, 0.0)
    context = (contexts[:, None] * alpha[..., None]).sum(axis=2)    # [B, K, D]
    if valid is not None:
        context = jnp.where(valid, context, 0.0)
    return context.reshape(rows, -1), alpha.reshape(rows, N)


@jax.named_scope("decoder/logits")
def decode_logits(
    params: Params,
    config: Config,
    expanded_output: jnp.ndarray,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    with_activity: bool = False,
) -> jnp.ndarray:
    """concat(output, context, word_embed) → vocab logits
    (reference decode, model.py:438-459).

    with_activity=True (static) returns (logits, L1 of the tanh output)."""
    p = params["decode"]
    rate = config.fc_drop_rate
    dt = jnp.dtype(config.compute_dtype)
    act = jnp.float32(0)
    if train:
        k0, k1 = jax.random.split(rng)
        expanded_output = _dropout(k0, expanded_output, rate, train)
    if config.num_decode_layers == 1:
        logits = _dense(p["fc"], expanded_output, dtype=dt)
        return (logits, act) if with_activity else logits
    temp = _dense(p["fc_1"], expanded_output, activation="tanh", dtype=dt)
    act = _l1(temp)
    if train:
        temp = _dropout(k1, temp, rate, train)
    logits = _dense(p["fc_2"], temp, dtype=dt)
    return (logits, act) if with_activity else logits


def decoder_step(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    state: DecoderState,
    word: jnp.ndarray,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    ctx_proj: Optional[jnp.ndarray] = None,
    with_activity: bool = False,
    row_mask: Optional[jnp.ndarray] = None,
) -> Tuple[DecoderState, jnp.ndarray, jnp.ndarray]:
    """One decoder step: attend → embed → LSTM → logits.

    Returns (new_state, logits [B, V], alpha [B, N]) — plus the step's L1
    activity sum when with_activity=True (static).  ``state.output`` must
    be the post-dropout h when training, matching the reference where the
    DropoutWrapper's output feeds the next attend (model.py:262,307).

    ctx_proj: hoisted :func:`precompute_attend` output — inference only
    (training's per-step context dropout invalidates it, so it is ignored
    when train=True).

    row_mask: optional [B] bool, forwarded to
    :func:`attend_with_precomputed` on the hoisted inference path (the
    stepped decode's dead-slot mask); ignored elsewhere — the monolithic
    path never sets it, so its programs are untouched.
    """
    if train:
        k_att, k_in, k_out, k_state, k_dec = jax.random.split(rng, 5)
    else:
        k_att = k_in = k_out = k_state = k_dec = None
    ldr = config.lstm_drop_rate
    act = jnp.float32(0)

    if ctx_proj is not None and not train:
        context, alpha = attend_with_precomputed(
            params, config, contexts, ctx_proj, state.output,
            row_mask=row_mask,
        )
    else:
        context, alpha, act = attend_context(
            params, config, contexts, state.output, train, k_att,
            with_activity=with_activity,
        )

    with jax.named_scope("decoder/embed"):
        word_embed = params["word_embedding"]["weights"][word]    # [B, E]

    with jax.named_scope("decoder/lstm"):
        lstm_input = jnp.concatenate([context, word_embed], axis=-1)
        lstm_input = _dropout(k_in, lstm_input, ldr, train)
        new_c, new_h = lstm_step(
            params["lstm"], state.memory, state.recurrent, lstm_input,
            dtype=jnp.dtype(config.compute_dtype),
        )
        # DropoutWrapper: independent masks on emitted h and recurrent h; c exempt
        emitted = _dropout(k_out, new_h, ldr, train)
        recurrent_h = _dropout(k_state, new_h, ldr, train)

    with jax.named_scope("decoder/logits"):
        expanded = jnp.concatenate([emitted, context, word_embed], axis=-1)
    logits = decode_logits(
        params, config, expanded, train, k_dec, with_activity=with_activity
    )
    new_state = DecoderState(memory=new_c, output=emitted, recurrent=recurrent_h)
    if with_activity:
        logits, dec_act = logits
        return new_state, logits, alpha, act + dec_act
    return new_state, logits, alpha


def teacher_forced_decode(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    sentences: jnp.ndarray,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    with_activity: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full training-time unroll as one lax.scan.

    contexts [B, N, D]; sentences [B, T] int32.
    Returns (logits [B, T, V], alphas [B, T, N]) — plus the summed L1
    activity of every tanh layer output across init + all T steps when
    with_activity=True (static), matching the reference's unrolled graph
    where each step's dense layers contribute to REGULARIZATION_LOSSES.
    """
    B, T = sentences.shape
    if rng is None:
        if train:
            raise ValueError(
                "teacher_forced_decode(train=True) requires an rng; a fixed "
                "key would silently reuse identical dropout masks every step"
            )
        rng = jax.random.PRNGKey(0)  # never consumed when train=False
    k_init, k_steps = jax.random.split(rng)
    state = init_state(
        params, config, contexts, train, k_init, with_activity=with_activity
    )
    init_act = jnp.float32(0)
    if with_activity:
        state, init_act = state

    # input word at step t is sentences[:, t-1]; step 0 gets <start>=0
    words_in = jnp.concatenate(
        [jnp.zeros((B, 1), sentences.dtype), sentences[:, :-1]], axis=1
    )
    step_rngs = jax.random.split(k_steps, T)

    def body(state, xs):
        word_t, rng_t = xs
        out = decoder_step(
            params, config, contexts, state, word_t, train, rng_t,
            with_activity=with_activity,
        )
        if with_activity:
            state, logits, alpha, act = out
            return state, (logits, alpha, act)
        state, logits, alpha = out
        return state, (logits, alpha)

    if train and config.remat_decoder:
        # Rematerialize the whole step in backward: keep matmul outputs,
        # regenerate dropout masks / elementwise chains from rng_t instead
        # of stacking them as residuals across T steps.  Numerically
        # identical (same keys -> same masks); trades recompute for HBM
        # residual traffic.  This policy decides what the scan stacks, for
        # the attention chain too: under it attend_context's own
        # checkpoint keeps nothing across steps (its masks are drawn again
        # with the rest of the step) and only shapes the step's backward.
        # prevent_cse off: scan bodies are not subject to the CSE hazard
        # checkpoint guards against.
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_saveable,
            prevent_cse=False,
        )

    _, ys = jax.lax.scan(body, state, (words_in.T, step_rngs))
    if with_activity:
        logits, alphas, acts = ys
        # scan stacks along time-major; restore batch-major
        return (
            logits.transpose(1, 0, 2),
            alphas.transpose(1, 0, 2),
            init_act + acts.sum(),
        )
    logits, alphas = ys
    return logits.transpose(1, 0, 2), alphas.transpose(1, 0, 2)
