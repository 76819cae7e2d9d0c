"""On-device batched beam search.

The reference decodes with a host-side Python loop: ~beam_size × 20
sess.run round-trips per batch, a heap rebuilt between each
(/root/reference/base_model.py:163-240).  Here the whole search is ONE
compiled XLA program: a ``lax.scan`` over time carrying ``[batch, beam]``
states, so a batch of images decodes in a single device dispatch.  This is
the single biggest performance win over the reference (SURVEY.md §3.2).

Semantics preserved (the reference is the correctness oracle):
* a hypothesis completes when it emits the terminator token ('.' in the
  vocabulary, base_model.py:229-232) — completed captions include it;
* completed hypotheses accumulate in a per-image top-K set while partial
  beams keep expanding (the TopN pair, base_model.py:172-181);
* scores multiply raw next-word probabilities with no length
  normalization (base_model.py:224) — we carry log-probabilities, whose
  ordering is identical; reported scores are the same products;
* if nothing completed after max_caption_length steps, the partial beams
  are returned (base_model.py:236-237).

Deliberate upgrade: each step takes the global top-K over all beam×vocab
continuations (the eos column excluded from continuation) instead of the
reference's per-beam top-(K+1) heap pushes — a strictly-at-least-as-good
candidate set.  The reference's top-(K+1) survives as a gate on
completions only: eos closes a beam when it is among that beam's K+1
likeliest next words, and the gate needs one number per beam, the
(K+1)-th largest log-probability.

Both come out of ONE selection (``_top_rows``): ``lax.top_k`` with k = K+1
over the ``[B*K, V]`` rows the logits arrive in, values and indices.  Its
values' minimum is the gate's threshold.  Its K+1 candidates a row hold
the row's K best words other than eos, whether or not eos is among them,
and the global top-K lies within the union of the rows' own top-K; so the
continuations are a second ``top_k`` over ``[B, K*(K+1)]`` numbers (the
candidates plus their beam's score, eos masked), the same K — values,
parents and words — that a ``top_k`` over ``[B, K*V]`` gives: adding a
beam's score is monotone within its row, and both stages put the lower
index first among equals, beam-major.  (Only where V < K, or where the
add rounds two DIFFERENT log-probabilities of a row onto one score, can
the two orders part: the latter keeps the likelier word first, where the
flat form kept the lower index.)  The selection is on the log-softmax and
not on the raw logits, because two logits that round to one
log-probability are a tie to the search, won by the lower index.

Why per row: on the chip ``[B*K, V]`` is tiled (8, 128) and ``[B, K, V]``
(4, 128) over ``(K, V)``, so the reshape between them, the ``+ live_logp``
broadcast, the eos overwrite and the ``[B, K*V]`` view each wrote the
whole array again — at V = 128,256 four copies of f32[256,3,V] and a
second ``TopK``, 18% of the device's time.  Now nothing vocabulary-wide is
written after the logits: the log-softmax's last subtraction fuses into
the ``TopK`` call, and the eos column is a masked sum inside the pass
that sums the exponentials.  XLA:TPU emits its ``TopK`` call only for a
rank-2 operand whose returned values are all consumed (a rank-3 operand
or a sliced column lower to a full stable sort of the vocabulary); no
step sorts the vocabulary or holds an array per beam × vocabulary
(tests/test_aot_tpu.py pins both).

The decoder's state is opaque to the search but for who moves which leaf
(``models/decoders.py`` ``StepState``): a plain tree or ``StepState.beam``
follows its beam by ONE tree-wide gather by parent a step
(``_reorder_beams``); ``StepState.shared`` is carried as it is;
``StepState.at_source`` is never moved: for those leaves the search writes
``StepState.source`` (``b * K + parent[b, k]``) where it gathers the
others, and the decoder's next step reads them at that row (a recurrent
matrix state that every step rewrites whole is then passed over once a
step, not gathered and passed over again).

Greedy decoding is the beam_size=1 special case of the same program.

Two drivers run the SAME expansion math (``_expand_step``):

* the monolithic ``run_search`` while_loop — one dispatch per batch, the
  offline/eval path and the serving correctness oracle;
* the resumable stepped decode (``decode_step`` over a ``SlotCarry``) —
  the serve engine's continuous-batching path, where each slot of a
  fixed-capacity pool advances independently, new requests are seeded
  into free slots between steps (``init_slots``) and finished slots are
  harvested the step their early-exit condition fires
  (``harvest_slots``).  Per-slot results are bitwise-identical to the
  monolithic search because both paths share one step body: a slot
  freezes the step it seals (all K finished slots filled and
  min(fin) ≥ max(live)) — from that step on the monolithic search can no
  longer alter that image's merged result either (a later completion
  scores ≤ max(live) ≤ min(fin), and the finished-set merge's
  ``lax.top_k`` prefers the lower index among equals, where the finished
  entries sit; the eos gate's threshold is a value, the same number
  however its ties are ordered).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import Config
from ..models import decoders
from ..models.decoder import (
    DecoderState,
    decoder_step,
    init_state,
    precompute_attend,
)
from ..models.decoders import StepState, tile_beams  # noqa: F401  (re-exported)

NEG_INF = -1e30
# Added to completed-caption scores when ranking them against live partial
# beams at the end of the search, so every completed caption outranks every
# partial one (scores are log-probs of ≤20 tokens, far above -1e6).
_FINISHED_RANK_BONUS = 1e6


class BeamResult(NamedTuple):
    """Per-image captions ranked finished-first (reference semantics:
    completed captions beat live partials, base_model.py:236-237), then by
    descending score within each group — so log_scores is NOT globally
    monotonic when a weak completed caption outranks a strong partial."""

    words: jnp.ndarray      # [B, K, T] int32 token ids ('.'-terminated)
    log_scores: jnp.ndarray  # [B, K] sum of log p(word) — product ordering
    lengths: jnp.ndarray    # [B, K] int32 number of emitted tokens
    # [B, K, T, N] per-word attention maps of each returned caption
    # (soft-attention α over the context grid at the step that emitted
    # word t); None unless return_alphas was set
    alphas: Optional[jnp.ndarray] = None
    # decode-loop iterations actually executed — the deterministic
    # observability probe for the early exit (None unless return_steps
    # was set, so the default output pytree — and the shard_map out_specs
    # built from it — is unchanged).  Scalar int32 from run_search;
    # per-slot [S] int32 from harvest_slots.
    steps_run: Optional[jnp.ndarray] = None
    # what the decoder itself reports of the batch, {name: array}, opaque
    # to the search (``models/decoders.py`` ``Search.finish``: a decoder
    # with experts returns its token counts and chosen experts); None
    # where the decoder reports nothing
    decoder_stats: Optional[dict] = None


def _reorder_beams(state, B: int, K: int, batch_idx, parent):
    """ONE tree-wide per-parent gather: every per-beam leaf ``[B*K, ...]``
    follows its beam to the slot the search gave it.  The leaves a decoder
    keeps in ``StepState.at_source`` stay where its step wrote them: the
    search hands over ``source``, the flat row each slot descends from, and
    the decoder's next step reads them there."""

    def gather(x):
        return x.reshape((B, K) + x.shape[1:])[batch_idx, parent].reshape(x.shape)

    if isinstance(state, StepState):
        state = state._replace(beam=jax.tree_util.tree_map(gather, state.beam))
        if state.source is not None:
            state = state._replace(source=(batch_idx * K + parent).reshape(B * K).astype(state.source.dtype))
        return state
    return jax.tree_util.tree_map(gather, state)


class SearchState(NamedTuple):
    """The pure search bookkeeping of ``B`` independent images — everything
    the expansion step reads/writes besides the decoder's LSTM state."""

    live_logp: jnp.ndarray    # [B, K] cumulative log-prob of live beams
    live_words: jnp.ndarray   # [B, K, T]
    live_len: jnp.ndarray     # [B, K]
    last_word: jnp.ndarray    # [B, K] input word of the NEXT step
    fin_logp: jnp.ndarray     # [B, K] finished top-K (NEG_INF = empty slot)
    fin_words: jnp.ndarray    # [B, K, T]
    fin_len: jnp.ndarray      # [B, K]
    live_alphas: jnp.ndarray  # [B, K, T, An] (An=0 unless return_alphas)
    fin_alphas: jnp.ndarray   # [B, K, T, An]


def _init_search(B: int, K: int, T: int, An: int) -> SearchState:
    # beam 0 alive at logp 0; others dead so step 0 expands a single beam
    return SearchState(
        live_logp=jnp.full((B, K), NEG_INF, jnp.float32).at[:, 0].set(0.0),
        live_words=jnp.zeros((B, K, T), jnp.int32),
        live_len=jnp.zeros((B, K), jnp.int32),
        last_word=jnp.zeros((B, K), jnp.int32),  # <start> = 0 (model.py:253)
        fin_logp=jnp.full((B, K), NEG_INF, jnp.float32),
        fin_words=jnp.zeros((B, K, T), jnp.int32),
        fin_len=jnp.zeros((B, K), jnp.int32),
        live_alphas=jnp.zeros((B, K, T, An), jnp.float32),
        fin_alphas=jnp.zeros((B, K, T, An), jnp.float32),
    )


@jax.named_scope("beam/topk")
def _top_rows(rows: jnp.ndarray, k: int):
    """[R, V] -> (kth [R], values [R, k], indices [R, k]): each row's k
    largest values in descending order, the lower index first among
    equals, and the k-th of them (the minimum: a value, so ties cannot
    matter).  The ONE selection of a step over the vocabulary, written the
    one way XLA:TPU turns into its ``TopK`` custom call: a rank-2 operand
    (the ``[B*K, V]`` rows as the head writes them) and every returned
    value consumed.  A rank-3 operand, or slicing the last column out,
    each lowers instead to a full stable sort of the vocabulary axis."""
    vals, idx = jax.lax.top_k(rows, k)
    return vals.min(axis=-1), vals, idx


@jax.named_scope("beam/expand")
def _expand_step(
    eos_id: int,
    K: int,
    V: int,
    An: int,
    valid_size: Optional[int],
    new_state,
    logits: jnp.ndarray,
    alpha: jnp.ndarray,
    t_vec: jnp.ndarray,
    s: SearchState,
):
    """One beam-expansion step over ``B`` independent rows — the single
    implementation both the monolithic while_loop and the stepped slot
    pool run (bitwise parity between the two paths is BY CONSTRUCTION).

    new_state/logits/alpha: the decoder step's outputs over the flattened
    [B*K] beam batch; new_state is a tree of per-beam leaves (the LSTM's
    ``DecoderState``) or a ``StepState``.  t_vec [B] int32: each row's own time index —
    per-row because pool slots run staggered; the monolithic driver
    passes the loop counter broadcast to all rows.  Time-indexed writes
    use a one-hot select over the T axis (value-identical to an
    ``.at[:, :, t].set``, which needs a scalar t).
    """
    B = s.live_logp.shape[0]
    T = s.live_words.shape[2]
    batch_idx = jnp.arange(B)[:, None]  # [B,1] for beam gathers
    t_hot = jnp.arange(T)[None, :] == t_vec[:, None]            # [B,T]

    step_alpha = alpha.reshape(B, K, alpha.shape[-1])[:, :, :An]  # [B,K,An]
    if valid_size is not None and valid_size < V:
        logits = logits.at[:, valid_size:].set(NEG_INF)
    # log-softmax over the [B*K, V] rows, in its parts (the arithmetic of
    # ``jax.nn.log_softmax`` to the bit), so that the rows stay as the head
    # wrote them and nothing is written out again for the sake of the eos
    # column's B*K numbers.  That column is read as a masked sum along the
    # row (x + zeros: exact): it rides the pass that sums the exponentials.
    # A slice would not do: ``row_logp[:, eos_id]`` makes XLA:TPU write the
    # whole log-softmax out beside the copy it fuses into ``TopK``, and
    # ``logits[:, eos_id]`` made it hand a bfloat16 decoder's logits to the
    # slice rounded and to ``TopK`` unrounded, so that an eos at rank K+1
    # fell under its own threshold (seen on the chip, PR 31).
    x = logits.astype(jnp.float32)
    x_max = x.max(axis=-1, keepdims=True)
    lse = jnp.log(jnp.exp(x - x_max).sum(axis=-1, keepdims=True))
    row_logp = (x - x_max) - lse
    x_eos = jnp.where(jnp.arange(V) == eos_id, x, 0.0).sum(axis=-1)
    eos_row = (x_eos - x_max[:, 0]) - lse[:, 0]
    live_row = s.live_logp.reshape(B * K)
    C = min(K + 1, V)                       # candidates a row
    kth, row_top, row_word = _top_rows(row_logp, C)     # [B*K], 2 x [B*K,C]

    # --- completions: an eos hypothesis only becomes a candidate when
    # eos is within its beam's top-(K+1) next words — the reference only
    # ever pushes words from that set (base_model.py:219-230), so junk
    # completions can't crowd out the partial-beam fallback.
    eos_scores = jnp.where(
        eos_row >= kth, eos_row + live_row, NEG_INF
    ).reshape(B, K)
    eos_words = jnp.where(t_hot[:, None, :], jnp.int32(eos_id), s.live_words)
    eos_len = s.live_len + 1
    # the eos word was emitted from THIS step's attention
    eos_alphas = jnp.where(
        t_hot[:, None, :, None], step_alpha[:, :, None, :], s.live_alphas
    )
    cand_logp = jnp.concatenate([s.fin_logp, eos_scores], axis=1)   # [B,2K]
    cand_words = jnp.concatenate([s.fin_words, eos_words], axis=1)  # [B,2K,T]
    cand_len = jnp.concatenate([s.fin_len, eos_len], axis=1)
    cand_alphas = jnp.concatenate([s.fin_alphas, eos_alphas], axis=1)
    with jax.named_scope("beam/topk"):
        top_fin, fin_sel = jax.lax.top_k(cand_logp, K)
    fin_logp = top_fin
    with jax.named_scope("beam/tile"):
        fin_words = cand_words[batch_idx, fin_sel]
        fin_len = cand_len[batch_idx, fin_sel]
        fin_alphas = cand_alphas[batch_idx, fin_sel]

    # --- continuations: global top-K over beam×vocab, eos excluded: the
    # K best of the rows' K+1 candidates each plus its beam's score (the
    # module docstring says why they are the K of one top_k over [B, K*V])
    cont = jnp.where(
        row_word == eos_id, NEG_INF, row_top + live_row[:, None]
    ).reshape(B, K * C)
    with jax.named_scope("beam/topk"):
        top_live, flat_sel = jax.lax.top_k(cont, K)        # [B,K] of K*C
    parent = flat_sel // C                                 # source beam
    word = row_word.reshape(B, K * C)[batch_idx, flat_sel]  # chosen token

    # the per-parent gathers that reorder every beam's state
    with jax.named_scope("beam/tile"):
        state = _reorder_beams(new_state, B, K, batch_idx, parent)
        live_words = jnp.where(
            t_hot[:, None, :], word[:, :, None], s.live_words[batch_idx, parent]
        )
        live_len = s.live_len[batch_idx, parent] + 1
        live_alphas = jnp.where(
            t_hot[:, None, :, None],
            step_alpha[batch_idx, parent][:, :, None, :],
            s.live_alphas[batch_idx, parent],
        )
    return state, SearchState(
        live_logp=top_live,
        live_words=live_words,
        live_len=live_len,
        last_word=word,
        fin_logp=fin_logp,
        fin_words=fin_words,
        fin_len=fin_len,
        live_alphas=live_alphas,
        fin_alphas=fin_alphas,
    )


def _sealed(fin_logp: jnp.ndarray, live_logp: jnp.ndarray) -> jnp.ndarray:
    """[B] bool: which rows' results can no longer change.  Cumulative
    scores are sums of log-probs, so a live beam's score can only FALL.
    Once a row has all K finished slots filled and its worst finished
    caption outranks its best live beam, no later step can alter its
    merged result (a new completion scores below min(fin) and the merge
    ranks finished first)."""
    return jnp.all(fin_logp > NEG_INF / 2, axis=1) & (
        fin_logp.min(axis=1) >= live_logp.max(axis=1)
    )


@jax.named_scope("beam/finalize")
def _merge_results(
    s: SearchState,
    K: int,
    return_alphas: bool,
    steps: Optional[jnp.ndarray] = None,
) -> BeamResult:
    """Final ranking: completed captions first (the reference only falls
    back to partials when NOTHING completed, base_model.py:236-237); any
    fin slots that never filled are backfilled per-slot from the live
    partial beams instead of surfacing -inf junk rows."""
    B = s.live_logp.shape[0]
    batch_idx = jnp.arange(B)[:, None]
    fin_valid = s.fin_logp > NEG_INF / 2
    rank_key = jnp.concatenate(
        [
            jnp.where(fin_valid, s.fin_logp + _FINISHED_RANK_BONUS, NEG_INF),
            s.live_logp,
        ],
        axis=1,
    )                                                       # [B,2K]
    cand_logp = jnp.concatenate([s.fin_logp, s.live_logp], axis=1)
    cand_words = jnp.concatenate([s.fin_words, s.live_words], axis=1)
    cand_len = jnp.concatenate([s.fin_len, s.live_len], axis=1)
    with jax.named_scope("beam/topk"):
        _, sel = jax.lax.top_k(rank_key, K)                 # [B,K]
    alphas = None
    if return_alphas:
        cand_alphas = jnp.concatenate([s.fin_alphas, s.live_alphas], axis=1)
        alphas = cand_alphas[batch_idx, sel]
    return BeamResult(
        words=cand_words[batch_idx, sel],
        log_scores=cand_logp[batch_idx, sel],
        lengths=cand_len[batch_idx, sel],
        alphas=alphas,
        steps_run=steps,
    )


def run_search(
    config: Config,
    step_fn,
    state0,
    B: int,
    eos_id: int,
    beam_size: Optional[int] = None,
    max_len: Optional[int] = None,
    valid_size: Optional[int] = None,
    return_alphas: bool = False,
    alpha_width: Optional[int] = None,
    early_exit: bool = True,
    return_steps: bool = False,
    return_state: bool = False,
):
    """The search engine shared by the single-device and context-parallel
    decode paths.  Returns the BeamResult, or with ``return_state``
    (result, the decoder's state as the last step left it).

    step_fn(state, last_word [B*K] int32) -> (new_state, logits [B*K, V],
    alpha [B*K, Na]) — one decoder step over the flattened beam batch.
    state0: the initial state already tiled to [B*K, ...] rows: a tree of
    per-beam leaves (the LSTM's DecoderState), or a StepState whose
    ``shared`` part rides along unreordered and whose ``at_source`` part
    stays where the step wrote it, named by ``source``.
    alpha_width: Na of step_fn's alpha (the LOCAL context-block width
    under context parallelism); required when return_alphas is set.
    early_exit: stop the while_loop as soon as no image's result can
    change (see cond below) — exact, result-identical; False forces the
    full T steps (the A/B + testing control).
    """
    K = beam_size or config.beam_size
    T = max_len or config.max_caption_length
    V = config.vocabulary_size

    # per-step attention maps of every hypothesis; zero-width unless
    # requested, so the carry copies cost nothing in the default path
    if return_alphas and alpha_width is None:
        raise ValueError("return_alphas requires alpha_width")
    An = (alpha_width or 0) if return_alphas else 0
    search0 = _init_search(B, K, T, An)

    def body(loop_carry):
        t, (state, s) = loop_carry
        new_state, logits, alpha = step_fn(state, s.last_word.reshape(B * K))
        t_vec = jnp.full((B,), t, jnp.int32)
        state, s = _expand_step(
            eos_id, K, V, An, valid_size, new_state, logits, alpha, t_vec, s
        )
        return t + 1, (state, s)

    def cond(loop_carry):
        t, (_, s) = loop_carry
        if not early_exit:
            return t < T
        # Exact early exit (see _sealed).  Mean COCO captions run well
        # short of T=20 (reference filter ≤20, coco.py:323-339), so this
        # saves real decode steps with bit-identical results (pinned by
        # tests).
        return (t < T) & ~jnp.all(_sealed(s.fin_logp, s.live_logp))

    # the loop's own ops (condition, counter, what only feeds it) carry a
    # scope too, so a trace read by scope leaves nothing of it unnamed
    with jax.named_scope("beam/loop"):
        t_final, (state, search) = jax.lax.while_loop(
            cond, body, (jnp.int32(0), (state0, search0))
        )
    result = _merge_results(
        search, K, return_alphas, steps=t_final if return_steps else None
    )
    return (result, state) if return_state else result


def beam_search(
    params,
    config: Config,
    contexts: jnp.ndarray,
    eos_id: int,
    beam_size: Optional[int] = None,
    max_len: Optional[int] = None,
    valid_size: Optional[int] = None,
    hoist_attention: bool = True,
    return_alphas: bool = False,
    early_exit: bool = True,
    return_steps: bool = False,
) -> BeamResult:
    """Decode captions for a batch of context grids.

    contexts: [B, N, D] float32 (encoder output).
    eos_id: vocabulary index of the '.' terminator token.
    valid_size: number of real vocabulary entries; logit columns beyond it
      are masked out.  The model's logit width is config.vocabulary_size,
      but a vocabulary built from a small corpus shrinks below that
      (reference vocabulary.py:25-26), leaving trailing logit columns with
      no word — the reference would index past its word list there.
    hoist_attention: precompute the context half of the attention MLP
      outside the decode loop (inference-exact; False keeps the
      step-by-step oracle path for testing).
    return_alphas: also carry each hypothesis's per-step attention maps
      through the search (the paper's per-word attention figures; neither
      the reference nor its upstream exposes them at decode time).

    The context-parallel twin of this wrapper (context grid sharded over
    the mesh's 'model' axis, distributed-softmax attend) is
    :func:`sat_tpu.parallel.context.cp_beam_search`; both plug their step
    function into the same :func:`run_search` engine.
    """
    K = beam_size or config.beam_size
    search = decoders.search(
        params, config, contexts, K, max_len or config.max_caption_length,
        hoist_attention=hoist_attention, return_alphas=return_alphas,
    )
    result, state = run_search(
        config, search.step_fn, search.state0, contexts.shape[0], eos_id,
        beam_size=K, max_len=max_len, valid_size=valid_size,
        return_alphas=return_alphas, alpha_width=search.alpha_width,
        early_exit=early_exit, return_steps=return_steps, return_state=True,
    )
    return search.finish(result, state)


@partial(
    jax.jit,
    static_argnames=(
        "config", "eos_id", "beam_size", "max_len", "valid_size",
        "return_alphas", "early_exit", "return_steps",
    ),
)
def beam_search_jit(
    params, config, contexts, eos_id, beam_size=None, max_len=None,
    valid_size=None, return_alphas=False, early_exit=True, return_steps=False,
):
    return beam_search(
        params, config, contexts, eos_id, beam_size, max_len, valid_size,
        return_alphas=return_alphas, early_exit=early_exit,
        return_steps=return_steps,
    )


def greedy_decode(
    params,
    config: Config,
    contexts: jnp.ndarray,
    eos_id: int,
    max_len: Optional[int] = None,
    valid_size: Optional[int] = None,
    return_steps: bool = False,
) -> BeamResult:
    """Argmax decoding — the degenerate beam=1 case."""
    return beam_search(
        params, config, contexts, eos_id,
        beam_size=1, max_len=max_len, valid_size=valid_size,
        return_steps=return_steps,
    )


# ---------------------------------------------------------------------------
# Resumable stepped decode — the serve engine's continuous-batching path
# ---------------------------------------------------------------------------


class SlotCarry(NamedTuple):
    """Full resumable state of an S-slot decode pool.

    Every leaf has a fixed shape for a given pool geometry, so one AOT
    compile of each pool program (``init_slots`` / ``decode_step`` /
    ``retire_slots`` / ``harvest_slots``) serves the pool's whole
    lifetime — the serving zero-recompile guarantee extends to the
    stepped path unchanged.  Slots advance independently: ``t`` is each
    slot's own time index and ``alive`` its in-flight flag; inactive
    rows pass through every program untouched (one-hot selects only —
    no scatter at traced offsets anywhere).
    """

    ctx: jnp.ndarray        # [S*K, N, D] per-slot context grid, K-tiled
    ctx_proj: jnp.ndarray   # [S*K, N] or [S*K, N, da] hoisted attention
    state: DecoderState     # [S*K, H] LSTM carry
    search: SearchState     # [S, ...] beam bookkeeping
    t: jnp.ndarray          # [S] int32 per-slot time index
    alive: jnp.ndarray      # [S] bool — seeded and not yet finished


def init_slot_pool(
    config: Config,
    slots: int,
    beam_size: Optional[int] = None,
    max_len: Optional[int] = None,
    return_alphas: bool = False,
    alpha_width: Optional[int] = None,
) -> SlotCarry:
    """An empty pool: all slots dead, all state zeroed."""
    K = beam_size or config.beam_size
    T = max_len or config.max_caption_length
    N, D, H = config.num_ctx, config.dim_ctx, config.num_lstm_units
    An = (alpha_width or N) if return_alphas else 0
    S = int(slots)
    if config.num_attend_layers == 1:
        ctx_proj = jnp.zeros((S * K, N), jnp.float32)
    else:
        ctx_proj = jnp.zeros((S * K, N, config.dim_attend_layer), jnp.float32)
    return SlotCarry(
        ctx=jnp.zeros((S * K, N, D), jnp.float32),
        ctx_proj=ctx_proj,
        state=DecoderState(
            memory=jnp.zeros((S * K, H), jnp.float32),
            output=jnp.zeros((S * K, H), jnp.float32),
            recurrent=jnp.zeros((S * K, H), jnp.float32),
        ),
        search=_init_search(S, K, T, An),
        t=jnp.zeros((S,), jnp.int32),
        alive=jnp.zeros((S,), jnp.bool_),
    )


def init_slots(
    params,
    config: Config,
    carry: SlotCarry,
    lane_ctx: jnp.ndarray,
    slot_src: jnp.ndarray,
    admit_mask: jnp.ndarray,
    beam_size: Optional[int] = None,
) -> SlotCarry:
    """Seed slots anywhere in the pool from an encoded admission lane.

    lane_ctx: [L, N, D] — one encoder output per freshly admitted image
    (L is the lane width the encoder was compiled at, ≤ page_width).
    slot_src: [S] int32 — which lane row feeds each slot (gathered, so
    scattered free slots seed from one contiguous encode; rows of
    non-admitted slots are ignored — point them at 0).  admit_mask: [S]
    bool — True slots are (re)initialized to a fresh t=0 search over
    their lane context; False slots keep whatever state they held.

    The gather + full-pool select keeps this ONE compiled program per
    lane width regardless of which slots the host hands out, and the
    expensive encode runs at lane width while the cheap per-slot init
    (fc layers, beam bookkeeping) runs pool-wide.
    """
    K = beam_size or config.beam_size
    S = carry.t.shape[0]
    T = carry.search.live_words.shape[2]
    An = carry.search.live_alphas.shape[3]

    contexts = lane_ctx[slot_src]                               # [S, N, D]
    ctx_new = tile_beams(contexts, K)
    proj_new = tile_beams(precompute_attend(params, config, contexts), K)
    st = init_state(params, config, contexts, train=False)      # [S, H]
    st = DecoderState(*(tile_beams(x, K) for x in st))          # [S*K, H]
    fresh = _init_search(S, K, T, An)

    row_mask = jnp.repeat(admit_mask, K)                        # [S*K]

    def sel(new, old, mask):
        return jnp.where(
            mask.reshape(mask.shape + (1,) * (old.ndim - 1)), new, old
        )

    return SlotCarry(
        ctx=sel(ctx_new, carry.ctx, row_mask),
        ctx_proj=sel(proj_new, carry.ctx_proj, row_mask),
        state=DecoderState(
            *(sel(n, o, row_mask) for n, o in zip(st, carry.state))
        ),
        search=SearchState(
            *(sel(n, o, admit_mask) for n, o in zip(fresh, carry.search))
        ),
        t=sel(jnp.zeros((S,), jnp.int32), carry.t, admit_mask),
        alive=sel(jnp.ones((S,), jnp.bool_), carry.alive, admit_mask),
    )


def decode_step(
    params,
    config: Config,
    carry: SlotCarry,
    slot_mask: jnp.ndarray,
    eos_id: int,
    beam_size: Optional[int] = None,
    valid_size: Optional[int] = None,
) -> tuple:
    """Advance every active slot by one decode step.

    slot_mask: [S] bool — the host's view of which slots hold in-flight
    requests; a slot only advances when both slot_mask and carry.alive
    are set, so harvested-but-not-yet-reseeded slots stay frozen.

    Returns ``(carry, done)`` where done [S] bool flags slots that
    finished THIS step — sealed by the exact early-exit condition (same
    :func:`_sealed` the monolithic path uses) or out of time (t == T).
    The decoder runs over all S*K rows every step (dead rows compute
    garbage that one-hot selects discard); with bucket-sized pools this
    is the same arithmetic the monolithic batch spends on padding.
    """
    K = beam_size or config.beam_size
    S = carry.t.shape[0]
    T = carry.search.live_words.shape[2]
    V = config.vocabulary_size
    An = carry.search.live_alphas.shape[3]
    active = slot_mask & carry.alive                             # [S]
    row_active = jnp.repeat(active, K)                           # [S*K]

    # dead rows' stale carry state is garbage to the decoder: row_mask
    # zeroes their attention inside the (Pallas or XLA) attend so nothing
    # non-finite can arise there; their outputs are then discarded by the
    # selects below exactly as before.  Live rows are bitwise unchanged.
    new_state, logits, alpha = decoder_step(
        params, config, carry.ctx, carry.state,
        carry.search.last_word.reshape(S * K),
        train=False, ctx_proj=carry.ctx_proj, row_mask=row_active,
    )
    g_state, stepped = _expand_step(
        eos_id, K, V, An, valid_size, new_state, logits, alpha,
        carry.t, carry.search,
    )

    # freeze everything in non-active slots — including sealed ones, whose
    # results must hold bitwise until the host harvests them

    def sel_rows(new, old):
        return jnp.where(
            row_active.reshape((S * K,) + (1,) * (old.ndim - 1)), new, old
        )

    def sel_slot(new, old):
        return jnp.where(
            active.reshape((S,) + (1,) * (old.ndim - 1)), new, old
        )

    state = DecoderState(
        *(sel_rows(n, o) for n, o in zip(g_state, carry.state))
    )
    search = SearchState(
        *(sel_slot(n, o) for n, o in zip(stepped, carry.search))
    )
    t = jnp.where(active, carry.t + 1, carry.t)
    sealed = _sealed(search.fin_logp, search.live_logp)
    alive = jnp.where(active, ~sealed & (t < T), carry.alive)
    done = active & ~alive
    return (
        SlotCarry(
            ctx=carry.ctx, ctx_proj=carry.ctx_proj, state=state,
            search=search, t=t, alive=alive,
        ),
        done,
    )


def decode_multi_step(
    params,
    config: Config,
    carry: SlotCarry,
    slot_mask: jnp.ndarray,
    eos_id: int,
    k=1,
    beam_size: Optional[int] = None,
    valid_size: Optional[int] = None,
) -> tuple:
    """Advance the pool by up to ``k`` decode steps in ONE dispatch.

    The inner loop is a ``lax.while_loop`` whose body is *exactly*
    :func:`decode_step` — a slot that seals on inner iteration i drops
    ``alive`` and is excluded from every later iteration by the same
    ``slot_mask & alive`` gate the host-driven loop applies between
    dispatches, so K fused steps are bitwise-identical to K sequential
    ``decode_step`` dispatches (words, scores, alphas, per-slot ``t``).
    Done detection moves on-device: the accumulated ``done`` mask names
    every slot that sealed anywhere inside the window, and the host only
    harvests.  The loop early-exits when nothing is left active, so a
    pool that drains mid-window never burns the full K.

    ``k`` is a dynamic operand — ``lax.while_loop`` takes a traced
    bound, so ONE executable serves every ladder depth and the
    zero-recompile guarantee across the ladder is structural, not a
    warmed-lane-per-K inventory.  Returns ``(carry, done, steps_run)``
    with ``steps_run`` the number of inner iterations actually executed
    (``< k`` on early exit).
    """
    S = carry.t.shape[0]

    def cond(loop):
        i, c, _ = loop
        return (i < k) & jnp.any(slot_mask & c.alive)

    def body(loop):
        i, c, done_acc = loop
        c, done = decode_step(
            params, config, c, slot_mask, eos_id,
            beam_size=beam_size, valid_size=valid_size,
        )
        return (i + 1, c, done_acc | done)

    steps_run, carry, done = jax.lax.while_loop(
        cond, body, (jnp.int32(0), carry, jnp.zeros((S,), jnp.bool_))
    )
    return carry, done, steps_run


def retire_slots(carry: SlotCarry, retire_mask: jnp.ndarray) -> SlotCarry:
    """Mark slots dead after harvest (idempotent — ``decode_step`` already
    cleared ``alive`` for sealed slots; this also covers cancelling a
    still-running slot, e.g. a request whose client gave up)."""
    return carry._replace(alive=carry.alive & ~retire_mask)


def harvest_slots(
    carry: SlotCarry, return_alphas: bool = False
) -> BeamResult:
    """Merge every slot's finished/live beams into ranked results [S, ...]
    (the host slices the done rows).  steps_run is the per-slot [S] step
    count — the continuous path's decode_steps observability probe."""
    K = carry.search.live_logp.shape[1]
    return _merge_results(carry.search, K, return_alphas, steps=carry.t)
