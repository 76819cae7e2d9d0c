"""Beam search tests: greedy oracle, numpy step-wise oracle, reference-style
host-heap oracle (the algorithm of reference base_model.py:163-240
re-implemented as a correctness baseline), and the no-completion fallback."""

import heapq
import sys
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sat_tpu.config import Config
from sat_tpu.models.decoder import decoder_step, init_decoder_params, init_state
from sat_tpu.ops.beam_search import beam_search, greedy_decode


def tiny_config(**kw) -> Config:
    base = dict(
        cnn="vgg16",
        vocabulary_size=30,
        dim_embedding=12,
        num_lstm_units=16,
        dim_initialize_layer=12,
        dim_attend_layer=12,
        dim_decode_layer=24,
        max_caption_length=6,
        batch_size=3,
        beam_size=3,
        compute_dtype="float32",
    )
    base.update(kw)
    return Config(**base)


EOS = 2  # pretend '.' lives at index 2


def setup(seed=0, B=3, **kw):
    cfg = tiny_config(**kw)
    params = init_decoder_params(jax.random.PRNGKey(seed), cfg)
    contexts = jnp.asarray(
        np.random.default_rng(seed).normal(size=(B, cfg.num_ctx, cfg.dim_ctx)),
        jnp.float32,
    )
    return cfg, params, contexts


def host_step(params, cfg, contexts, state, words):
    """One decoder step on host, returning (state, log-probs)."""
    state, logits, _ = decoder_step(
        params, cfg, contexts, state, jnp.asarray(words, jnp.int32), train=False
    )
    return state, np.asarray(jax.nn.log_softmax(logits, axis=-1))


class TestGreedy:
    def test_greedy_matches_argmax_rollout(self):
        cfg, params, contexts = setup()
        res = greedy_decode(params, cfg, contexts, eos_id=EOS)
        B, T = contexts.shape[0], cfg.max_caption_length

        state = init_state(params, cfg, contexts)
        words = np.zeros((B,), np.int32)
        done = np.zeros((B,), bool)
        out = np.zeros((B, T), np.int32)
        logp_total = np.zeros((B,), np.float64)
        for t in range(T):
            state, logp = host_step(params, cfg, contexts, state, words)
            # greedy == beam 1: continuation excludes eos; eos closes the beam
            for b in range(B):
                if done[b]:
                    continue
                best = int(np.argmax(logp[b]))
                if best == EOS:
                    out[b, t] = EOS
                    logp_total[b] += logp[b, EOS]
                    done[b] = True
                else:
                    cont = logp[b].copy()
                    cont[EOS] = -np.inf
                    w = int(np.argmax(cont))
                    out[b, t] = w
                    logp_total[b] += cont[w]
                    words[b] = w

        got = np.asarray(res.words[:, 0])
        for b in range(B):
            L = int(res.lengths[b, 0])
            finished = EOS in out[b]
            if finished:
                exp_len = int(np.argmax(out[b] == EOS)) + 1
                assert L == exp_len
                np.testing.assert_array_equal(got[b, :L], out[b, :L])


class TestBeamOracle:
    def _numpy_beam(self, cfg, params, contexts, K, T):
        """Step-wise numpy implementation of OUR semantics (global top-K,
        log-space, eos completes)."""
        B = contexts.shape[0]
        V = cfg.vocabulary_size
        state0 = init_state(params, cfg, contexts)
        # replicate per beam via flat batch
        ctx_rep = jnp.repeat(contexts, K, axis=0)
        state = type(state0)(*(jnp.repeat(s, K, axis=0) for s in state0))
        live_logp = np.full((B, K), -1e30)
        live_logp[:, 0] = 0.0
        live_words = np.zeros((B, K, T), np.int32)
        live_len = np.zeros((B, K), np.int32)
        last = np.zeros((B, K), np.int32)
        fin = [[] for _ in range(B)]  # list of (logp, words, len)

        for t in range(T):
            state, step_logp = host_step(
                params, cfg, ctx_rep, state, last.reshape(-1)
            )
            step_logp = step_logp.reshape(B, K, V)
            logp = step_logp + live_logp[..., None]
            for b in range(B):
                # completions — gated on eos being in the beam's top-(K+1)
                for k in range(K):
                    kth = np.sort(step_logp[b, k])[-min(K + 1, V)]
                    if step_logp[b, k, EOS] < kth:
                        continue
                    w = live_words[b, k].copy()
                    w[t] = EOS
                    fin[b].append((logp[b, k, EOS], w, live_len[b, k] + 1))
                fin[b] = sorted(fin[b], key=lambda x: -x[0])[:K]
            cont = logp.copy()
            cont[:, :, EOS] = -np.inf
            flat = cont.reshape(B, K * V)
            sel = np.argsort(-flat, axis=1)[:, :K]
            parent, word = sel // V, sel % V
            new_words = np.zeros_like(live_words)
            new_len = np.zeros_like(live_len)
            ns = [np.asarray(s).reshape(B, K, -1) for s in state]
            picked = [np.zeros_like(s) for s in ns]
            for b in range(B):
                for k in range(K):
                    p = parent[b, k]
                    new_words[b, k] = live_words[b, p]
                    new_words[b, k, t] = word[b, k]
                    new_len[b, k] = live_len[b, p] + 1
                    for i in range(3):
                        picked[i][b, k] = ns[i][b, p]
                live_logp[b] = flat[b, sel[b]]
            live_words, live_len, last = new_words, new_len, word.astype(np.int32)
            state = type(state0)(
                *(jnp.asarray(p.reshape(B * K, -1), jnp.float32) for p in picked)
            )
        return fin

    def test_matches_numpy_oracle(self):
        cfg, params, contexts = setup(seed=3)
        # nudge eos into contention so completions actually happen
        bias = np.asarray(params["decode"]["fc_2"]["bias"]).copy()
        bias[EOS] += 1.5
        params["decode"]["fc_2"]["bias"] = jnp.asarray(bias)
        K, T = cfg.beam_size, cfg.max_caption_length
        res = beam_search(params, cfg, contexts, eos_id=EOS)
        fin = self._numpy_beam(cfg, params, contexts, K, T)
        for b in range(contexts.shape[0]):
            assert fin[b], "oracle found no completions; reseed the test"
            n = len(fin[b])
            exp_scores = [s for s, _, _ in fin[b]]
            np.testing.assert_allclose(
                np.asarray(res.log_scores[b, :n]), exp_scores, rtol=1e-4, atol=1e-4
            )
            best_words = fin[b][0][1]
            L = fin[b][0][2]
            np.testing.assert_array_equal(
                np.asarray(res.words[b, 0, :L]), best_words[:L]
            )

    def test_at_least_as_good_as_reference_heap_semantics(self):
        """Reference algorithm (per-beam top-(K+1), prob products, TopN
        heaps) re-implemented on host; our global-top-K search must find a
        best caption with score >= the reference's."""
        cfg, params, contexts = setup(seed=11)
        K, T = cfg.beam_size, cfg.max_caption_length
        B = contexts.shape[0]
        state0 = init_state(params, cfg, contexts)

        # ---- reference-style host search (one image at a time) ----
        ref_best = []
        for b in range(B):
            ctx_b = contexts[b : b + 1]
            partial = [([], np.asarray(state0.memory[b]),
                        np.asarray(state0.output[b]), 1.0)]
            complete = []
            for t in range(T):
                expansions = []
                for sent, mem, out, score in partial:
                    st = type(state0)(
                        memory=jnp.asarray(mem[None]),
                        output=jnp.asarray(out[None]),
                        recurrent=jnp.asarray(out[None]),
                    )
                    word_in = sent[-1] if sent else 0
                    st2, logp = host_step(params, cfg, ctx_b, st, [word_in])
                    probs = np.exp(logp[0])
                    top = np.argsort(-probs)[: K + 1]
                    for w in top:
                        cand = (sent + [int(w)], np.asarray(st2.memory[0]),
                                np.asarray(st2.output[0]), score * probs[w])
                        if w == EOS:
                            complete.append(cand)
                        else:
                            expansions.append(cand)
                complete = sorted(complete, key=lambda x: -x[3])[:K]
                partial = sorted(expansions, key=lambda x: -x[3])[:K]
            pool = complete if complete else partial
            ref_best.append(max(c[3] for c in pool))

        res = beam_search(params, cfg, contexts, eos_id=EOS)
        ours = np.exp(np.asarray(res.log_scores[:, 0], np.float64))
        for b in range(B):
            assert ours[b] >= ref_best[b] * (1 - 1e-4), (b, ours[b], ref_best[b])


class TestFallback:
    def test_no_completion_returns_partials(self):
        """Suppress eos by giving it a huge negative embedding-path logit:
        easier — just use an eos_id the model can't prefer and tiny T with
        a vocab where eos never tops; verify lengths == T when nothing
        finished."""
        cfg, params, contexts = setup(seed=5)
        # make eos catastrophically unlikely by biasing the decode layer
        p2 = jax.tree_util.tree_map(lambda x: x, params)
        bias = np.asarray(p2["decode"]["fc_2"]["bias"]).copy()
        bias[EOS] = -1e9
        p2["decode"]["fc_2"]["bias"] = jnp.asarray(bias)
        res = beam_search(p2, cfg, contexts, eos_id=EOS)
        T = cfg.max_caption_length
        assert (np.asarray(res.lengths) == T).all()
        assert (np.asarray(res.words) != EOS).all()
        # scores sorted descending
        s = np.asarray(res.log_scores)
        assert (np.diff(s, axis=1) <= 1e-6).all()

    def test_partial_slots_backfilled_with_live_beams(self):
        """Images with 1..K-1 completions must not surface -inf junk rows:
        unfilled slots come from the live partial beams."""
        cfg, params, contexts = setup(seed=3)
        bias = np.asarray(params["decode"]["fc_2"]["bias"]).copy()
        bias[EOS] += 1.5  # some but rarely K completions per image
        params["decode"]["fc_2"]["bias"] = jnp.asarray(bias)
        res = beam_search(params, cfg, contexts, eos_id=EOS)
        s = np.asarray(res.log_scores)
        assert (s > -1e15).all(), "junk sentinel rows leaked into results"
        words = np.asarray(res.words)
        lengths = np.asarray(res.lengths)
        T = cfg.max_caption_length
        for b in range(words.shape[0]):
            for k in range(cfg.beam_size):
                finished = EOS in words[b, k]
                # a backfilled partial is a full-length eos-free rollout
                assert finished or lengths[b, k] == T

    def test_beam1_equals_greedy(self):
        cfg, params, contexts = setup(seed=7)
        r1 = beam_search(params, cfg, contexts, eos_id=EOS, beam_size=1)
        r2 = greedy_decode(params, cfg, contexts, eos_id=EOS)
        np.testing.assert_array_equal(np.asarray(r1.words), np.asarray(r2.words))


def test_returned_alphas_match_teacher_forced_replay():
    """The winning caption's attention maps must equal the alphas obtained
    by replaying that exact word sequence through decoder_step — pins the
    per-step parent-gather bookkeeping of the alpha carry."""
    cfg, params, contexts = setup(seed=5, B=3)
    out = beam_search(params, cfg, contexts, EOS, return_alphas=True)
    B, K, T, N = out.alphas.shape
    assert (B, K, T, N) == (3, 3, cfg.max_caption_length, cfg.num_ctx)

    for b in range(B):
        for k in range(K):
            words = np.asarray(out.words[b, k])
            length = int(out.lengths[b, k])
            state = init_state(params, cfg, contexts[b : b + 1], train=False)
            for t in range(length):
                last = 0 if t == 0 else int(words[t - 1])
                state, _, alpha = decoder_step(
                    params, cfg, contexts[b : b + 1], state,
                    jnp.asarray([last], jnp.int32), train=False,
                )
                np.testing.assert_allclose(
                    np.asarray(out.alphas[b, k, t]),
                    np.asarray(alpha[0]),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"b={b} k={k} t={t}",
                )
            # rows sum to 1 inside the caption, stay zero past its end
            sums = np.asarray(out.alphas[b, k]).sum(-1)
            np.testing.assert_allclose(sums[:length], 1.0, rtol=1e-5)
            np.testing.assert_allclose(sums[length:], 0.0, atol=1e-7)


def test_alphas_off_by_default_and_costless():
    cfg, params, contexts = setup(seed=3, B=2)
    out = beam_search(params, cfg, contexts, EOS)
    assert out.alphas is None


def test_valid_size_masks_phantom_vocab_columns():
    """A vocabulary smaller than config.vocabulary_size leaves trailing
    logit columns with no word (reference vocabulary.py:25-26 shrinks the
    vocab; its word list would be indexed past the end).  With valid_size
    set, no emitted token id may reach the phantom range."""
    from sat_tpu.config import Config
    from sat_tpu.models import init_decoder_params
    from sat_tpu.ops.beam_search import beam_search_jit

    config = Config(
        vocabulary_size=50,
        dim_embedding=16,
        num_lstm_units=16,
        dim_initialize_layer=16,
        dim_attend_layer=16,
        dim_decode_layer=32,
        max_caption_length=6,
        compute_dtype="float32",
    )
    params = init_decoder_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(0)
    contexts = jnp.asarray(rng.normal(size=(3, 8, 512)).astype(np.float32))

    valid = 17
    out = beam_search_jit(
        params, config, contexts, eos_id=3, beam_size=3, valid_size=valid
    )
    words = np.asarray(out.words)
    lengths = np.asarray(out.lengths)
    for b in range(words.shape[0]):
        for k in range(words.shape[1]):
            emitted = words[b, k, : lengths[b, k]]
            assert (emitted < valid).all(), (b, k, emitted)


def test_early_exit_is_exact():
    """The while_loop early exit (stop once every image's finished set can
    no longer change) must return bit-identical results to the full
    T-step control, across seeds and beam widths — including models whose
    beams complete at different steps per image."""
    for seed in range(6):
        for K in (1, 2, 3):
            cfg, params, contexts = setup(seed=seed, B=4, beam_size=K,
                                          max_caption_length=8)
            full = beam_search(
                params, cfg, contexts, EOS, early_exit=False,
                return_alphas=True,
            )
            fast = beam_search(
                params, cfg, contexts, EOS, early_exit=True,
                return_alphas=True,
            )
            np.testing.assert_array_equal(
                np.asarray(fast.words), np.asarray(full.words),
                err_msg=f"seed={seed} K={K}",
            )
            np.testing.assert_array_equal(
                np.asarray(fast.lengths), np.asarray(full.lengths)
            )
            np.testing.assert_array_equal(
                np.asarray(fast.log_scores), np.asarray(full.log_scores)
            )
            np.testing.assert_array_equal(
                np.asarray(fast.alphas), np.asarray(full.alphas)
            )


def test_early_exit_actually_exits():
    """With the decode bias rigged so eos dominates every step, all beams
    finish immediately; the early-exit search must (a) still equal the
    full-length control and (b) demonstrably stop.  The stop is asserted
    on the deterministic steps_run probe (the while_loop's final t), not
    wall-clock — timing on a loaded CI box is advisory only (ADVICE r3)."""
    import time

    cfg, params, contexts = setup(seed=1, B=4, beam_size=3,
                                  max_caption_length=40)
    # rig the vocab-logit bias: eos wins by a mile at every step
    p = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy via rebuild
    fc = "fc" if "fc" in p["decode"] else list(p["decode"].keys())[-1]
    bias = np.asarray(p["decode"][fc]["bias"]).copy()
    bias[EOS] += 50.0
    p["decode"][fc]["bias"] = jnp.asarray(bias)

    full = jax.jit(
        lambda c: beam_search(p, cfg, c, EOS, early_exit=False,
                              return_steps=True)
    )
    fast = jax.jit(
        lambda c: beam_search(p, cfg, c, EOS, early_exit=True,
                              return_steps=True)
    )
    rf = full(contexts)
    rx = fast(contexts)
    np.testing.assert_array_equal(np.asarray(rx.words), np.asarray(rf.words))
    # beam 0 completes at step 0; the other fin slots fill at step 1 —
    # nothing survives past two tokens when eos dominates
    assert int(np.asarray(rx.lengths).max()) <= 2

    # the deterministic signal: the control runs all 40 iterations, the
    # exited program stops as soon as every image is sealed (~2 steps;
    # ≤4 leaves margin for the one extra cond evaluation per fill step)
    assert int(np.asarray(rf.steps_run)) == 40
    assert int(np.asarray(rx.steps_run)) <= 4, int(np.asarray(rx.steps_run))

    def steady(fn):
        jax.block_until_ready(fn(contexts))
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(contexts)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    t_full, t_fast = steady(full), steady(fast)
    if t_fast >= t_full / 2:  # advisory: report, don't flake
        import warnings

        warnings.warn(
            f"early-exit wall-clock advisory: fast={t_fast:.3f}s "
            f"full={t_full:.3f}s (deterministic steps_run check passed)"
        )


# ---------------------------------------------------------------------------
# The per-beam top-(K+1) threshold: one exact value per row, by whatever
# selection — pinned bit for bit against the sliced rank-3 ``top_k`` it
# replaced (which XLA:TPU lowered to a full sort of the vocabulary axis)
# ---------------------------------------------------------------------------

_bs = sys.modules["sat_tpu.ops.beam_search"]  # the module, not the function


def _threshold_rows(case, K, rng):
    """[B, K, V] float32 next-word log-probabilities of one step."""
    B, V = 4, 37
    if case == "random":
        logits = rng.normal(size=(B, K, V))
    elif case == "ties":
        # a handful of distinct values, so every row repeats its maximum
        # and holds exact ties at and around rank K+1
        logits = rng.integers(0, 3, size=(B, K, V)).astype(np.float32)
        logits[0] = 1.0  # a row of one value throughout
    elif case == "masked_tail":
        logits = rng.normal(size=(B, K, V))
        logits[..., V - 9:] = _bs.NEG_INF  # what valid_size does
        logits[1, :, K:] = _bs.NEG_INF     # fewer real words than K+1
    elif case == "vocab_not_over_k":
        logits = rng.normal(size=(B, K, K))  # V == K: min(K+1, V) guards
    else:
        raise AssertionError(case)
    return jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)


@pytest.mark.parametrize("K", [1, 3, 5])
@pytest.mark.parametrize(
    "case", ["random", "ties", "masked_tail", "vocab_not_over_k"]
)
def test_kth_threshold_is_bitwise_the_sliced_top_k(case, K):
    step_logp = _threshold_rows(case, K, np.random.default_rng(K))
    B, _, V = step_logp.shape
    k = min(K + 1, V)
    want = jax.lax.top_k(step_logp, k)[0][..., -1]
    got = jax.jit(_bs._top_rows, static_argnums=1)(
        step_logp.reshape(B * K, V), k
    )[0].reshape(B, K)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and what the step makes of it, for an eos in any column
    np.testing.assert_array_equal(
        np.asarray(step_logp >= got[..., None]),
        np.asarray(step_logp >= want[..., None]),
    )


def _parent_expand_step(eos_id, K, V, An, valid_size, new_state, logits,
                        alpha, t_vec, s):
    """``_expand_step`` as it stood before the threshold changed (commit
    5072338), scopes dropped: one ``top_k`` over ``[B, K*V]`` and a sliced
    rank-3 ``top_k`` for the threshold.  The oracle of the whole-search
    cases and of the step-level ones below."""
    from sat_tpu.models.decoder import DecoderState

    NEG_INF = _bs.NEG_INF
    B = s.live_logp.shape[0]
    T = s.live_words.shape[2]
    H = new_state.output.shape[-1]
    batch_idx = jnp.arange(B)[:, None]
    t_hot = jnp.arange(T)[None, :] == t_vec[:, None]

    step_alpha = alpha.reshape(B, K, -1)[:, :, :An]
    if valid_size is not None and valid_size < V:
        logits = logits.at[:, valid_size:].set(NEG_INF)
    step_logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    step_logp = step_logp.reshape(B, K, V)
    logp = step_logp + s.live_logp[..., None]

    kth = jax.lax.top_k(step_logp, min(K + 1, V))[0][..., -1]
    eos_allowed = step_logp[:, :, eos_id] >= kth
    eos_scores = jnp.where(eos_allowed, logp[:, :, eos_id], NEG_INF)
    eos_words = jnp.where(t_hot[:, None, :], jnp.int32(eos_id), s.live_words)
    eos_len = s.live_len + 1
    eos_alphas = jnp.where(
        t_hot[:, None, :, None], step_alpha[:, :, None, :], s.live_alphas
    )
    cand_logp = jnp.concatenate([s.fin_logp, eos_scores], axis=1)
    cand_words = jnp.concatenate([s.fin_words, eos_words], axis=1)
    cand_len = jnp.concatenate([s.fin_len, eos_len], axis=1)
    cand_alphas = jnp.concatenate([s.fin_alphas, eos_alphas], axis=1)
    fin_logp, fin_sel = jax.lax.top_k(cand_logp, K)
    fin_words = cand_words[batch_idx, fin_sel]
    fin_len = cand_len[batch_idx, fin_sel]
    fin_alphas = cand_alphas[batch_idx, fin_sel]

    cont = logp.at[:, :, eos_id].set(NEG_INF).reshape(B, K * V)
    top_live, flat_sel = jax.lax.top_k(cont, K)
    parent = flat_sel // V
    word = (flat_sel % V).astype(jnp.int32)

    gather_bk = lambda x: x.reshape(B, K, -1)[batch_idx, parent]  # noqa: E731
    state = DecoderState(
        memory=gather_bk(new_state.memory).reshape(B * K, H),
        output=gather_bk(new_state.output).reshape(B * K, H),
        recurrent=gather_bk(new_state.recurrent).reshape(B * K, H),
    )
    live_words = jnp.where(
        t_hot[:, None, :], word[:, :, None], s.live_words[batch_idx, parent]
    )
    live_len = s.live_len[batch_idx, parent] + 1
    live_alphas = jnp.where(
        t_hot[:, None, :, None],
        step_alpha[batch_idx, parent][:, :, None, :],
        s.live_alphas[batch_idx, parent],
    )
    return state, _bs.SearchState(
        live_logp=top_live, live_words=live_words, live_len=live_len,
        last_word=word, fin_logp=fin_logp, fin_words=fin_words,
        fin_len=fin_len, live_alphas=live_alphas, fin_alphas=fin_alphas,
    )


@pytest.mark.parametrize(
    "seed,K,V,valid",
    [(3, 1, 10, None), (0, 3, 10, None), (2, 5, 10, None), (1, 5, 12, 8)],
    ids=["greedy", "beam3", "beam5", "beam5-valid8"],
)
def test_whole_search_equals_the_parents_expand_step(
    monkeypatch, seed, K, V, valid
):
    """words, log_scores, lengths and alphas of a whole search, bit for
    bit against the same engine run over the parent's step body — on
    inputs where the threshold decides captions: a threshold one rank
    off, either way, changes the result."""
    cfg, params, contexts = setup(seed=seed, B=5, beam_size=K,
                                  max_caption_length=8, vocabulary_size=V)

    def search():
        # a fresh jit each time, so the trace reads what is patched in
        return jax.jit(lambda c: beam_search(
            params, cfg, c, EOS, valid_size=valid,
            return_alphas=True, return_steps=True,
        ))(contexts)

    def same(a, b):
        return all(
            np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b)
        )

    got = search()
    with monkeypatch.context() as m:
        m.setattr(_bs, "_expand_step", _parent_expand_step)
        want = search()
    for name in got._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=name,
        )
    top_rows = _bs._top_rows
    for off in (-1, 1):
        with monkeypatch.context() as m:
            # the threshold alone: the candidates stay the K+1 they were
            m.setattr(
                _bs, "_top_rows",
                lambda rows, k, off=off: (
                    top_rows(rows, max(1, min(k + off, rows.shape[-1])))[0],
                    *top_rows(rows, k)[1:],
                ),
            )
            assert not same(search(), want), f"rank {off:+d} went unnoticed"


# ---------------------------------------------------------------------------
# The step selects per row: one top-(K+1) over the [B*K, V] rows, then a
# merge of K x (K+1) candidates.  Pinned bit for bit, every output of the
# step, against the parent's one ``top_k`` over [B, K*V]
# ---------------------------------------------------------------------------


class _StepInputs(NamedTuple):
    eos_id: int
    V: int
    valid: Optional[int]
    new_state: tuple
    logits: jnp.ndarray   # [B*K, V]
    alpha: jnp.ndarray
    t_vec: jnp.ndarray
    s: tuple              # SearchState


def _step_inputs(case, K, eos_at, rng):
    """One step over B = 4 images mid-search: every beam's words, lengths
    and state rows differ, so a wrong parent shows in each gathered
    output."""
    from sat_tpu.models.decoder import DecoderState

    B, T, H, An = 4, 5, 3, 2
    V = {"vocab_k_plus_1": K + 1, "vocab_k": K}.get(case, 37)
    eos_id = {"first": 0, "mid": V // 2, "last": V - 1}[eos_at]
    valid = None
    if case == "ties":
        # a handful of distinct values: exact ties within a row at and
        # around rank K+1, eos among them; beams 0 and 1 of an image see
        # the same row from the same score, so they tie across beams too
        logits = rng.integers(0, 3, size=(B, K, V)).astype(np.float32)
        if K > 1:
            logits[:, 1] = logits[:, 0]
        logits[0] = 1.0  # an image of one value throughout
    else:
        logits = rng.normal(size=(B, K, V)).astype(np.float32)
        # eos inside the top K+1 of some rows and the best word of others
        logits[1, :, eos_id] += 2.0
        logits[2, 0, eos_id] += 9.0
    if case == "masked_tail":
        valid = V - 9           # eos "last" then lies in the masked tail
        logits[3, :, K:] = _bs.NEG_INF  # fewer real words than K+1

    s = _bs._init_search(B, K, T, An)
    t = 0
    if case != "step0":
        t = 2
        live = -rng.uniform(1.0, 9.0, size=(B, K)).astype(np.float32)
        if case == "ties" and K > 1:
            live[:, 1] = live[:, 0]
        fin = np.where(
            rng.random((B, K)) < 0.5, -rng.uniform(1.0, 20.0, size=(B, K)),
            _bs.NEG_INF,
        ).astype(np.float32)
        s = s._replace(
            live_logp=jnp.asarray(live),
            live_words=jnp.asarray(
                rng.integers(0, V, size=(B, K, T)), jnp.int32
            ),
            live_len=jnp.asarray(rng.integers(1, 3, size=(B, K)), jnp.int32),
            fin_logp=jnp.asarray(-np.sort(-fin, axis=1)),
            fin_words=jnp.asarray(
                rng.integers(0, V, size=(B, K, T)), jnp.int32
            ),
            live_alphas=jnp.asarray(
                rng.random((B, K, T, An)), jnp.float32
            ),
        )
    new_state = DecoderState(*(
        jnp.asarray(rng.normal(size=(B * K, H)), jnp.float32) for _ in range(3)
    ))
    alpha = jnp.asarray(rng.random((B * K, An + 1)), jnp.float32)
    return _StepInputs(
        eos_id, V, valid, new_state,
        jnp.asarray(logits.reshape(B * K, V)), alpha,
        jnp.full((B,), t, jnp.int32), s,
    )


def _run_step(step, K, i):
    An = i.s.live_alphas.shape[3]
    return jax.jit(
        lambda ns, lg, al, tv, ss: step(
            i.eos_id, K, i.V, An, i.valid, ns, lg, al, tv, ss
        )
    )(i.new_state, i.logits, i.alpha, i.t_vec, i.s)


def _assert_same_step(got, want):
    g, w = (jax.tree_util.tree_leaves_with_path(x) for x in (got, want))
    assert len(g) == len(w)
    for (path, a), (_, b) in zip(g, w):
        assert a.dtype == b.dtype, path
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )


@pytest.mark.parametrize("eos_at", ["first", "mid", "last"])
@pytest.mark.parametrize("K", [1, 3, 5])
@pytest.mark.parametrize(
    "case",
    ["random", "ties", "step0", "masked_tail", "vocab_k_plus_1", "vocab_k"],
)
def test_step_selects_per_row_what_one_top_k_over_beams_by_vocabulary_did(
    case, K, eos_at
):
    """Every output of one step (the reordered state; live scores, words,
    lengths, maps and the next input word, which carry ``top_live``,
    ``parent`` and ``word``; the finished set, which carries
    ``eos_scores``) bit for bit the oracle's, dtypes included."""
    inputs = _step_inputs(case, K, eos_at, np.random.default_rng(7 * K + 1))
    _assert_same_step(
        _run_step(_bs._expand_step, K, inputs),
        _run_step(_parent_expand_step, K, inputs),
    )


@pytest.mark.parametrize(
    "mutant,first_seen_in",
    # the first leaf to differ: a wrong parent, a wrong word of beam 0
    [("k_candidates_a_row", "memory"), ("raw_logits", "live_words")],
)
def test_step_parity_notices_a_narrower_or_a_raw_selection(
    monkeypatch, mutant, first_seen_in
):
    """The two shortcuts the step must not take, each made here and seen
    by the comparison above.  K candidates a row lose a beam's K-th word
    where eos is among its top K.  A selection on the raw logits orders
    two words by logits that round to ONE log-probability, where the
    search's order is the lower index first."""
    B = 4
    top_rows = _bs._top_rows
    rng = np.random.default_rng(0)
    if mutant == "k_candidates_a_row":
        K = 3
        inputs = _step_inputs("random", K, "mid", rng)
        # eos is every row's best word, and one beam an image so far
        # ahead that all K continuations are its own
        logits = np.asarray(inputs.logits).copy()
        logits[:, inputs.eos_id] += 9.0
        live = np.asarray(inputs.s.live_logp).copy()
        live[:, 1:] -= 50.0
        inputs = inputs._replace(
            logits=jnp.asarray(logits),
            s=inputs.s._replace(live_logp=jnp.asarray(live)),
        )

        def patched(rows, k):
            # the threshold as it is; the (K+1)-th candidate void
            kth, vals, idx = top_rows(rows, k)
            return kth, vals.at[:, K:].set(_bs.NEG_INF), idx
    else:
        K = 1
        inputs = _step_inputs("random", K, "first", rng)
        # words 3 and 5 lead every row by ten nats, their logits one ulp
        # apart with the higher at the higher index; a log-sum-exp of
        # log 2 rounds both to one log-probability
        logits = np.asarray(inputs.logits).copy() - 10.0
        logits[:, 3] = np.float32(1e-3)
        logits[:, 5] = np.nextafter(np.float32(1e-3), np.float32(1.0))
        raw = jnp.asarray(logits)
        logp = np.asarray(jax.nn.log_softmax(raw, axis=-1))
        assert (logp[:, 3] == logp[:, 5]).all(), "the ulp survived: resize it"
        inputs = inputs._replace(logits=raw)

        def patched(rows, k):
            kth = top_rows(rows, k)[0]
            _, idx = jax.lax.top_k(raw, k)
            return kth, jnp.take_along_axis(rows, idx, axis=-1), idx

    want = _run_step(_parent_expand_step, K, inputs)
    _assert_same_step(_run_step(_bs._expand_step, K, inputs), want)
    monkeypatch.setattr(_bs, "_top_rows", patched)
    with pytest.raises(AssertionError, match=first_seen_in):
        _assert_same_step(_run_step(_bs._expand_step, K, inputs), want)


@pytest.mark.parametrize("decoder", ["lstm", "lfm2_moe"])
def test_a_decoder_with_no_leaf_read_at_a_source_row_gets_the_state_tree_it_got(decoder):
    """``StepState.at_source`` / ``source`` are for a decoder that says so
    of a leaf (``qwen3_next``'s recurrent state).  The LSTM still hands the
    search its plain ``DecoderState``; a language model with none still a
    ``StepState`` of ``beam`` and ``shared`` alone, the two new fields None
    before and after a reorder, so its program has the leaves it had, all
    of ``beam`` gathered by parent."""
    from sat_tpu.models import decoders
    from sat_tpu.models.decoder import DecoderState

    B, K = 2, 3
    if decoder == "lstm":
        cfg, params, contexts = setup(B=B)
    else:
        from test_lfm2 import CONFIG as cfg

        params = jax.eval_shape(lambda: decoders.init_params(jax.random.PRNGKey(0), cfg))
        params = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, x.dtype), params)
        contexts = jnp.ones((B, cfg.num_ctx, cfg.dim_ctx), jnp.float32)
    state0 = decoders.search(params, cfg, contexts, K, cfg.max_caption_length).state0

    def numbered(x):     # every per-beam leaf holds its row's number
        return (jnp.zeros_like(x) + jnp.arange(B * K).reshape((B * K,) + (1,) * (x.ndim - 1))).astype(x.dtype)

    if decoder == "lstm":
        state0 = jax.tree_util.tree_map(numbered, state0)
    else:
        state0 = state0._replace(beam=jax.tree_util.tree_map(numbered, state0.beam))
    parent = jnp.array([[2, 0, 1], [1, 1, 0]])
    rows = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
    moved = _bs._reorder_beams(state0, B, K, jnp.arange(B)[:, None], parent)
    assert jax.tree_util.tree_structure(moved) == jax.tree_util.tree_structure(state0)
    if decoder == "lstm":
        assert isinstance(state0, DecoderState) and isinstance(moved, DecoderState)
        beams, moved_beams = state0, moved
    else:
        assert isinstance(state0, decoders.StepState)
        assert state0.at_source is None and state0.source is None
        assert moved.at_source is None and moved.source is None
        assert jax.tree_util.tree_structure(state0) == jax.tree_util.tree_structure(
            decoders.StepState(state0.beam, state0.shared)
        )
        for a, b in zip(jax.tree_util.tree_leaves(moved.shared), jax.tree_util.tree_leaves(state0.shared)):
            assert np.array_equal(a, b)
        beams, moved_beams = state0.beam, moved.beam
    for a, b in zip(jax.tree_util.tree_leaves(moved_beams), jax.tree_util.tree_leaves(beams)):
        assert np.array_equal(np.asarray(a), np.asarray(b)[np.asarray(rows)])
