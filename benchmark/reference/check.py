"""The numbers that decide ``correct``, from the program's outputs and the
reference's (numpy only).  The limits live in the traffic mix's
``limits`` block; PERF.md §2 gives the readings each was set from.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _log_softmax(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    m = x.max(axis=-1, keepdims=True)
    return x - m - np.log(np.exp(x - m).sum(axis=-1, keepdims=True))


def served_numbers(ref_logits: np.ndarray, tokens: np.ndarray, lengths: Sequence[int],
                   reported_logp: Sequence[float], beam: int) -> Dict[str, float]:
    """For captions a beam search of width ``beam`` returned.

    score_gap  widest |reported log-probability - the reference's
               log-probability of the same tokens| (nats).  Precision
               shows here: it sums the log-softmax of every served token.
    rank_gap   widest gap (logit units, floored at 0) by which a served
               token's reference logit lies below the reference's
               (beam+1)-th best at its position.  A beam only ever extends
               a hypothesis by one of its ``beam`` best non-terminator
               words, or ends it when the terminator is among its beam+1
               best, so a sound program never leaves that set; a token
               altered where it is produced, or a caption that belongs to
               another image, does.
    """
    n, T, _V = ref_logits.shape
    logp = _log_softmax(ref_logits)
    idx = np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    tok_lp = np.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    ref_score = (tok_lp * idx).sum(axis=1)
    kth = -np.partition(-ref_logits, beam, axis=-1)[..., beam]
    tok_logit = np.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    below = np.where(idx, kth - tok_logit, -np.inf)
    gaps = np.abs(np.asarray(reported_logp, np.float64) - ref_score)
    return {
        "score_gap": float(gaps.max()),
        "score_gap_mean": float(gaps.mean()),
        "rank_gap": float(max(0.0, below.max())),
        "ref_score": ref_score,                       # type: ignore[dict-item]
    }


def control_numbers(ref_logits: np.ndarray, low_logits: np.ndarray, tokens: np.ndarray,
                    lengths: Sequence[int], beam: int) -> Dict[str, float]:
    """The same two numbers with the lower-precision reference in the
    program's place, at the same prompts and tokens: its log-probability
    of the tokens, and the reference-logit gap of the word it ranks
    (beam+1)-th, the last it could still serve."""
    n, T, _V = ref_logits.shape
    idx = np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    low_lp = np.take_along_axis(_log_softmax(low_logits), tokens[..., None], axis=-1)[..., 0]
    ref_lp = np.take_along_axis(_log_softmax(ref_logits), tokens[..., None], axis=-1)[..., 0]
    kth_ref = -np.partition(-ref_logits, beam, axis=-1)[..., beam]
    low_kth_token = np.argpartition(-low_logits, beam, axis=-1)[..., beam]
    low_choice = np.take_along_axis(ref_logits, low_kth_token[..., None], axis=-1)[..., 0]
    below = np.where(idx, kth_ref - low_choice, -np.inf)
    gaps = np.abs(((low_lp - ref_lp) * idx).sum(axis=1))
    return {
        "score_gap": float(gaps.max()),
        "score_gap_mean": float(gaps.mean()),
        "rank_gap": float(max(0.0, below.max())),
    }


def worst_leaf_gap(program: Dict[str, np.ndarray], reference: Dict[str, np.ndarray]) -> float:
    """Largest |‖program leaf‖ - ‖reference leaf‖| over the leaves, each
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))[:4]}")
    ref_norm = {k: float(np.linalg.norm(v.astype(np.float64))) for k, v in reference.items()}
    median = float(np.median(list(ref_norm.values())))
    worst = 0.0
    for k, v in program.items():
        gap = abs(float(np.linalg.norm(np.asarray(v, np.float64))) - ref_norm[k])
        worst = max(worst, gap / max(ref_norm[k], median, 1e-30))
    return worst


def train_numbers(losses_p: List[float], losses_r: List[float],
                  grad_p: Dict[str, np.ndarray], grad_r: Dict[str, np.ndarray],
                  delta_p: Dict[str, np.ndarray], delta_r: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {f"loss_gap_step{i + 1}": abs(p - r) / abs(r)
           for i, (p, r) in enumerate(zip(losses_p, losses_r))}
    out["grad_norm_gap"] = worst_leaf_gap(grad_p, grad_r)
    out["update_norm_gap"] = worst_leaf_gap(delta_p, delta_r)
    return out
