"""Operations and bytes of what the GLM-5.2 decoder adds, from shapes and
from the program's own counters (never from the compiler's cost analysis):
the yardsticks of ``lm_dsa_prefill_roofline_share``,
``lm_dsa_step_roofline_share`` and ``lm_moe_held_experts_roofline_share``.
A multiply-add counts as 2 operations; only matrix products are counted.

Each count is the LEAST any form must do, so that no sound reading passes
100%: the prefill's attention over the causal half and, from position
``index_topk`` on, over ``index_topk`` keys a query (a form that masks a
whole block of keys does more); a step's chosen latents read once a ROW,
or the image's whole prefix once for its K beams where K x index_topk > N
makes that the lesser (it is, in the cell: the program reads it so), each
map once a step, the indexer's keys once per image; the expert products over the
pairs that landed here and the maps of the experts a step VISITED, as the
program counted both.  ``benchmark/tests/test_glm52.py`` holds them
against hand counts.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference.params import context_shape


def _dims(model: dict):
    m = model
    return (int(m["hidden_size"]), int(m["num_attention_heads"]), int(m["kv_lora_rank"]),
            int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"]), int(m["v_head_dim"]))


def _index_dims(model: dict):
    return int(model["q_lora_rank"]), int(model["index_n_heads"]), int(model["index_head_dim"])


def full_layers(model: dict) -> int:
    return sum(kind == "full" for kind in model["indexer_types"])


def attended_keys(model: dict, positions: int) -> int:
    """sum over queries t = 0..positions-1 of min(t + 1, index_topk)."""
    k = min(int(model["index_topk"]), positions)
    return k * (k + 1) // 2 + (positions - k) * k


def prefill_attention_flops(model: dict, positions: int) -> float:
    """ONE layer's attention proper over ONE image's ``positions``: the
    expand through W_kvb, scores over nope + rope and the weighted sum over
    v of the keys each query attends."""
    _, nh, rank, nope, rope, vd = _dims(model)
    expand = positions * rank * nh * (nope + vd)
    return 2.0 * (expand + attended_keys(model, positions) * nh * (nope + rope + vd))


def prefill_attention_bytes(model: dict, positions: int, itemsize: int = 2) -> float:
    """What it has to move: W_kvb once, the latents in, the queries in and
    the heads' outputs out."""
    _, nh, rank, nope, rope, vd = _dims(model)
    return itemsize * (rank * nh * (nope + vd) + positions * (rank + rope)
                       + positions * nh * (nope + rope) + positions * nh * vd)


def prefill_attention(run) -> Dict[str, float]:
    """Of ONE decoded batch's prefill: every image, every layer."""
    N, _ = context_shape(run.model)
    times = int(run.extras["batch_size"]) * len(run.model["layer_types"])
    return {"flops": times * prefill_attention_flops(run.model, N),
            "bytes": times * prefill_attention_bytes(run.model, N)}


def step_select_flops(model: dict, rows: int, visible: int, full: bool) -> float:
    """ONE layer's step for ``rows`` tokens, each seeing ``visible``
    positions: in a layer with an indexer its three maps and its scores
    over every visible position; in every layer the absorb, scores and
    weighted sum over the min(index_topk, visible) chosen latents, the
    un-absorb.  (W_qa, W_qb, W_kva and W_o are the query's, the latent's
    and the output's: not counted here, nor timed.)"""
    H, nh, rank, nope, rope, vd = _dims(model)
    qr, nI, dI = _index_dims(model)
    chosen = min(int(model["index_topk"]), visible)
    per_row = nh * nope * rank + nh * (rank + rope) * chosen + nh * rank * chosen + nh * rank * vd
    if full:
        per_row += qr * nI * dI + H * dI + H * nI + nI * dI * visible
    return 2.0 * rows * per_row


def step_select_bytes(model: dict, images: int, rows: int, prefix: int, suffix: int, full: bool,
                      itemsize: int = 2) -> float:
    """What it has to move: W_kvb once; the chosen latents once a row, or
    the image's prefix once per image where that is less; in a layer with
    an indexer its maps once, the prefix's indexer keys once per image and
    each row's suffix keys; rows in and out."""
    H, nh, rank, nope, rope, vd = _dims(model)
    qr, nI, dI = _index_dims(model)
    chosen = min(int(model["index_topk"]), prefix + suffix)
    latents = min(rows * chosen, images * prefix + rows * suffix) * (rank + rope)
    moved = rank * nh * (nope + vd) + latents + rows * nh * (nope + rope) + rows * nh * vd
    if full:
        moved += qr * nI * dI + H * dI + H * nI + (images * prefix + rows * suffix) * dI + rows * (qr + H)
    return float(itemsize * moved)


def step_select(run) -> Dict[str, float]:
    """Of ONE decoded batch's caption steps: ``batch_size * beam_size``
    rows a step, every layer, step t over N + t + 1 visible positions."""
    images = int(run.extras["batch_size"])
    rows = images * int(run.extras["beam_size"])
    layers, full = len(run.model["layer_types"]), full_layers(run.model)
    N, _ = context_shape(run.model)
    flops = bytes_ = 0.0
    for t in range(int(run.extras["caption_steps"])):
        for is_full, times in ((True, full), (False, layers - full)):
            flops += times * step_select_flops(run.model, rows, N + t + 1, is_full)
            bytes_ += times * step_select_bytes(run.model, images, rows, N, t + 1, is_full)
    return {"flops": flops, "bytes": bytes_}


def held_expert_flops(model: dict, pairs: float) -> float:
    """The three grouped products over ``pairs`` (token, expert) pairs."""
    return 2.0 * 3 * model["hidden_size"] * model["moe_intermediate_size"] * pairs


def held_expert_bytes(model: dict, pairs: float, visited: float, itemsize: int = 2) -> float:
    """The three maps of each of ``visited`` experts once, the pairs' rows
    in and out."""
    return itemsize * (3.0 * visited * model["hidden_size"] * model["moe_intermediate_size"]
                       + 2.0 * pairs * model["hidden_size"])


def step_held_experts(run) -> Dict[str, float]:
    """Of ONE decoded batch's caption steps, from the program's counters
    (``run.extras``, a value a batch; the median batch is taken): the pairs
    that landed on the experts held here, and the experts visited, summed
    over the expert layers and the steps."""
    pairs = float(np.median(run.extras["step_held_pairs"]))
    visited = float(np.median(run.extras["step_experts_visited"]))
    return {"flops": held_expert_flops(run.model, pairs),
            "bytes": held_expert_bytes(run.model, pairs, visited)}
