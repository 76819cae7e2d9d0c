"""Operations and bytes of what the dots3-note-prev decoder adds, from
shapes (never from the compiler's cost analysis): the yardsticks of
``lm_swa_prefill_roofline_share``, ``lm_swa_step_roofline_share``,
``lm_dots3_full_prefill_roofline_share`` and
``lm_dots3_full_step_roofline_share``.  A multiply-add counts as 2
operations; only matrix products are counted.

Each count is the LEAST any form must do, so that no sound reading passes
100%: a sliding layer's prefill over ``min(t + 1, window)`` keys a query (a
form that visits whole key tiles does more); a sliding layer's step as
``flops_mla.step_attention`` reckons one (every map once a step, rows in
and out) over the ``min(window, N + t + 1)`` positions a row still sees, of
which the image's share is read once per IMAGE (the kept tail's visible
part) and the row's own once a row; the full layers' prefill and their
steps' indexer + selection + absorbed attention are ``flops_dsa``'s counts
at this stack's full-layer widths (the unprefixed keys of the model block)
and at the number of full layers, every one with an indexer of its own.
``benchmark/tests/test_dots3.py`` holds them against hand counts.
"""

from __future__ import annotations

from typing import Dict

import flops_dsa
from reference.params import context_shape
from reference.params_dots3 import kind_widths


def layers_of(model: dict, kind: str) -> int:
    return sum(k == kind for k in model["layer_types"])


def band_keys(window: int, positions: int) -> int:
    """sum over queries t = 0..positions-1 of min(t + 1, window)."""
    k = min(int(window), positions)
    return k * (k + 1) // 2 + (positions - k) * k


def swa_prefill_flops(model: dict, positions: int) -> float:
    """ONE sliding layer's attention proper over ONE image's ``positions``:
    the expand through W_kvb, scores over nope + rope and the weighted sum
    over v of the keys in each query's band."""
    w = kind_widths(model, "sliding_attention")
    expand = positions * w["kv_rank"] * w["heads"] * (w["nope"] + w["v"])
    band = band_keys(model["sliding_window_size"], positions)
    return 2.0 * (expand + band * w["heads"] * (w["nope"] + w["rope"] + w["v"]))


def swa_prefill_bytes(model: dict, positions: int, itemsize: int = 2) -> float:
    """W_kvb once, the latents in, the queries in, the heads' outputs out."""
    w = kind_widths(model, "sliding_attention")
    return itemsize * (w["kv_rank"] * w["heads"] * (w["nope"] + w["v"]) + positions * (w["kv_rank"] + w["rope"])
                       + positions * w["heads"] * (w["nope"] + w["rope"]) + positions * w["heads"] * w["v"])


def swa_prefill_attention(run) -> Dict[str, float]:
    """Of ONE decoded batch's prefill: every image, every sliding layer."""
    N, _ = context_shape(run.model)
    times = int(run.extras["batch_size"]) * layers_of(run.model, "sliding_attention")
    return {"flops": times * swa_prefill_flops(run.model, N), "bytes": times * swa_prefill_bytes(run.model, N)}


def swa_step_flops(model: dict, rows: int, seen: int) -> float:
    """ONE sliding layer's attention for ``rows`` tokens, each over the
    ``seen`` positions of its window: W_qa, W_qb, W_kva, the gate's map,
    the absorb, scores over rank + rope, the weighted sum over rank, the
    un-absorb, W_o."""
    H = int(model["hidden_size"])
    w = kind_widths(model, "sliding_attention")
    nh, rank, nope, rope, vd = w["heads"], w["kv_rank"], w["nope"], w["rope"], w["v"]
    per_row = (H * w["q_rank"] + w["q_rank"] * nh * (nope + rope) + H * (rank + rope) + H * nh
               + nh * nope * rank + nh * (rank + rope) * seen + nh * rank * seen
               + nh * rank * vd + nh * vd * H)
    return 2.0 * rows * per_row


def swa_step_bytes(model: dict, images: int, rows: int, tail: int, suffix: int, itemsize: int = 2) -> float:
    """Its six maps once, ``tail`` latents of the image's prefix once per
    IMAGE, each row's own ``suffix`` latents, the rows in and out."""
    H = int(model["hidden_size"])
    w = kind_widths(model, "sliding_attention")
    nh, rank, nope, rope, vd = w["heads"], w["kv_rank"], w["nope"], w["rope"], w["v"]
    maps = (H * w["q_rank"] + w["q_rank"] * nh * (nope + rope) + H * (rank + rope) + H * nh
            + rank * nh * (nope + vd) + nh * vd * H)
    return itemsize * (maps + (images * tail + rows * suffix) * (rank + rope) + 2.0 * rows * H)


def swa_step_attention(run) -> Dict[str, float]:
    """Of ONE decoded batch's caption steps: ``batch_size * beam_size`` rows
    a step, every sliding layer; step t (position N + t) sees its own
    t + 1 suffix positions and what is left of the window in the prefix."""
    images = int(run.extras["batch_size"])
    rows = images * int(run.extras["beam_size"])
    N, _ = context_shape(run.model)
    window = int(run.model["sliding_window_size"])
    flops = bytes_ = 0.0
    for t in range(int(run.extras["caption_steps"])):
        own = min(t + 1, window)
        tail = min(window - own, N)
        flops += swa_step_flops(run.model, rows, own + tail)
        bytes_ += swa_step_bytes(run.model, images, rows, tail, own)
    times = layers_of(run.model, "sliding_attention")
    return {"flops": times * flops, "bytes": times * bytes_}


def full_prefill_attention(run) -> Dict[str, float]:
    """Of ONE decoded batch's prefill: every image, every FULL layer, each
    ``flops_dsa``'s count (expand, scores and weighted sum over
    min(t + 1, index_topk) keys a query) at the full layers' widths."""
    N, _ = context_shape(run.model)
    times = int(run.extras["batch_size"]) * layers_of(run.model, "full_attention")
    return {"flops": times * flops_dsa.prefill_attention_flops(run.model, N),
            "bytes": times * flops_dsa.prefill_attention_bytes(run.model, N)}


def full_step_select(run) -> Dict[str, float]:
    """Of ONE decoded batch's caption steps: ``batch_size * beam_size`` rows
    a step, every FULL layer, step t over N + t + 1 visible positions, each
    ``flops_dsa``'s count of a layer WITH an indexer (no layer shares
    another's selection here)."""
    images = int(run.extras["batch_size"])
    rows = images * int(run.extras["beam_size"])
    N, _ = context_shape(run.model)
    flops = bytes_ = 0.0
    for t in range(int(run.extras["caption_steps"])):
        flops += flops_dsa.step_select_flops(run.model, rows, N + t + 1, True)
        bytes_ += flops_dsa.step_select_bytes(run.model, images, rows, N, t + 1, True)
    times = layers_of(run.model, "full_attention")
    return {"flops": times * flops, "bytes": times * bytes_}
