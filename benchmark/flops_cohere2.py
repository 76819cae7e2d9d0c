"""Operations and bytes of what the command-a-plus decoder adds, from
shapes (never from the compiler's cost analysis): the yardsticks of
``lm_gqa_window_prefill_roofline_share`` and ``lm_gqa_step_roofline_share``
(``lm_gqa_held_experts_roofline_share`` takes ``flops_dsa.step_held_experts``
as it is: its count reads the hidden size and the expert width from the
model block, and the pairs and the experts visited from the program's
counters).  A multiply-add counts as 2 operations; only matrix products are
counted.

Each count is the LEAST any form must do, so that no sound reading passes
100%: a sliding layer's prefill attention over ``min(t + 1, window)`` keys
a query (a kernel that visits whole key tiles does more), its keys and
values read once a KEY/VALUE head (a form that replicates them over the
group reads sixteen times that); the steps' attention of both kinds with
every map once a step, an image's kept prefix (a sliding layer's visible
tail, the full layer's N positions) once per IMAGE and never once a beam,
the rows' own suffixes once a row.  The full layer's PREFILL attention has
no yardstick because the program has no such op: it is the last kept layer,
and in a parallel block nothing at a prefix position reads the last
layer's output (PERF.md section 4).  ``benchmark/tests/test_cohere2.py``
holds the counts against hand counts.
"""

from __future__ import annotations

from typing import Dict

from reference.params import context_shape
from reference.params_cohere2 import head_dim


def layers_of(model: dict, kind: str) -> int:
    return sum(k == kind for k in model["layer_types"])


def band_keys(window: int, positions: int) -> int:
    """sum over queries t = 0..positions-1 of min(t + 1, window)."""
    k = min(int(window), positions)
    return k * (k + 1) // 2 + (positions - k) * k


def window_prefill_flops(model: dict, positions: int) -> float:
    """ONE sliding layer's scores and weighted sum over ONE image's
    ``positions``: every query head over the keys of its query's band."""
    nh, d = int(model["num_attention_heads"]), head_dim(model)
    return 2.0 * band_keys(model["sliding_window_size"], positions) * nh * 2 * d


def window_prefill_bytes(model: dict, positions: int, itemsize: int = 2) -> float:
    """The queries in, keys and values once a key/value head, the heads'
    outputs out."""
    nh, kv, d = int(model["num_attention_heads"]), int(model["num_key_value_heads"]), head_dim(model)
    return itemsize * positions * d * (2 * nh + 2 * kv)


def window_prefill_attention(run) -> Dict[str, float]:
    """Of ONE decoded batch's prefill: every image, every sliding layer."""
    N, _ = context_shape(run.model)
    times = int(run.extras["batch_size"]) * layers_of(run.model, "sliding_attention")
    return {"flops": times * window_prefill_flops(run.model, N), "bytes": times * window_prefill_bytes(run.model, N)}


def step_flops(model: dict, rows: int, seen: int) -> float:
    """ONE layer's attention for ``rows`` tokens, each over ``seen``
    positions: W_q, W_k, W_v, W_o, scores and weighted sum."""
    H = int(model["hidden_size"])
    nh, kv, d = int(model["num_attention_heads"]), int(model["num_key_value_heads"]), head_dim(model)
    return 2.0 * rows * (2 * H * nh * d + 2 * H * kv * d + 2 * nh * d * seen)


def step_bytes(model: dict, images: int, rows: int, kept: int, own: int, itemsize: int = 2) -> float:
    """Its four maps once, ``kept`` positions of keys and of values of the
    image's prefix once per IMAGE, each row's ``own`` suffix positions, the
    rows in and out."""
    H = int(model["hidden_size"])
    nh, kv, d = int(model["num_attention_heads"]), int(model["num_key_value_heads"]), head_dim(model)
    maps = 2 * H * nh * d + 2 * H * kv * d
    return itemsize * (maps + 2.0 * (images * kept + rows * own) * kv * d + 2.0 * rows * H)


def step_attention(run) -> Dict[str, float]:
    """Of ONE decoded batch's caption steps: ``batch_size * beam_size`` rows
    a step, every layer of both kinds; step t (position N + t) sees its own
    t + 1 suffix positions and, in a sliding layer, what is left of the
    window in the prefix, in the full layer all N."""
    images = int(run.extras["batch_size"])
    rows = images * int(run.extras["beam_size"])
    N, _ = context_shape(run.model)
    window = int(run.model["sliding_window_size"])
    sliding, full = (layers_of(run.model, k) for k in ("sliding_attention", "full_attention"))
    flops = bytes_ = 0.0
    for t in range(int(run.extras["caption_steps"])):
        own = min(t + 1, window)
        tail = min(window - own, N)
        flops += sliding * step_flops(run.model, rows, own + tail) + full * step_flops(run.model, rows, N + t + 1)
        bytes_ += (sliding * step_bytes(run.model, images, rows, tail, own)
                   + full * step_bytes(run.model, images, rows, N, t + 1))
    return {"flops": flops, "bytes": bytes_}
