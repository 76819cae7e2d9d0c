"""Operations and bytes of latent attention's STEP from shapes (never from
the compiler's cost analysis): the yardstick of
``lm_mla_step_roofline_share``.  A multiply-add counts as 2 operations;
only matrix products are counted.  The work is the absorbed form's over
the latent cache (what a step has to do when no key or value is kept
expanded), whatever implements it; ``benchmark/tests/test_kanana2.py``
holds ``step_attention_flops`` against the TPU compiler's count of the
program's own step attention.
"""

from __future__ import annotations

from typing import Dict

from reference.params import context_shape


def _dims(model: dict):
    m = model
    return (int(m["hidden_size"]), int(m["num_attention_heads"]), int(m["kv_lora_rank"]),
            int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"]), int(m["v_head_dim"]))


def step_attention_flops(model: dict, rows: int, latents: int) -> float:
    """ONE layer's attention for ``rows`` tokens, each over ``latents``
    cached positions (its image's prefix + its own suffix + itself):
    W_q, W_kva, the absorb of W_kvb's key half into the query, scores over
    the latents' rank + rope numbers, the weighted sum over their rank,
    the un-absorb through W_kvb's value half, W_o."""
    H, nh, rank, nope, rope, vd = _dims(model)
    per_row = (H * nh * (nope + rope) + H * (rank + rope)            # W_q, W_kva
               + nh * nope * rank                                    # absorb
               + nh * (rank + rope) * latents + nh * rank * latents  # scores, weighted sum
               + nh * rank * vd + nh * vd * H)                       # un-absorb, W_o
    return 2.0 * rows * per_row


def step_attention_bytes(model: dict, images: int, rows: int, prefix: int, suffix: int,
                         itemsize: int = 2) -> float:
    """What ONE layer's step attention has to move: its four maps once,
    the prefix's latents once per IMAGE (the beams of an image share them),
    each row's own ``suffix`` latents, and the rows in and out."""
    H, nh, rank, nope, rope, vd = _dims(model)
    maps = H * nh * (nope + rope) + H * (rank + rope) + rank * nh * (nope + vd) + nh * vd * H
    cache = (images * prefix + rows * suffix) * (rank + rope)
    return itemsize * (maps + cache + 2.0 * rows * H)


def step_attention(run) -> Dict[str, float]:
    """Operations and bytes of the attention of ONE decoded batch's caption
    steps: ``batch_size * beam_size`` rows a step, every layer, step t over
    N + t + 1 latents (``run.extras``: what the driver ran)."""
    images = int(run.extras["batch_size"])
    rows = images * int(run.extras["beam_size"])
    layers = len(run.model["layer_types"])
    N, _ = context_shape(run.model)
    flops = bytes_ = 0.0
    for t in range(int(run.extras["caption_steps"])):
        flops += step_attention_flops(run.model, rows, N + t + 1)
        bytes_ += step_attention_bytes(run.model, images, rows, N, t + 1)
    return {"flops": layers * flops, "bytes": layers * bytes_}
