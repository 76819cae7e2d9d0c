"""Operations and bytes of the LFM2-MoE decoder from shapes (never from
the compiler's cost analysis): the yardstick of
``lm_moe_experts_roofline_share``.  A multiply-add counts as 2 operations;
only matrix products are counted.  ``tests/test_lfm2_reference.py`` holds
``moe_layer_flops`` against the TPU compiler's count of the program's own
expert layer.
"""

from __future__ import annotations

from typing import Dict


def moe_layers(model: dict) -> int:
    return len(model["layer_types"]) - int(model["num_dense_layers"])


def expert_flops(model: dict, rows: int) -> float:
    """The three grouped products of ONE expert layer over ``rows``
    tokens: each routed (token, expert) pair goes through w1, w3, w2."""
    pairs = rows * int(model["num_experts_per_tok"])
    return 2.0 * 3 * model["hidden_size"] * model["moe_intermediate_size"] * pairs


def expert_bytes(model: dict, rows: int, itemsize: int = 2) -> float:
    """What ONE expert layer has to move over ``rows`` tokens: the three
    maps of all its experts, once, and the routed rows in and out."""
    pairs = rows * int(model["num_experts_per_tok"])
    weights = 3.0 * model["num_experts"] * model["hidden_size"] * model["moe_intermediate_size"]
    return itemsize * (weights + 2.0 * pairs * model["hidden_size"])


def moe_layer_flops(model: dict, rows: int) -> float:
    """One expert layer whole: the router's product and the experts'."""
    return 2.0 * rows * model["hidden_size"] * model["num_experts"] + expert_flops(model, rows)


def step_experts(run) -> Dict[str, float]:
    """Operations and bytes of the expert products of ONE decoded batch's
    caption steps: ``batch_size * beam_size`` rows a step, every step and
    every expert layer (``run.extras``: what the driver ran), each reading
    the maps of all ``num_experts`` experts (under the balanced routing of
    the cell's weights the rows of every step after the first reach every
    expert: the driver's note ``experts_visited_a_step`` says so, run by
    run; the first step, every row on ``<start>``, reads fewer and is
    counted like the others)."""
    rows = int(run.extras["batch_size"]) * int(run.extras["beam_size"])
    times = int(run.extras["caption_steps"]) * moe_layers(run.model)
    return {"flops": times * expert_flops(run.model, rows), "bytes": times * expert_bytes(run.model, rows)}
