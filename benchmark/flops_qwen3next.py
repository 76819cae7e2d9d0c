"""Operations and bytes of what the Qwen3-Next decoder adds, from shapes
(never from the compiler's cost analysis): the yardsticks of
``lm_gdn_scan_roofline_share``, ``lm_gdn_state_roofline_share`` and
``lm_gdn_held_experts_roofline_share``.  A multiply-add counts as 2
operations; only matrix products are counted.

The two Gated DeltaNet counts are of the WORK, the recurrence of the
configuration's equations, whatever form implements it, so that a later
kernel is read against the same yardstick and no sound reading passes
100%: per position, layer and value head the three products of the state
(``S^T k``, ``k d^T``, ``S^T q``: 6 dk dv operations); a whole sequence
(``prefill_scan``) reads each position's q, k, v and writes its output
once, at 2 bytes a value (the least a form could hold them in: the
program's are float32), and writes the final state once; a step
(``step_state``) reads S once and writes it once a row and layer, float32,
plus the row's q, k, v in and output out.  The chunked form does more
operations than the recurrence (the chunk's own scores and the solve) and
the XLA step reads S twice: both show as a share under 100.
``step_experts_held`` is ``flops_dsa.step_held_experts`` as it is: its
count reads the hidden size and the expert width from the model block, and
the pairs and the experts visited from the program's counters.
``benchmark/tests/test_qwen3next.py`` holds the counts against hand counts.
"""

from __future__ import annotations

from typing import Dict

from flops_dsa import step_held_experts as step_experts_held  # noqa: F401
from reference.params import context_shape
from reference.params_qwen3next import gdn_dims, linear_layers


def rule_flops(model: dict, positions: int) -> float:
    """ONE DeltaNet layer's recurrence over ``positions`` tokens: every
    value head's three products of its [dk, dv] state."""
    _, nv, dk, dv, _, _ = gdn_dims(model)
    return 2.0 * 3 * dk * dv * nv * positions


def rule_row_values(model: dict) -> int:
    """Values a position brings and takes: q and k of the key heads, v in
    and the output out of the value heads."""
    nk, nv, dk, dv, _, _ = gdn_dims(model)
    return 2 * nk * dk + 2 * nv * dv


def state_bytes(model: dict, itemsize: int = 4) -> int:
    """One row's S of one layer."""
    _, nv, dk, dv, _, _ = gdn_dims(model)
    return itemsize * nv * dk * dv


def prefill_scan(run) -> Dict[str, float]:
    """Of ONE decoded batch's prefill: every image, every DeltaNet layer,
    the N prefix positions."""
    N, _ = context_shape(run.model)
    times = int(run.extras["batch_size"]) * len(linear_layers(run.model))
    return {"flops": times * rule_flops(run.model, N),
            "bytes": times * (2.0 * N * rule_row_values(run.model) + state_bytes(run.model))}


def step_state(run) -> Dict[str, float]:
    """Of ONE decoded batch's caption steps: ``batch_size * beam_size``
    rows a step, every step and DeltaNet layer: S in and out once, the
    row's q, k, v and output."""
    rows = int(run.extras["batch_size"]) * int(run.extras["beam_size"])
    times = rows * int(run.extras["caption_steps"]) * len(linear_layers(run.model))
    return {"flops": times * rule_flops(run.model, 1),
            "bytes": times * (2.0 * state_bytes(run.model) + 4.0 * rule_row_values(run.model))}
