"""Operations and bytes from shapes (never from the compiler's cost
analysis): the yardstick for ``train_mfu`` and for a kernel's roofline.

A multiply-add counts as 2 operations.  Only matrix multiplications and
convolutions are counted — the element-wise work (tanh, softmax, dropout
masks) is left out, and a gradient nothing needs is not counted, so a
share computed from these counts errs low.  ``tests/test_flops.py`` holds
them against the compiler's count of the program's own train step.
"""

from __future__ import annotations

import math
from typing import Dict

from reference.params import RESNET_STAGES, VGG_LAYERS, context_shape


def cnn_forward_flops(model: dict) -> float:
    """One image through the encoder."""
    size = int(model.get("image_size", 224))
    total = 0.0
    if model["cnn"] == "vgg16":
        cin, hw = 3, size
        for _name, cout, pool in VGG_LAYERS:
            total += 2.0 * hw * hw * 9 * cin * cout
            cin = cout
            if pool:
                hw = math.ceil(hw / 2)
        return total
    hw = math.ceil(size / 2)
    total += 2.0 * hw * hw * 49 * 3 * 64
    hw = math.ceil(hw / 2)
    cin = 64
    for _stage, c, n_identity, stride in RESNET_STAGES:
        for i in range(n_identity + 1):
            out_hw = math.ceil(hw / stride) if i == 0 else hw
            if i == 0:
                total += 2.0 * out_hw * out_hw * cin * 4 * c          # projection shortcut
            total += 2.0 * out_hw * out_hw * cin * c                  # 1x1 (strided in block a)
            total += 2.0 * out_hw * out_hw * 9 * c * c                # 3x3
            total += 2.0 * out_hw * out_hw * c * 4 * c                # 1x1
            cin, hw = 4 * c, out_hw
    return total


def decoder_step_flops(model: dict, hoisted: bool) -> Dict[str, float]:
    """One decoder step of one row, in two parts: ``grid`` is the work whose
    left operand is the encoder's grid itself (the context half of the
    attention MLP, N*D*da, and the weighted sum over the grid, N*D);
    ``rest`` is everything downstream of a parameter.  ``hoisted``:
    inference computes the context half once per image, not per step."""
    m = model
    E, H, V = m["dim_embedding"], m["num_lstm_units"], m["vocabulary_size"]
    N, D = context_shape(m)
    da, dd = m["dim_attend_layer"], m["dim_decode_layer"]
    grid = 2.0 * N * D + (0.0 if hoisted else 2.0 * N * D * da)
    attend = 2.0 * (H * da + N * da)
    lstm = 2.0 * (D + E + H) * 4 * H
    decode = 2.0 * ((H + D + E) * dd + dd * V)
    return {"grid": grid, "rest": attend + lstm + decode}


def decoder_init_flops(model: dict) -> Dict[str, float]:
    """The two initial-state MLPs: their first layers read the grid's mean."""
    _N, D = context_shape(model)
    di, H = model["dim_initialize_layer"], model["num_lstm_units"]
    return {"grid": 2.0 * 2 * D * di, "rest": 2.0 * 2 * di * H}


def train_flops_per_caption(model: dict, train_cnn: bool = False) -> float:
    """Forward of the CNN, forward + backward of the decoder over T
    teacher-forced steps.  A matmul's backward pass is two more matmuls of
    its size, one for each operand's gradient: 3x forward.  With the CNN
    frozen the grid needs no gradient, so the work that reads the grid
    itself costs 2x forward (the weighted sum: forward and the weights'
    gradient; fc_1a: forward and the kernel's gradient), and the CNN 1x.
    Recomputed work (remat, the scan's checkpoint) is not counted."""
    T = model["max_caption_length"]
    step, init = decoder_step_flops(model, hoisted=False), decoder_init_flops(model)
    grid = init["grid"] + T * step["grid"]
    rest = init["rest"] + T * step["rest"]
    if train_cnn:
        return 3.0 * (cnn_forward_flops(model) + grid + rest)
    return cnn_forward_flops(model) + 2.0 * grid + 3.0 * rest


def attend_kernel_cost(rows: int, model: dict, act_bytes: int = 4) -> Dict[str, float]:
    """fused_attend over ``rows`` (images x beams) rows: temp = ctx_proj +
    t2; logits = temp @ w2; alpha = softmax; context = alpha @ contexts.
    Operations 2*rows*N*(da + D) (+ the add); bytes: ctx_proj [rows,N,da]
    and contexts [rows,N,D] read once, t2, alpha and context written.
    At N=196, D=da=512 that is 4 operations per 8 bytes read: far under
    the v5e's 240 operations per byte, so HBM bandwidth bounds it."""
    N, D = context_shape(model)
    da = model["dim_attend_layer"]
    ops = 2.0 * rows * N * (da + D) + rows * N * da
    byts = act_bytes * rows * (N * da + N * D + da + N + D)
    return {"ops": ops, "bytes": byts, "bound": "bandwidth"}
