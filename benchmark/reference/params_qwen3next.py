"""Parameter specification and seeded weights of the Qwen3-Next captioner
(``configs/sat-qwen3-next-80b-a3b.json``): the VGG16 encoder of
``reference/params.py`` plus the connector and the ``qwen3_next`` stack at
the source's widths.  Per layer two RMSNorm weights kept about ZERO (the
source multiplies by ``1 + w``); a ``linear_attention`` layer's Gated
DeltaNet mixer (``in_proj_qkvz`` ``[H, nk (2 dk + 2 r dv)]`` laid out per
key head ``[q | k | v x r | z x r]``, ``in_proj_ba`` ``[H, 2 nv]`` per key
head ``[b x r | a x r]``, the conv's taps ``conv1d`` ``[L, 2 nk dk + nv dv]``
oldest first, ``A_log`` and ``dt_bias`` ``[nv]`` float32, the gated norm's
plain weight ``norm`` ``[dv]``, ``out_proj``); a ``full_attention`` layer's
gated grouped-query mixer (``q_proj`` ``[H, nh x 2d]`` per head ``[query |
gate]``, ``k_proj``, ``v_proj``, ``o_proj``, ``q_norm`` and ``k_norm``
``[d]`` about zero); the router over ALL ``num_experts`` (no selection bias:
no such leaf), the share of the routed experts this chip holds, and the one
shared expert with its gate ``shared/gate`` ``[H, 1]``; the final norm,
the embedding's slice and the untied head's.

As ``params_cohere2.py`` (one generator per leaf on the host, every
bfloat16 leaf's value bfloat16-representable, ``residual`` leaves, here
``out_proj``, ``o_proj`` and every ``w2``, scaled by 1 / sqrt(2 x layers
kept)), with three kinds of its own: ``norm0`` (normal std 0.1 about
ZERO), and the decay's, drawn as the public Gated DeltaNet layer
initialises them, so that the state neither dies in a step nor never
forgets: ``A ~ U(0, 16)``, ``A_log = log A``; ``dt`` log-uniform in
[1e-3, 1e-1], ``dt_bias = dt + log(-expm1(-dt))`` (softplus's inverse).
Nothing here imports the program; leaves are named as the program names
them.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from .params import _cnn_spec, context_shape
from .params_glm52 import held_experts  # noqa: F401
from .params_lfm2 import BF16, Spec, _draw, _round_bf16, layer_name  # noqa: F401


def gdn_dims(model: dict):
    """(nk, nv, dk, dv, value heads a key head, the conv's width)."""
    nk, nv = int(model["linear_num_key_heads"]), int(model["linear_num_value_heads"])
    dk, dv = int(model["linear_key_head_dim"]), int(model["linear_value_head_dim"])
    return nk, nv, dk, dv, nv // nk, 2 * nk * dk + nv * dv


def linear_layers(model: dict):
    return [i for i, kind in enumerate(model["layer_types"]) if kind == "linear_attention"]


def decoder_spec(model: dict) -> Spec:
    m = model
    H, E, V, I = m["hidden_size"], m["num_experts"], m["vocabulary_size"], m["moe_intermediate_size"]
    held, d = held_experts(m), int(m["head_dim"])
    nh, kv = m["num_attention_heads"], m["num_key_value_heads"]
    nk, nv, dk, dv, r, width = gdn_dims(m)
    shared = int(m["shared_expert_intermediate_size"])
    _, D = context_shape(m)
    p = "params/decoder/"
    spec: Spec = {
        p + "connector/kernel": ((D, H), "connector", "float32"),
        p + "connector/bias": ((H,), "connector_bias", "float32"),
        p + "lm/embed_tokens": ((V, H), "linear", "bfloat16"),
        p + "lm/norm": ((H,), "norm0", "bfloat16"),
        p + "lm/lm_head": ((H, V), "linear", "bfloat16"),
    }
    for i, kind in enumerate(m["layer_types"]):
        q = f"{p}lm/layers/{layer_name(i)}/"
        spec[q + "input_layernorm"] = ((H,), "norm0", "bfloat16")
        spec[q + "post_attention_layernorm"] = ((H,), "norm0", "bfloat16")
        if kind == "linear_attention":
            spec[q + "linear_attn/in_proj_qkvz"] = ((H, 2 * nk * dk + 2 * nv * dv), "linear", "bfloat16")
            spec[q + "linear_attn/in_proj_ba"] = ((H, 2 * nv), "linear", "bfloat16")
            spec[q + "linear_attn/conv1d"] = ((int(m["linear_conv_kernel_dim"]), width), "taps", "bfloat16")
            spec[q + "linear_attn/A_log"] = ((nv,), "A_log", "float32")
            spec[q + "linear_attn/dt_bias"] = ((nv,), "dt_bias", "float32")
            spec[q + "linear_attn/norm"] = ((dv,), "norm", "bfloat16")
            spec[q + "linear_attn/out_proj"] = ((nv * dv, H), "residual", "bfloat16")
        else:
            spec[q + "self_attn/q_proj"] = ((H, nh * 2 * d), "linear", "bfloat16")
            spec[q + "self_attn/k_proj"] = ((H, kv * d), "linear", "bfloat16")
            spec[q + "self_attn/v_proj"] = ((H, kv * d), "linear", "bfloat16")
            spec[q + "self_attn/o_proj"] = ((nh * d, H), "residual", "bfloat16")
            spec[q + "self_attn/q_norm"] = ((d,), "norm0", "bfloat16")
            spec[q + "self_attn/k_norm"] = ((d,), "norm0", "bfloat16")
        spec[q + "feed_forward/gate"] = ((H, E), "linear", "bfloat16")
        spec[q + "feed_forward/w1"] = ((held, H, I), "linear", "bfloat16")
        spec[q + "feed_forward/w3"] = ((held, H, I), "linear", "bfloat16")
        spec[q + "feed_forward/w2"] = ((held, I, H), "residual", "bfloat16")
        spec[q + "feed_forward/shared/w1"] = ((H, shared), "linear", "bfloat16")
        spec[q + "feed_forward/shared/w3"] = ((H, shared), "linear", "bfloat16")
        spec[q + "feed_forward/shared/w2"] = ((shared, H), "residual", "bfloat16")
        spec[q + "feed_forward/shared/gate"] = ((H, 1), "linear", "bfloat16")
    return spec


def param_spec(model: dict) -> Spec:
    """{leaf path: (shape, kind, dtype)}: the encoder's leaves (float32)
    and the decoder's."""
    cnn = {k: (shape, kind, "float32") for k, (shape, kind) in _cnn_spec(model).items()}
    return {**cnn, **decoder_spec(model)}


def draw(rng: np.random.Generator, shape, kind: str, dtype: str, layers: int) -> np.ndarray:
    """``params_lfm2._draw`` and this stack's three kinds."""
    if kind == "norm0":
        return (0.1 * rng.standard_normal(shape, np.float32)).astype(BF16)
    if kind == "A_log":
        return np.log(rng.uniform(0.0, 16.0, shape)).astype(np.float32)
    if kind == "dt_bias":
        dt = np.exp(rng.uniform(0.0, 1.0, shape) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        dt = np.maximum(dt, 1e-4)
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    return _draw(rng, shape, kind, dtype, layers)


def make_weights(model: dict, seed: int, only=None, threads: int = 12) -> Dict[str, np.ndarray]:
    """``params_cohere2.make_weights`` over this stack's spec: a leaf
    depends on the seed and on its own path alone, so any subset can be
    made again later (the reference makes one layer at a time)."""
    spec = param_spec(model)
    names = sorted(spec)
    seed = int(seed)
    layers = len(model["layer_types"])

    def build(i: int):
        shape, kind, dtype = spec[names[i]]
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, i])
        return names[i], draw(rng, shape, kind, dtype, layers)

    wanted = [i for i, n in enumerate(names) if only is None or only(n)]
    with ThreadPoolExecutor(threads) as pool:
        return dict(pool.map(build, wanted))
