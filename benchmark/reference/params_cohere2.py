"""Parameter specification and seeded weights of the command-a-plus
captioner (``configs/sat-command-a-plus.json``): the VGG16 encoder of
``reference/params.py`` plus the connector and the ``cohere2_moe`` stack at
the source's widths: per layer ONE LayerNorm weight (``input_norm``),
grouped-query attention (``q_proj`` ``[H, nh * d]``, ``k_proj``, ``v_proj``
``[H, nkv * d]``, ``o_proj`` ``[nh * d, H]``, no bias), the router over ALL
``num_experts`` (no selection bias: no such leaf), the share of the routed
experts this chip holds, and the ``n_shared_experts`` shared experts side by
side as one SwiGLU (``shared/w1``, ``w3`` ``[H, n * I]``: expert s is columns
``s * I .. (s + 1) * I``; ``shared/w2`` ``[n * I, H]``: its rows); the final
LayerNorm's weight and the embedding's slice, which is the head.

As ``params_dots3.py``, whose draws (one generator per leaf on the host,
every decoder value bfloat16-representable, ``residual`` leaves, here
``o_proj`` and every ``w2``, scaled by 1 / sqrt(2 x layers kept)) are used
as they are.  Nothing here imports the program; leaves are named as the
program names them.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from .params import _cnn_spec, context_shape
from .params_glm52 import held_experts  # noqa: F401
from .params_lfm2 import BF16, Spec, _draw, _round_bf16, layer_name  # noqa: F401


def head_dim(model: dict) -> int:
    return int(model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"])


def sliding_layers(model: dict):
    return [i for i, kind in enumerate(model["layer_types"]) if kind == "sliding_attention"]


def decoder_spec(model: dict) -> Spec:
    m = model
    H, E, V, I = m["hidden_size"], m["num_experts"], m["vocabulary_size"], m["moe_intermediate_size"]
    held, d = held_experts(m), head_dim(m)
    nh, kv = m["num_attention_heads"], m["num_key_value_heads"]
    _, D = context_shape(m)
    p = "params/decoder/"
    spec: Spec = {
        p + "connector/kernel": ((D, H), "connector", "float32"),
        p + "connector/bias": ((H,), "connector_bias", "float32"),
        p + "lm/embed_tokens": ((V, H), "linear", "bfloat16"),
        p + "lm/norm": ((H,), "norm", "bfloat16"),
    }
    for i in range(len(m["layer_types"])):
        q = f"{p}lm/layers/{layer_name(i)}/"
        spec[q + "input_norm"] = ((H,), "norm", "bfloat16")
        spec[q + "self_attn/q_proj"] = ((H, nh * d), "linear", "bfloat16")
        spec[q + "self_attn/k_proj"] = ((H, kv * d), "linear", "bfloat16")
        spec[q + "self_attn/v_proj"] = ((H, kv * d), "linear", "bfloat16")
        spec[q + "self_attn/o_proj"] = ((nh * d, H), "residual", "bfloat16")
        spec[q + "feed_forward/gate"] = ((H, E), "linear", "bfloat16")
        if m.get("use_expert_bias", True):
            spec[q + "feed_forward/expert_bias"] = ((E,), "expert_bias", "float32")
        spec[q + "feed_forward/w1"] = ((held, H, I), "linear", "bfloat16")
        spec[q + "feed_forward/w3"] = ((held, H, I), "linear", "bfloat16")
        spec[q + "feed_forward/w2"] = ((held, I, H), "residual", "bfloat16")
        S = int(m["n_shared_experts"]) * I
        if S:
            spec[q + "feed_forward/shared/w1"] = ((H, S), "linear", "bfloat16")
            spec[q + "feed_forward/shared/w3"] = ((H, S), "linear", "bfloat16")
            spec[q + "feed_forward/shared/w2"] = ((S, H), "residual", "bfloat16")
    return spec


def param_spec(model: dict) -> Spec:
    """{leaf path: (shape, kind, dtype)}: the encoder's leaves (float32)
    and the decoder's."""
    cnn = {k: (shape, kind, "float32") for k, (shape, kind) in _cnn_spec(model).items()}
    return {**cnn, **decoder_spec(model)}


def make_weights(model: dict, seed: int, only=None, threads: int = 12) -> Dict[str, np.ndarray]:
    """``params_dots3.make_weights`` over this stack's spec: a leaf depends
    on the seed and on its own path alone, so any subset can be made again
    later (the reference makes one layer at a time)."""
    spec = param_spec(model)
    names = sorted(spec)
    seed = int(seed)
    layers = len(model["layer_types"])

    def build(i: int):
        shape, kind, dtype = spec[names[i]]
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, i])
        return names[i], _draw(rng, shape, kind, dtype, layers)

    wanted = [i for i, n in enumerate(names) if only is None or only(n)]
    with ThreadPoolExecutor(threads) as pool:
        return dict(pool.map(build, wanted))
