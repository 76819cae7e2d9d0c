"""Parameter specification and seeded weights of the dots3-note-prev
captioner (``configs/sat-dots3-note-prev.json``): the VGG16 encoder of
``reference/params.py`` plus the connector and the ``dots3_note`` stack at
the source's widths: per layer latent attention at its KIND's widths (a
``full_attention`` layer the widths ``params_glm52`` reads, with an indexer
in every one; a ``sliding_attention`` layer the ``swa_*`` widths, none), a
headwise gate ``gate_proj`` ``[H, heads]`` in both kinds, a leading dense
layer, the share of the routed experts this chip holds beside one shared
SwiGLU, an untied head over the vocabulary's slice.

As ``params_glm52.py``, whose draws (one generator per leaf on the host,
every decoder value bfloat16-representable, ``residual`` leaves scaled by
1 / sqrt(2 x layers kept)) and kinds of leaf are used as they are;
``gate_proj`` is ``linear`` (normal, std 0.02).  Nothing here imports the
program; leaves are named as the program names them.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from .params import _cnn_spec, context_shape
from .params_glm52 import held_experts  # noqa: F401
from .params_lfm2 import BF16, Spec, _draw, _round_bf16, is_moe, layer_name  # noqa: F401


def kind_widths(model: dict, kind: str) -> dict:
    """The numbers of one kind of layer's attention, under one set of
    names: heads, q_rank, kv_rank, nope, rope, v, theta."""
    m, p = model, "swa_" if kind == "sliding_attention" else ""
    return {"heads": int(m[p + "num_attention_heads"]), "q_rank": int(m[p + "q_lora_rank"]),
            "kv_rank": int(m[p + "kv_lora_rank"]), "nope": int(m[p + "qk_nope_head_dim"]),
            "rope": int(m[p + "qk_rope_head_dim"]), "v": int(m[p + "v_head_dim"]),
            "theta": float(m[p + "rope_theta"])}


def full_layers(model: dict):
    return [i for i, kind in enumerate(model["layer_types"]) if kind == "full_attention"]


def decoder_spec(model: dict) -> Spec:
    m = model
    H, E, V = m["hidden_size"], m["num_experts"], m["vocabulary_size"]
    held = held_experts(m)
    nI, dI = m["index_n_heads"], m["index_head_dim"]
    _, D = context_shape(m)
    p = "params/decoder/"
    spec: Spec = {
        p + "connector/kernel": ((D, H), "connector", "float32"),
        p + "connector/bias": ((H,), "connector_bias", "float32"),
        p + "lm/embed_tokens": ((V, H), "linear", "bfloat16"),
        p + "lm/norm": ((H,), "norm", "bfloat16"),
    }
    if not m.get("tie_word_embeddings", False):
        spec[p + "lm/lm_head"] = ((H, V), "linear", "bfloat16")
    for i, kind in enumerate(m["layer_types"]):
        w = kind_widths(m, kind)
        nh, qr, rank, nope, rope, vd = (w[k] for k in ("heads", "q_rank", "kv_rank", "nope", "rope", "v"))
        q = f"{p}lm/layers/{layer_name(i)}/"
        a = q + "self_attn/"
        spec[q + "operator_norm"] = ((H,), "norm", "bfloat16")
        spec[q + "ffn_norm"] = ((H,), "norm", "bfloat16")
        spec[a + "q_a_proj"] = ((H, qr), "linear", "bfloat16")
        spec[a + "q_a_layernorm"] = ((qr,), "norm", "bfloat16")
        spec[a + "q_b_proj"] = ((qr, nh * (nope + rope)), "linear", "bfloat16")
        spec[a + "kv_a_proj"] = ((H, rank + rope), "linear", "bfloat16")
        spec[a + "kv_a_layernorm"] = ((rank,), "norm", "bfloat16")
        spec[a + "kv_b_proj"] = ((rank, nh * (nope + vd)), "linear", "bfloat16")
        spec[a + "o_proj"] = ((nh * vd, H), "residual", "bfloat16")
        if m.get("attention_gate", "none") == "headwise":
            spec[a + "gate_proj"] = ((H, nh), "linear", "bfloat16")
        if kind == "full_attention":
            spec[a + "indexer/wq_b"] = ((qr, nI * dI), "linear", "bfloat16")
            spec[a + "indexer/wk"] = ((H, dI), "linear", "bfloat16")
            spec[a + "indexer/k_norm_weight"] = ((dI,), "norm", "bfloat16")
            spec[a + "indexer/k_norm_bias"] = ((dI,), "norm_bias", "bfloat16")
            spec[a + "indexer/weights_proj"] = ((H, nI), "linear", "bfloat16")
        if is_moe(m, i):
            I = m["moe_intermediate_size"]
            spec[q + "feed_forward/gate"] = ((H, E), "linear", "bfloat16")
            spec[q + "feed_forward/expert_bias"] = ((E,), "expert_bias", "float32")
            spec[q + "feed_forward/w1"] = ((held, H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w3"] = ((held, H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w2"] = ((held, I, H), "residual", "bfloat16")
            S = int(m["n_shared_experts"]) * I
            if S:
                spec[q + "feed_forward/shared/w1"] = ((H, S), "linear", "bfloat16")
                spec[q + "feed_forward/shared/w3"] = ((H, S), "linear", "bfloat16")
                spec[q + "feed_forward/shared/w2"] = ((S, H), "residual", "bfloat16")
        else:
            I = m["intermediate_size"]
            spec[q + "feed_forward/w1"] = ((H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w3"] = ((H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w2"] = ((I, H), "residual", "bfloat16")
    return spec


def param_spec(model: dict) -> Spec:
    """{leaf path: (shape, kind, dtype)}: the encoder's leaves (float32)
    and the decoder's."""
    cnn = {k: (shape, kind, "float32") for k, (shape, kind) in _cnn_spec(model).items()}
    return {**cnn, **decoder_spec(model)}


def make_weights(model: dict, seed: int, only=None, threads: int = 12) -> Dict[str, np.ndarray]:
    """``params_glm52.make_weights`` over this stack's spec: a leaf depends
    on the seed and on its own path alone, so any subset can be made again
    later (the reference makes one layer at a time)."""
    spec = param_spec(model)
    names = sorted(spec)
    seed = int(seed)
    layers = len(model["layer_types"])

    def build(i: int):
        name = names[i]
        shape, kind, dtype = spec[name]
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, i])
        if kind == "norm_bias":
            return name, (0.1 * rng.standard_normal(shape, np.float32)).astype(BF16)
        return name, _draw(rng, shape, kind, dtype, layers)

    wanted = [i for i, n in enumerate(names) if only is None or only(n)]
    with ThreadPoolExecutor(threads) as pool:
        return dict(pool.map(build, wanted))
