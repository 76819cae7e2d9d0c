"""Parameter specification and seeded weights of the GLM-5.2 captioner
(``configs/sat-glm-5.2.json``): the VGG16 encoder of ``reference/params.py``
plus the connector and the ``glm_moe_dsa`` stack at GLM-5.2's widths
(latent attention with a compressed query, an indexer in the layers whose
``indexer_types`` entry is "full", a leading dense layer, the share of the
routed experts this chip holds beside one shared SwiGLU, an untied head
over the vocabulary's slice).

As ``params_kanana2.py`` (whose draws, one generator per leaf on the host,
and whose rule that every decoder value is bfloat16-representable are used
here as they are): the benchmark makes the weights, the harness writes
them through the program's checkpoint path, the plain reference
(``glm52_captioner.py``) is handed the same values, and nothing here
imports the program.  Leaves are named as the program names them; the
configuration file maps the source's names onto these.  Kinds of leaf:

* ``linear``: q_a_proj, q_b_proj, kv_a_proj, kv_b_proj, the indexer's
  wq_b, wk and weights_proj, gate (ALL ``num_experts`` outputs), w1, w3
  (the ``experts_held`` experts here, and the shared one), the embedding
  and the head: normal, std 0.02;
* ``residual``: o_proj, every w2: std 0.02 / sqrt(2 x layers kept);
* ``norm``: operator_norm, ffn_norm, q_a_layernorm, kv_a_layernorm, the
  indexer key's LayerNorm weight, the final norm: 1 + normal std 0.1;
* ``norm_bias``: the indexer key's LayerNorm bias: normal std 0.1;
* ``expert_bias`` (all ``num_experts``) and ``connector/bias`` are only
  STARTED here and then fitted on a seeded calibration batch
  (``glm52_captioner.calibrate``).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from .params import _cnn_spec, context_shape
from .params_lfm2 import BF16, Spec, _draw, _round_bf16, is_moe, layer_name  # noqa: F401


def held_experts(model: dict) -> int:
    return int(model.get("experts_held") or model["num_experts"])


def full_layers(model: dict):
    return [i for i, kind in enumerate(model["indexer_types"]) if kind == "full"]


def decoder_spec(model: dict) -> Spec:
    m = model
    H, E, V = m["hidden_size"], m["num_experts"], m["vocabulary_size"]
    held = held_experts(m)
    nh, rank, qr = m["num_attention_heads"], m["kv_lora_rank"], m["q_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
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
    for i, kind in enumerate(m["indexer_types"]):
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
        if kind == "full":
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
    """All leaves (or those whose path ``only(path)`` accepts) as numpy
    arrays of the spec's dtype.  A leaf depends on the seed and on its
    own path alone, so any subset can be made again later: the reference
    makes one layer at a time."""
    spec = param_spec(model)
    names = sorted(spec)
    seed = int(seed)
    layers = len(model["indexer_types"])

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
