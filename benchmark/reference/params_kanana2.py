"""Parameter specification and seeded weights of the kanana-2 captioner
(``configs/sat-kanana2-30b-a3b.json``): the VGG16 encoder of
``reference/params.py`` plus the connector and the DeepSeek-V3 stack at
kanana-2-30b-a3b's sizes (latent attention, a leading dense layer, 128
routed experts beside one shared SwiGLU, an untied head).

As ``params_lfm2.py`` (whose draws, one generator per leaf on the host, and
whose rule that every decoder value is bfloat16-representable are used
here as they are): the benchmark makes the weights, the harness writes
them through the program's checkpoint path, the plain reference
(``kanana2_captioner.py``) is handed the same values, and nothing here
imports the program.  Leaves are named as the program names them; the
configuration file maps the source's names onto these.  Kinds of leaf:

* ``linear``: q_proj, kv_a_proj, kv_b_proj, gate, w1, w3 (routed and
  shared), the embedding and the head: normal, std 0.02;
* ``residual``: the maps that write into the residual stream (o_proj,
  every w2): std 0.02 / sqrt(2 x layers kept);
* ``norm``: operator_norm, ffn_norm, kv_a_layernorm, the final norm:
  1 + normal std 0.1;
* ``expert_bias`` (the source's ``e_score_correction_bias``) and
  ``connector/bias`` are only STARTED here and then fitted on a seeded
  calibration batch (``kanana2_captioner.calibrate``).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from .params import _cnn_spec, context_shape
from .params_lfm2 import BF16, Spec, _draw, _round_bf16, is_moe, layer_name  # noqa: F401


def latent_width(model: dict) -> int:
    """What a token leaves in the cache: the latent and the rotary key."""
    return int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])


def decoder_spec(model: dict) -> Spec:
    m = model
    H, E, V = m["hidden_size"], m["num_experts"], m["vocabulary_size"]
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
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
    for i in range(len(m["layer_types"])):
        q = f"{p}lm/layers/{layer_name(i)}/"
        spec[q + "operator_norm"] = ((H,), "norm", "bfloat16")
        spec[q + "ffn_norm"] = ((H,), "norm", "bfloat16")
        spec[q + "self_attn/q_proj"] = ((H, nh * (nope + rope)), "linear", "bfloat16")
        spec[q + "self_attn/kv_a_proj"] = ((H, rank + rope), "linear", "bfloat16")
        spec[q + "self_attn/kv_a_layernorm"] = ((rank,), "norm", "bfloat16")
        spec[q + "self_attn/kv_b_proj"] = ((rank, nh * (nope + vd)), "linear", "bfloat16")
        spec[q + "self_attn/o_proj"] = ((nh * vd, H), "residual", "bfloat16")
        if is_moe(m, i):
            I = m["moe_intermediate_size"]
            spec[q + "feed_forward/gate"] = ((H, E), "linear", "bfloat16")
            spec[q + "feed_forward/expert_bias"] = ((E,), "expert_bias", "float32")
            spec[q + "feed_forward/w1"] = ((E, H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w3"] = ((E, H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w2"] = ((E, I, H), "residual", "bfloat16")
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
    layers = len(model["layer_types"])

    def build(i: int):
        name = names[i]
        shape, kind, dtype = spec[name]
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, i])
        return name, _draw(rng, shape, kind, dtype, layers)

    wanted = [i for i, n in enumerate(names) if only is None or only(n)]
    with ThreadPoolExecutor(threads) as pool:
        return dict(pool.map(build, wanted))
