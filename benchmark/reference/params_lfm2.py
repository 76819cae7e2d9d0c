"""Parameter specification and seeded weights of the LFM2-MoE captioner
(``configs/sat-lfm2-8b-a1b.json``): the VGG16 encoder of
``reference/params.py`` plus the connector and the language-model stack.

The benchmark makes the weights, not the program: ``make_weights`` builds
every leaf on the HOST from the seed (numpy, one generator per leaf, in
threads: 3.1e9 values), the harness writes them through the program's
checkpoint path, and the plain reference (``lfm2_captioner.py``) is handed
the same values.  Nothing here imports the program; the benchmark's tests
check that ``param_spec`` names, shapes and dtypes equal the program's own
tree.

Every value of the decoder is bfloat16-representable, so that the program
(which holds the stack in bfloat16, the configuration's stated precision)
and the float32 reference hold the SAME numbers.  Distributions (each a
line of the configuration's ``assumed``):

* linear maps and the embedding: normal, std 0.02; the maps that write
  into the residual stream (``out_proj``, ``w2``) scaled by
  1/sqrt(2 x layers kept), so that the stream's scale does not grow with
  depth;
* norm weights: 1 + normal std 0.1;
* ``expert_bias``: normal std 0.1, float32;
* conv taps: uniform(-0.5, 0.5);
* the connector (float32 in the program, since it trains): normal std
  0.02 kernel, std 0.01 bias, rounded to bfloat16-representable values.

Two kinds of leaf are only STARTED here: ``connector/bias`` and every
``expert_bias`` are then fitted on a seeded calibration batch by
``lfm2_captioner.calibrate`` (the centring and the load balance that a
trained deployment has and random weights have not), and the fitted
values replace these draws in the program's checkpoint and in the
reference alike.  A program that leaves the bias out then chooses other
experts, and fails.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import ml_dtypes
import numpy as np

from .params import _cnn_spec, context_shape

BF16 = np.dtype(ml_dtypes.bfloat16)
Spec = Dict[str, Tuple[Tuple[int, ...], str, str]]   # path -> (shape, kind, dtype)


def layer_name(i: int) -> str:
    return f"{i:02d}"


def is_moe(model: dict, layer: int) -> bool:
    return layer >= int(model["num_dense_layers"])


def decoder_spec(model: dict) -> Spec:
    m = model
    H, E, V = m["hidden_size"], m["num_experts"], m["vocabulary_size"]
    hd = H // m["num_attention_heads"]
    kvw = m["num_key_value_heads"] * hd
    _, D = context_shape(m)
    p = "params/decoder/"
    spec: Spec = {
        p + "connector/kernel": ((D, H), "connector", "float32"),
        p + "connector/bias": ((H,), "connector_bias", "float32"),
        p + "lm/embed_tokens": ((V, H), "linear", "bfloat16"),
        p + "lm/embedding_norm": ((H,), "norm", "bfloat16"),
    }
    for i, kind in enumerate(m["layer_types"]):
        q = f"{p}lm/layers/{layer_name(i)}/"
        spec[q + "operator_norm"] = ((H,), "norm", "bfloat16")
        spec[q + "ffn_norm"] = ((H,), "norm", "bfloat16")
        if kind == "conv":
            spec[q + "conv/in_proj"] = ((H, 3 * H), "linear", "bfloat16")
            spec[q + "conv/conv"] = ((m["conv_L_cache"], H), "taps", "bfloat16")
            spec[q + "conv/out_proj"] = ((H, H), "residual", "bfloat16")
        else:
            spec[q + "self_attn/q_proj"] = ((H, H), "linear", "bfloat16")
            spec[q + "self_attn/k_proj"] = ((H, kvw), "linear", "bfloat16")
            spec[q + "self_attn/v_proj"] = ((H, kvw), "linear", "bfloat16")
            spec[q + "self_attn/out_proj"] = ((H, H), "residual", "bfloat16")
            spec[q + "self_attn/q_layernorm"] = ((hd,), "norm", "bfloat16")
            spec[q + "self_attn/k_layernorm"] = ((hd,), "norm", "bfloat16")
        if is_moe(m, i):
            I = m["moe_intermediate_size"]
            spec[q + "feed_forward/gate"] = ((H, E), "linear", "bfloat16")
            spec[q + "feed_forward/expert_bias"] = ((E,), "expert_bias", "float32")
            spec[q + "feed_forward/w1"] = ((E, H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w3"] = ((E, H, I), "linear", "bfloat16")
            spec[q + "feed_forward/w2"] = ((E, I, H), "residual", "bfloat16")
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


def _round_bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(BF16).astype(np.float32)


def _draw(rng: np.random.Generator, shape, kind: str, dtype: str, layers: int) -> np.ndarray:
    f32 = np.float32
    if kind in ("conv", "conv_first"):                     # the encoder, as reference/params.py
        fan_in = shape[0] * shape[1] * shape[2]
        std = math.sqrt(2.0 / fan_in) * (1.0 / 64.0 if kind == "conv_first" else 1.0)
        return (std * rng.standard_normal(shape, f32)).astype(f32)
    if kind == "bias":
        return (0.01 * rng.standard_normal(shape, f32)).astype(f32)
    if kind == "expert_bias":
        return (0.1 * rng.standard_normal(shape, f32)).astype(f32)
    if kind == "connector":
        return _round_bf16(0.02 * rng.standard_normal(shape, f32))
    if kind == "connector_bias":
        return _round_bf16(0.01 * rng.standard_normal(shape, f32))
    if kind == "linear":
        x = rng.standard_normal(shape, f32)
        x *= f32(0.02)
    elif kind == "residual":
        x = rng.standard_normal(shape, f32)
        x *= f32(0.02 / math.sqrt(2.0 * layers))
    elif kind == "norm":
        x = 1.0 + 0.1 * rng.standard_normal(shape, f32)
    elif kind == "taps":
        x = rng.uniform(-0.5, 0.5, shape).astype(f32)
    else:
        raise ValueError(kind)
    return x.astype(BF16) if dtype == "bfloat16" else x.astype(f32)


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
