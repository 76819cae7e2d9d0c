"""Parameter specification and seeded weights of the two captioners.

The benchmark makes the weights, not the program: ``make_weights`` builds
the whole tree on the device in ONE jitted call from the seed, the harness
writes it through the program's checkpoint path, and the plain reference
(``model.py``) is handed the same tree.  Nothing here imports the program;
``benchmark/tests/test_reference.py`` checks that ``param_spec`` names and
shapes equal the program's own tree.

A leaf is named by its path, e.g. ``params/decoder/lstm/kernel`` or
``batch_stats/res2a/bn2a_branch1/mean``.  Distributions (not the
program's initialisers, on purpose — see PERF.md §4 "assumed"):

* conv kernels: normal, std sqrt(2/fan_in) (He), so that activations keep
  their scale through 13 (VGG16) or 53 (ResNet50) ReLU layers; the first
  conv is scaled by 1/64 so that pixel-scale inputs (|x| up to ~150) give
  O(1) features, as ImageNet-trained encoders do;
* fully connected kernels and the embedding: uniform(-0.08, 0.08), the
  source's ``fc_kernel_initializer_scale``;
* biases: normal, std 0.01; the LSTM bias likewise;
* BatchNorm: scale uniform(0.5, 1.0) (0.25..0.5 on the last norm of a
  bottleneck, which keeps the residual sum from growing 16 blocks long),
  bias normal 0.05, moving mean normal 0.05, moving variance
  uniform(0.8, 1.2).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

VGG_LAYERS = [
    ("conv1_1", 64, False), ("conv1_2", 64, True),
    ("conv2_1", 128, False), ("conv2_2", 128, True),
    ("conv3_1", 256, False), ("conv3_2", 256, False), ("conv3_3", 256, True),
    ("conv4_1", 512, False), ("conv4_2", 512, False), ("conv4_3", 512, True),
    ("conv5_1", 512, False), ("conv5_2", 512, False), ("conv5_3", 512, False),
]
# (stage, bottleneck width, identity blocks after the projection block, stride)
RESNET_STAGES = [("2", 64, 2, 1), ("3", 128, 3, 2), ("4", 256, 5, 2), ("5", 512, 2, 2)]

Spec = Dict[str, Tuple[Tuple[int, ...], str]]


def context_shape(model: dict) -> Tuple[int, int]:
    """(N, D) of the encoder's grid at the configuration's image size."""
    size = int(model.get("image_size", 224))
    if model["cnn"] == "vgg16":
        return (math.ceil(size / 16) ** 2, 512)
    return (math.ceil(size / 32) ** 2, 2048)


def _bn(spec: Spec, scope: str, name: str, c: int, last: bool = False) -> None:
    spec[f"params/cnn/{scope}{name}/scale"] = ((c,), "bn_scale_last" if last else "bn_scale")
    spec[f"params/cnn/{scope}{name}/bias"] = ((c,), "bn_bias")
    spec[f"batch_stats/{scope}{name}/mean"] = ((c,), "bn_bias")
    spec[f"batch_stats/{scope}{name}/var"] = ((c,), "bn_var")


def _cnn_spec(model: dict) -> Spec:
    spec: Spec = {}
    if model["cnn"] == "vgg16":
        cin = 3
        for name, cout, _ in VGG_LAYERS:
            kind = "conv_first" if name == "conv1_1" else "conv"
            spec[f"params/cnn/{name}/conv/kernel"] = ((3, 3, cin, cout), kind)
            spec[f"params/cnn/{name}/conv/bias"] = ((cout,), "bias")
            cin = cout
        return spec
    spec["params/cnn/conv1/conv/kernel"] = ((7, 7, 3, 64), "conv_first")
    spec["params/cnn/conv1/conv/bias"] = ((64,), "bias")
    _bn(spec, "", "bn_conv1", 64)
    cin = 64
    for stage, c, n_identity, _stride in RESNET_STAGES:
        for i in range(n_identity + 1):
            st = f"{stage}{chr(ord('a') + i)}"
            scope = f"res{st}/"
            branches = [("2a", 1, cin, c), ("2b", 3, c, c), ("2c", 1, c, 4 * c)]
            if i == 0:
                branches.insert(0, ("1", 1, cin, 4 * c))
            for br, k, ci, co in branches:
                spec[f"params/cnn/{scope}res{st}_branch{br}/conv/kernel"] = ((k, k, ci, co), "conv")
                _bn(spec, scope, f"bn{st}_branch{br}", co, last=br == "2c")
            cin = 4 * c
    return spec


def _decoder_spec(model: dict) -> Spec:
    m = model
    for key in ("num_initialize_layers", "num_attend_layers", "num_decode_layers"):
        if int(m.get(key, 2)) != 2:
            raise ValueError(f"the reference implements the two-layer {key} only")
    E, H, V = m["dim_embedding"], m["num_lstm_units"], m["vocabulary_size"]
    N, D = context_shape(m)
    di, da, dd = m["dim_initialize_layer"], m["dim_attend_layer"], m["dim_decode_layer"]
    p = "params/decoder/"
    spec: Spec = {
        p + "word_embedding/weights": ((V, E), "fc"),
        p + "lstm/kernel": ((D + E + H, 4 * H), "fc"),
        p + "lstm/bias": ((4 * H,), "bias"),
    }
    for name, d_in, d_out, bias in (
        ("initialize/fc_a1", D, di, True), ("initialize/fc_a2", di, H, True),
        ("initialize/fc_b1", D, di, True), ("initialize/fc_b2", di, H, True),
        ("attend/fc_1a", D, da, True), ("attend/fc_1b", H, da, True),
        ("attend/fc_2", da, 1, False),
        ("decode/fc_1", H + D + E, dd, True), ("decode/fc_2", dd, V, True),
    ):
        spec[p + name + "/kernel"] = ((d_in, d_out), "fc")
        if bias:
            spec[p + name + "/bias"] = ((d_out,), "bias")
    return spec


def param_spec(model: dict) -> Spec:
    """{leaf path: (shape, kind)} for a configuration's ``model`` block."""
    return {**_cnn_spec(model), **_decoder_spec(model)}


def _draw(key, shape, kind: str):
    f32 = jnp.float32
    if kind in ("conv", "conv_first"):
        fan_in = shape[0] * shape[1] * shape[2]
        std = math.sqrt(2.0 / fan_in) * (1.0 / 64.0 if kind == "conv_first" else 1.0)
        return std * jax.random.normal(key, shape, f32)
    if kind == "fc":
        return jax.random.uniform(key, shape, f32, -0.08, 0.08)
    if kind == "bias":
        return 0.01 * jax.random.normal(key, shape, f32)
    if kind == "bn_bias":
        return 0.05 * jax.random.normal(key, shape, f32)
    if kind == "bn_scale":
        return jax.random.uniform(key, shape, f32, 0.5, 1.0)
    if kind == "bn_scale_last":
        return jax.random.uniform(key, shape, f32, 0.25, 0.5)
    if kind == "bn_var":
        return jax.random.uniform(key, shape, f32, 0.8, 1.2)
    raise ValueError(kind)


def make_weights(model: dict, seed: int) -> Dict[str, jax.Array]:
    """All leaves, float32, on the default device, from one jitted call.
    Keys are threefry (jax's default), so the weights do not depend on the
    backend: the CPU tests and the chip see the same tree for a seed."""
    spec = param_spec(model)
    names = sorted(spec)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        return {n: _draw(keys[i], *spec[n]) for i, n in enumerate(names)}

    # the seed may exceed 32 signed bits: fold the high word in
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return build(key)


def nest(flat: Dict[str, jax.Array], prefix: str) -> dict:
    """The sub-tree under ``prefix`` (e.g. 'params/decoder') as nested dicts."""
    out: dict = {}
    for name, value in flat.items():
        if not name.startswith(prefix + "/"):
            continue
        node = out
        parts = name[len(prefix) + 1:].split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return out
