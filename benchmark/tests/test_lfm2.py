"""The LFM2-MoE configuration's pieces under benchmark/, tiny, on the CPU:
the driver kind's rehearsal; the parameter spec against the program's own
tree; the calibration of the router's balance; a lower precision, a sabotaged token and a dropped ``expert_bias``
each coming out not correct; ``flops_lfm2`` against the TPU compiler's
count; the configuration file against the catalog's rule."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

CELL = "lfm2-eval-beam3-b256"


def _cell(rehearsal=True):
    import harness

    cell = harness.Cell(CELL, rehearsal=rehearsal)
    if rehearsal:
        cell.model.update(cell.config["rehearsal_model"])
    return cell


def _run(*extra, seed=2 ** 31 + 7):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL, "--seed", str(seed),
         "--seconds", "3", "--trace", "0", "--cpu-rehearsal", *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=900)


def test_rehearsal_passes_reads_the_counter_and_the_fp8_control_stands_clear():
    proc = _run("--control", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["device"]["platform"] == "cpu"
    assert "metrics" not in last and "correct" not in last
    assert "lm_moe_load_max_over_mean" in last["per_layer_names"]
    notes = next(ln["notes"] for ln in lines if "notes" in ln)
    # the nearest precision below, in the program's place, through the same
    # comparison with the same limits: it is not correct
    assert notes["control"]["fp8"]["fails"], notes
    # the routes compared are the timed beam program's own record
    assert notes["route_captions"] >= 8 and notes["route_agreement"] >= 0.96
    # the driver's own rule: one seed's gigabytes at a time
    assert len(os.listdir(os.path.join(BENCH_DIR, ".work", CELL))) == 1


@pytest.mark.parametrize("sabotage,failed", [("token", "rank_gap"), ("no_expert_bias", "route_agreement")])
def test_a_broken_program_is_not_correct(sabotage, failed):
    """One served token altered where it is produced; ``expert_bias`` zeroed
    in the checkpoint the program loads (and only there): the program then
    chooses other experts than the reference."""
    code = (
        "import sys, json, types; sys.argv=['run.py']; import run, harness;"
        f"a=types.SimpleNamespace(workload={CELL!r}, seed=9, seconds=3.0, trace=0, cpu_rehearsal=True, rates=None);"
        f"cell, facts, out = run.run_cell(a, sabotage={sabotage!r});"
        "print(json.dumps({'checks': {c['name']: [c['value'], c.get('limit')] for c in out.checks},"
        " 'agreement': out.notes['route_agreement']}))"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=BENCH_DIR, capture_output=True, text=True,
                          timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    value, limit = got["checks"][failed]
    assert (value > limit) if limit is not None else (value is False), got


def test_param_spec_equals_the_program_s_tree():
    """Names, shapes AND dtypes, at the rehearsal's widths and (shapes only,
    nothing is made) at the published ones."""
    import jax

    from sat_tpu.train.step import create_train_state

    import harness
    from reference import params_lfm2

    for rehearsal in (True, False):
        cell = _cell(rehearsal)
        config = harness.program_config(cell, "/tmp/k", "/tmp/r", 1)
        shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes.params)
        program = {"params/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
                   for path, leaf in flat}
        spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype) in
                params_lfm2.param_spec(cell.model).items()}
        assert program == spec
    total = sum(int(np.prod(shape)) for name, (shape, _dtype) in spec.items() if "/decoder/" in name)
    assert total == 3_136_899_584           # 3.14 B: PERF.md section 4's arithmetic of the cut


def test_the_weights_are_the_seed_s_and_bfloat16_exact():
    from reference import params_lfm2

    model = _cell().model
    a = params_lfm2.make_weights(model, 2 ** 33 + 1)
    b = params_lfm2.make_weights(model, 2 ** 33 + 1, only=lambda n: n.endswith("connector/kernel"))
    c = params_lfm2.make_weights(model, 2 ** 33 + 2, only=lambda n: n.endswith("connector/kernel"))
    (name, again), (_, other) = next(iter(b.items())), next(iter(c.items()))
    assert np.array_equal(a[name], again) and not np.array_equal(again, other)
    assert np.array_equal(again, again.astype(params_lfm2.BF16).astype(np.float32))
    bias = [v for k, v in a.items() if k.endswith("expert_bias")]
    assert bias and all(v.dtype == np.float32 and v.std() > 0.03 for v in bias)


def test_the_calibration_centres_the_prefix_and_spreads_the_load():
    """``lfm2_captioner.calibrate`` on a seeded batch: the connector's bias
    maps the batch's mean grid vector to zero, and on FRESH images and
    captions the fullest expert of every layer takes less of the load than
    under the seed's own draw of the two kinds of leaf."""
    from reference import lfm2_captioner as ref
    from reference import params_lfm2

    model = _cell().model
    weights = params_lfm2.make_weights(model, 5)
    rng = np.random.default_rng(5)
    size, T, V = model["image_size"], model["max_caption_length"], model["vocabulary_size"]
    images = rng.integers(0, 256, (24, size, size, 3), dtype=np.uint8)
    tokens = rng.integers(2, V, (24, T)).astype(np.int32)
    fitted = ref.calibrate(model, weights, images[:16], tokens[:16], block=8)
    assert sorted(fitted) == sorted(k for k in weights if k.endswith(("connector/bias", "expert_bias")))
    grids = ref._grids(model, weights, images[:16], "f32")
    centre = grids.reshape(-1, grids.shape[-1]).mean(0) @ weights["params/decoder/connector/kernel"]
    np.testing.assert_allclose(centre + fitted["params/decoder/connector/bias"], 0, atol=0.02 * np.abs(centre).max())

    def fullest(leaves):
        def weights_of(prefix):
            path = "params/decoder/" + prefix
            return leaves[path] if path in leaves else ref.nest(leaves, path)
        _, routes = ref.forward(weights_of, model, ref._grids(model, weights, images[16:], "f32"), tokens[16:])
        counts = np.stack([np.bincount(np.asarray(r).ravel(), minlength=model["num_experts"]) for r in routes])
        return counts.max(axis=1) / counts.mean(axis=1)

    drawn, balanced = fullest(weights), fullest({**weights, **fitted})
    assert (balanced < drawn).all() and balanced.max() < 2.0 < drawn.max(), (drawn, balanced)


def test_the_configuration_file_keeps_every_published_number():
    """The catalog's rule: every number of the source's config under the
    same top-level key, but for the keys ``reduced`` names; the program's
    ``model`` block says the same widths."""
    with open(os.path.join(ROOT, "benchmark", "configs", "sat-lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    published = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168, "max_position_embeddings": 128000,
        "moe_intermediate_size": 1792, "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1, "vocab_size": 65536,
    }
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers"}
    assert len(cfg["layer_types"]) == 24 and cfg["model_type"] == "lfm2_moe"
    model = cfg["model"]
    for key in published:
        if key in model:
            assert model[key] == cfg[key], key
    assert model["vocabulary_size"] == cfg["vocab_size"]
    assert model["layer_types"] == [cfg["layer_types"][i] for i in cfg["kept_layers"]]
    assert len(model["layer_types"]) == cfg["num_hidden_layers"] == 9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_flops_lfm2_against_the_compiler_s_count(one_chip, monkeypatch):
    """The program's own expert layer at the published widths over a step's
    768 rows, compiled for a described v5e: the compiler counts the routed
    pairs' products and the router's, and so do the shapes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from sat_tpu.config import Config
    from sat_tpu.models import lfm2

    import flops_lfm2

    cell = _cell(rehearsal=False)
    model = cell.model
    config = Config(**{k: tuple(v) if isinstance(v, list) else v for k, v in model.items()})
    H, E, I = model["hidden_size"], model["num_experts"], model["moe_intermediate_size"]
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    p = {"ffn_norm": sd((H,), jnp.bfloat16),
         "feed_forward": {"gate": sd((H, E), jnp.bfloat16), "expert_bias": sd((E,), jnp.float32),
                          "w1": sd((E, H, I), jnp.bfloat16), "w3": sd((E, H, I), jnp.bfloat16),
                          "w2": sd((E, I, H), jnp.bfloat16)}}
    rows = 768
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        for backend in ("cpu", "tpu"):      # XLA's ragged_dot, then the Pallas grouped product
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            compiled = jax.jit(lambda q, x: lfm2.moe_ffn(q, config, x)).lower(
                p, sd((rows, H), jnp.bfloat16)).compile()
            counted = compiled.cost_analysis()["flops"]
            want = flops_lfm2.moe_layer_flops(model, rows)
            # the compiler also counts the element-wise work (norm, SwiGLU, the weighted sum)
            assert want <= counted <= 1.02 * want, (backend, counted, want)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    assert flops_lfm2.expert_flops(model, rows) == 2 * 3 * 2048 * 1792 * 768 * 4
    assert flops_lfm2.expert_bytes(model, rows) == 2 * (3 * 32 * 2048 * 1792 + 2 * 768 * 4 * 2048)
