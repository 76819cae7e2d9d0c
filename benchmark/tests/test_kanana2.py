"""The kanana-2 configuration's pieces under benchmark/, tiny, on the CPU:
the rehearsal of its cell; a lower precision and four sabotaged programs
(a token, the selection bias, the rotary key in the step, the shared expert)
each coming out not correct; the parameter spec against the program's own
tree and the file's count; ``flops_mla`` against the TPU compiler's count;
the configuration file against the catalog's rule; BENCHMARK.json's lists
against the mix."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

CELL = "kanana2-eval-beam3-b256"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "sat-kanana2-30b-a3b.json")
NEW_METRICS = ["lm_mla_prefill_device_ms", "lm_mla_step_device_ms", "lm_mla_absorb_device_ms",
               "lm_moe_shared_device_ms", "lm_state_mb", "lm_mla_step_roofline_share"]


def _cell(rehearsal=True):
    import harness

    cell = harness.Cell(CELL, rehearsal=rehearsal)
    if rehearsal:
        cell.model.update(cell.config["rehearsal_model"])
    return cell


def test_rehearsal_passes_reads_the_counters_and_the_fp8_control_stands_clear():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL, "--seed", str(2 ** 31 + 7),
         "--seconds", "3", "--trace", "0", "--cpu-rehearsal", "--control", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["device"]["platform"] == "cpu"
    assert "metrics" not in last and "correct" not in last
    assert {"lm_moe_load_max_over_mean", "lm_state_mb"} <= set(last["per_layer_names"])
    notes = next(ln["notes"] for ln in lines if "notes" in ln)
    assert notes["control"]["fp8"]["fails"], notes
    assert notes["route_captions"] >= 8 and notes["route_agreement"] >= 0.96
    # the state is the latents': 3 layers x (4 images x 4 positions + 12 beams x 20 steps) x (32 + 32) x 2 bytes
    # + the record 12 x 20 x 2 expert layers x 3 x 4 bytes
    assert notes["lm_state_mb"] == pytest.approx((3 * (16 + 240) * 64 * 2 + 12 * 20 * 2 * 3 * 4) / 1e6)
    assert len(os.listdir(os.path.join(BENCH_DIR, ".work", CELL))) == 1


@pytest.mark.parametrize("sabotage,failed", [
    ("token", "rank_gap"), ("no_expert_bias", "route_agreement"),
    ("no_rope_key", "score_gap_mean"), ("no_shared_expert", "score_gap_mean"),
])
def test_a_broken_program_is_not_correct(sabotage, failed):
    """One served token altered where it is produced; ``expert_bias`` zeroed
    in the checkpoint the program loads; the step's ``q_rope . k_rope``
    dropped (the prefill keeps it: only prefill + cached steps against the
    full forward can tell); the shared expert's output zeroed."""
    code = (
        "import sys, json, types; sys.argv=['run.py']; import run, harness;"
        f"a=types.SimpleNamespace(workload={CELL!r}, seed=2 ** 31 + 7, seconds=3.0, trace=0, cpu_rehearsal=True, rates=None);"
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


def test_param_spec_equals_the_program_s_tree_and_the_file_s_count():
    """Names, shapes AND dtypes, at the rehearsal's widths and (shapes only,
    nothing is made) at the published ones; the builder's count of the cut
    is the configuration file's."""
    import jax

    from sat_tpu.train.step import create_train_state

    import harness
    from reference import params_kanana2

    for rehearsal in (True, False):
        cell = _cell(rehearsal)
        config = harness.program_config(cell, "/tmp/k", "/tmp/r", 1)
        shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes.params)
        program = {"params/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
                   for path, leaf in flat}
        spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype) in
                params_kanana2.param_spec(cell.model).items()}
        assert program == spec
    count = lambda part: sum(int(np.prod(shape)) for name, (shape, _d) in spec.items()  # noqa: E731
                             if "/decoder/" in name and part in name)
    said = cell.config["parameters"]
    assert count("") == said["decoder"] == 3_150_605_312 and said["decoder_bytes_bfloat16"] == 2 * count("")
    assert count("/connector/") == said["connector"] and count("/lm/") == said["stack"]
    assert count("/01/self_attn/") == said["attention_per_layer"]
    assert count("/lm/layers/01/") == said["expert_layer"] and count("/lm/layers/00/") == said["dense_layer"]
    assert count("/lm/embed_tokens") == count("/lm/lm_head") == said["embedding"] == said["head"]


def test_the_weights_are_the_seed_s_and_bfloat16_exact():
    from reference import params_kanana2

    model = _cell().model
    a = params_kanana2.make_weights(model, 2 ** 33 + 1)
    pick = lambda n: n.endswith(("lm/lm_head", "shared/w2"))  # noqa: E731
    b = params_kanana2.make_weights(model, 2 ** 33 + 1, only=pick)
    c = params_kanana2.make_weights(model, 2 ** 33 + 2, only=pick)
    assert len(b) == 3                               # the head and two expert layers' shared w2
    for name in b:
        assert np.array_equal(a[name], b[name]) and not np.array_equal(b[name], c[name])
        assert b[name].dtype == params_kanana2.BF16
    assert not np.array_equal(a["params/decoder/lm/lm_head"].T, a["params/decoder/lm/embed_tokens"])


def test_the_calibration_centres_the_prefix_and_spreads_the_load():
    from reference import kanana2_captioner as ref
    from reference import params_kanana2

    model = _cell().model
    weights = params_kanana2.make_weights(model, 5)
    rng = np.random.default_rng(5)
    size, T, V = model["image_size"], model["max_caption_length"], model["vocabulary_size"]
    images = rng.integers(0, 256, (24, size, size, 3), dtype=np.uint8)
    tokens = rng.integers(2, V, (24, T)).astype(np.int32)
    fitted = ref.calibrate(model, weights, images[:16], tokens[:16], block=8)
    assert sorted(fitted) == sorted(k for k in weights if k.endswith(("connector/bias", "expert_bias")))

    def fullest(leaves):
        def weights_of(prefix):
            path = "params/decoder/" + prefix
            return leaves[path] if path in leaves else ref.nest(leaves, path)
        _, routes = ref.forward(weights_of, model, ref._grids(model, weights, images[16:], "f32"), tokens[16:])
        counts = np.stack([np.bincount(np.asarray(r).ravel(), minlength=model["num_experts"]) for r in routes])
        return counts.max(axis=1) / counts.mean(axis=1)

    drawn, balanced = fullest(weights), fullest({**weights, **fitted})
    assert (balanced < drawn).all() and balanced.max() < 2.0, (drawn, balanced)


def test_the_configuration_file_keeps_every_published_number():
    """The catalog's rule: every key of the source's config at the top
    level with the source's value, but for the one key ``reduced`` names;
    the ``model`` block says the same widths under the program's names, by
    the file's own mapping."""
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 128256,
    }
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 5 and cfg["published"] == {"num_hidden_layers": 48}
    model = cfg["model"]
    for source_key, field in cfg["config_fields"].items():
        if field in model and source_key != "model_type":
            assert model[field] == cfg[source_key], source_key
    assert model["decoder"] == cfg["model_type"] and model["num_dense_layers"] == cfg["first_k_dense_replace"]
    assert model["layer_types"] == ["latent_attention"] * len(cfg["kept_layers"]) and cfg["kept_layers"] == [0, 1, 2, 3, 4]
    assert cfg["deployment"] and len(cfg["assumed"]) >= 6


def test_benchmark_json_s_lists_agree_with_the_mix():
    """The cell reports ``setup_s`` + ``decode_captions_per_s``; it is
    appended (last) to every list the lfm2 cell is on; the six metrics this
    configuration brings list this cell alone and have a file each."""
    import harness

    cell = _cell(rehearsal=False)
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s", "decode_captions_per_s"]
    assert cell.entry["chips"] == 1 and cell.mix["driver"] == "decode_offline_mla"
    per_layer = {m["name"]: m for m in cell.bench["per_layer"]}
    shared = [m for m in per_layer.values() if "lfm2-eval-beam3-b256" in m["workloads"]]
    assert len(shared) == 21 and all(m["workloads"][-1] == CELL for m in shared)
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] == "decode_captions_per_s"
        spec = harness.load_json(harness.metric_file(name))
        assert spec["unit"] == per_layer[name]["unit"] and spec["layer"] == per_layer[name]["layer"]
    assert {m["name"] for m in cell.per_layer()} == {m["name"] for m in shared} | set(NEW_METRICS)
    limits = cell.mix["limits"]
    assert set(limits) == {"score_gap", "score_gap_mean", "rank_gap", "route_agreement_min"}
    assert cell.mix["trace_seconds"] >= 3.0 and cell.mix["warm_batches"] == 4


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


def test_flops_mla_against_the_compiler_s_count(one_chip):
    """The program's own step attention (``attend_absorbed``) for 48 rows of
    16 images over 25 + 4 latents at a quarter of the published widths,
    compiled for a described v5e: the compiler counts the products the
    shapes count (and the element-wise work of the softmax and the rope)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from sat_tpu.config import Config
    from sat_tpu.models import deepseek_v3

    import flops_mla

    model = dict(hidden_size=512, num_attention_heads=8, kv_lora_rank=128, qk_nope_head_dim=32,
                 qk_rope_head_dim=16, v_head_dim=32)
    config = Config(decoder="deepseek_v3", num_hidden_layers=1, num_dense_layers=1,
                    layer_types=("latent_attention",), **model)
    B, K, N, T, t = 16, 3, 25, 8, 3
    H, nh, width = 512, 8, 128 + 16
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    m = {"q_proj": sd((H, nh * 48)), "kv_a_proj": sd((H, width)), "kv_a_layernorm": sd((128,)),
         "kv_b_proj": sd((128, nh * 64)), "o_proj": sd((nh * 32, H))}
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(lambda m, h, prefix, suffix: deepseek_v3.attend_absorbed(
            m, config, h, prefix, suffix, jnp.int32(t))).lower(
            m, sd((B * K, H)), sd((B, N, width)), sd((B * K, T, width))).compile()
        counted = compiled.cost_analysis()["flops"]
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    # the program scores all T suffix slots and masks those past t: the yardstick counts the t + 1 it needs
    want = flops_mla.step_attention_flops(model, B * K, N + t + 1)
    slots = flops_mla.step_attention_flops(model, B * K, N + T)
    assert want <= slots <= counted <= 1.05 * slots, (counted, want, slots)
    rows, lat = 768, 196 + 1
    full = dict(hidden_size=2048, num_attention_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128)
    assert flops_mla.step_attention_flops(full, rows, lat) == 2 * rows * (
        2048 * 6144 + 2048 * 576 + 32 * 128 * 512 + 32 * 576 * lat + 32 * 512 * lat + 32 * 512 * 128 + 4096 * 2048)
    assert flops_mla.step_attention_bytes(full, 256, rows, 196, 1) == 2 * (
        2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048 + (256 * 196 + rows) * 576 + 2 * rows * 2048)
