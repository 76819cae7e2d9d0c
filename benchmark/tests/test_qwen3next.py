"""The Qwen3-Next configuration's pieces under benchmark/, tiny, on the
CPU: the rehearsal of its cell (toy widths but for a stream 256 wide, 2
key heads serving 6 value heads, a rotary part of half the head, 8 of 16
experts held beside one gated shared expert); the two lower precisions
(float8 throughout; the state rounded to bfloat16) and the sabotaged
programs (the recurrence without its decay, a rope over the whole head, a
state kept in bfloat16, an ungated shared expert, a token) each coming out
not correct; the parameter spec against the program's own tree and the
file's arithmetic; ``flops_qwen3next`` against hand counts; the
configuration file against the catalog's rule; the new metric files against
the readers and scope files they name; BENCHMARK.json's lists against the
mix.  Every pin is of MEMBERSHIP, never of a position in a list (PERF.md
section 7 j): a later configuration's entries after these break nothing
here."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

CELL = "qwen3next-eval-beam3-b128"
CONFIG = "sat-qwen3-next-80b-a3b"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")
NEW_METRICS = ["lm_gdn_proj_device_ms", "lm_gdn_scan_device_ms", "lm_gdn_state_device_ms", "lm_gdn_state_mb",
               "lm_gdn_scan_roofline_share", "lm_gdn_state_roofline_share", "lm_gdn_held_pair_share",
               "lm_gdn_held_experts_roofline_share"]
# accepted metrics right for this cell whose lists the benchmark's own tests hold to other cells: where the
# reading is wanted this configuration brings a metric file of a new name on the same reader
HELD_TO_OTHER_CELLS = {"lm_moe_held_pair_share", "lm_moe_held_experts_roofline_share", "decode_device_empty_share",
                       "decode_empty_detok_ms", "decode_empty_data_wait_ms", "decode_empty_dispatch_ms"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LIMITS = {"score_gap", "score_gap_mean", "rank_gap", "route_agreement_min", "state_gap", "state_bf16_share"}


def _cell(rehearsal=True):
    import harness

    cell = harness.Cell(CELL, rehearsal=rehearsal)
    if rehearsal:
        cell.model.update(cell.config["rehearsal_model"])
    return cell


def test_rehearsal_passes_reads_the_counters_and_keeps_no_seed():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL, "--seed", str(2 ** 31 + 7),
         "--seconds", "3", "--trace", "0", "--cpu-rehearsal", "--control", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["device"]["platform"] == "cpu"
    assert "metrics" not in last and "correct" not in last
    assert {"lm_moe_load_max_over_mean", "lm_state_mb", "lm_gdn_state_mb",
            "lm_gdn_held_pair_share"} <= set(last["per_layer_names"])
    notes = next(ln["notes"] for ln in lines if "notes" in ln)
    # the float8 control fails the limits the other cells use and the state's gap; the state rounded to
    # bfloat16 fails the share of its values on the bfloat16 grid and NOTHING else
    assert set(notes["control"]["fp8"]["fails"]) == {"score_gap", "score_gap_mean", "rank_gap", "route_agreement",
                                                     "state_gap"}, notes
    assert notes["control"]["state_bf16"]["fails"] == ["state_bf16_share"], notes
    assert notes["control"]["state_bf16"]["state_gap"] < notes["state_gap"] < 0.05
    assert notes["route_captions"] >= 8 and notes["moe_pairs_over"] == 0 and notes["state_bf16_share"] < 1e-3
    # the state: three layers' S [6, 16, 8] float32 and taps [3, 112] bfloat16 a beam (12 beams); the full
    # layer's keys and values 32 wide, 36 positions an image and 20 a beam; the record of routes
    recurrent = 3 * 12 * (6 * 16 * 8 * 4 + 3 * 112 * 2)
    full = 2 * 32 * 2 * (4 * 36 + 12 * 20)
    records = 12 * 20 * (4 * 3) * 4
    assert notes["lm_gdn_state_mb"] == pytest.approx(recurrent / 1e6)
    assert notes["lm_state_mb"] == pytest.approx((recurrent + full + records) / 1e6)
    assert notes["lm_moe_held_pair_share"] == pytest.approx(0.5, abs=0.1)
    kept = os.path.join(BENCH_DIR, ".work", CELL)
    assert len(os.listdir(kept)) == 1 and "models0" not in os.listdir(os.path.join(kept, os.listdir(kept)[0]))


@pytest.mark.parametrize("sabotage,failed,passed", [
    ("no_decay", "state_gap", None), ("rope_whole_head", "score_gap", "state_gap"),
    ("state_bf16", "state_bf16_share", "state_gap"), ("ungated_shared", "score_gap", "state_bf16_share"),
    ("token", "rank_gap", "state_bf16_share"),
])
def test_a_broken_program_is_not_correct(sabotage, failed, passed):
    """The recurrence with g = 0 (S never forgets: the state's own gap
    reads over 1); a rope over all of a full layer's head (the DeltaNet
    layers before it are untouched: their states agree); S kept in
    bfloat16 between steps (no gap sees it; its values lie on the bfloat16
    grid); the shared expert without its gate; one served token altered."""
    code = (
        "import sys, json, types; sys.argv=['run.py']; import run, harness;"
        f"a=types.SimpleNamespace(workload={CELL!r}, seed=2 ** 31 + 7, seconds=3.0, trace=0, cpu_rehearsal=True, rates=None);"
        f"cell, facts, out = run.run_cell(a, sabotage={sabotage!r});"
        "print(json.dumps({'checks': {c['name']: [c['value'], c.get('limit')] for c in out.checks}}))"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=BENCH_DIR, capture_output=True, text=True,
                          timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    value, limit = got["checks"][failed]
    assert (value > limit) if limit is not None else (value is False), got
    if passed:
        value, limit = got["checks"][passed]
        assert value <= limit, got


def test_param_spec_equals_the_program_s_tree_and_the_file_s_arithmetic():
    """Names, shapes AND dtypes, at the rehearsal's widths and (shapes only,
    nothing is made) at the published ones; the configuration's
    ``parameters`` recomputed from the spec."""
    import jax

    from sat_tpu.train.step import create_train_state

    import harness
    from reference import params_qwen3next

    for rehearsal in (True, False):
        cell = _cell(rehearsal)
        config = harness.program_config(cell, "/tmp/k", "/tmp/r", 1)
        shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes.params)
        program = {"params/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
                   for path, leaf in flat}
        spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype) in
                params_qwen3next.param_spec(cell.model).items()}
        assert program == spec
    count = lambda *parts: sum(int(np.prod(shape)) for name, (shape, _d) in spec.items()  # noqa: E731
                               if "/decoder/" in name and all(p in name for p in parts))
    said = cell.config["parameters"]
    assert count("") == said["decoder"] == 3_678_663_744 and said["decoder_bytes_bfloat16"] == 2 * count("")
    assert count("/connector/") == said["connector"] == 1_050_624
    assert count("/00/linear_attn/") == count("/02/linear_attn/") == said["gated_delta_net_mixer"] == 33_718_464
    assert count("/03/self_attn/") == said["full_attention_mixer"] == 27_263_488 and not count("/03/linear_attn/")
    shared = count("/03/feed_forward/shared/")
    assert shared == said["shared_expert"] + said["shared_gate"] == said["one_expert"] + 2048 == 3_147_776
    assert count("/03/feed_forward/gate") == said["router"] == 2048 * 512 and not count("expert_bias")
    norms = count("/03/input_layernorm") + count("/03/post_attention_layernorm")
    assert norms == said["layer_norms"] == 4096
    routed = sum(count(f"/03/feed_forward/{w}") for w in ("w1", "w3", "w2"))       # the shared leaves lie under shared/
    assert routed == said["routed_experts_held"] == 256 * said["one_expert"]
    assert count("/lm/layers/00/") == said["delta_net_layer_as_held"] == 843_225_280
    assert count("/lm/layers/03/") == said["full_layer_as_held"] == 836_770_304
    assert count("/lm/layers/00/") - routed == said["delta_net_layer_without_routed_experts"] == 37_918_912
    assert count("/lm/layers/03/") - routed == said["full_layer_without_routed_experts"] == 31_463_936
    assert count("/lm/embed_tokens") == count("/lm/lm_head") == said["embedding_slice"] == said["head_slice"]
    whole = 36 * (said["delta_net_layer_without_routed_experts"] + said["routed_experts_whole"]) + 12 * (
        said["full_layer_without_routed_experts"] + said["routed_experts_whole"]) + 2 * 151_936 * 2048 + 2048
    assert whole == said["published_whole"] and 79.6e9 < whole < 79.7e9
    # one period with every expert: 13.2 GB before embedding and head, so no chip holds it
    period = 3 * said["delta_net_layer_without_routed_experts"] + said["full_layer_without_routed_experts"] \
        + 4 * said["routed_experts_whole"]
    assert 2 * period > 13.1e9


def test_the_configuration_keeps_every_published_width():
    """The catalog's rule: every number of the catalog's ``config`` under
    the same key, but for the keys in ``reduced``, each with its published
    value beside it and within the guide's floors."""
    cfg = json.load(open(CONFIG_FILE))
    row = next(json.loads(ln) for ln in open(CATALOG) if json.loads(ln)["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert cfg["source_url"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert all(cfg["published"][k] == row["config"][k] for k in cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in cfg["reduced"])
    kept, interval = cfg["kept_layers"], row["config"]["full_attention_interval"]
    assert kept == list(range(cfg["num_hidden_layers"])) and len(kept) >= 4 and not row["config"]["mlp_only_layers"]
    assert ["full_attention" if (i + 1) % interval == 0 else "linear_attention" for i in kept] \
        == cfg["kept_layer_types"] == cfg["model"]["layer_types"]
    assert cfg["kept_layer_types"] == ["linear_attention"] * 3 + ["full_attention"]          # one whole period, 3:1
    assert cfg["num_experts"] == cfg["model"]["experts_held"] == 256 >= 8
    assert cfg["model"]["num_experts"] == cfg["published"]["num_experts"] == 512
    assert cfg["vocab_size"] * 2 == cfg["published"]["vocab_size"] and cfg["model"]["vocabulary_size"] == cfg["vocab_size"]
    m, src = cfg["model"], row["config"]
    same = ["hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
            "rope_theta", "partial_rotary_factor", "norm_topk_prob", "tie_word_embeddings", "moe_intermediate_size",
            "shared_expert_intermediate_size", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim"]
    assert all(m[k] == src[k] for k in same)
    assert m["moe_intermediate_size"] == row["expert_width"] == 512 and m["norm_eps"] == src["rms_norm_eps"]
    assert m["use_expert_bias"] is False and m["routed_scaling_factor"] == 1.0 and m["num_dense_layers"] == 0
    assert m["scoring_func"] == "softmax" and m["shared_expert_gate"] is True and m["n_shared_experts"] == 1
    assert m["image_size"] == 224 and (m["image_size"] // 16) ** 2 == 196
    r = cfg["rehearsal_model"]          # the toy exercises the per-key-head layout and the partial rope
    assert r["linear_num_key_heads"] != r["linear_num_value_heads"] and r["partial_rotary_factor"] < 1
    assert "deployment" in cfg and len(cfg["assumed"]) >= 8 and "multi-token prediction head" in cfg["not_run"]


def test_flops_qwen3next_against_hand_counts():
    import flops_qwen3next

    model = _cell(rehearsal=False).model
    # a value head's three products of its [128, 128] state a position, 32 heads
    assert flops_qwen3next.rule_flops(model, 1) == 2 * 3 * 128 * 128 * 32 == 3_145_728
    assert flops_qwen3next.rule_row_values(model) == 2 * 2048 + 2 * 4096
    assert flops_qwen3next.state_bytes(model) == 32 * 128 * 128 * 4 == 2_097_152
    run = types.SimpleNamespace(model=model, extras={"batch_size": 128, "beam_size": 3, "caption_steps": 20,
                                                     "step_held_pairs": [153600.0], "step_experts_visited": [20000.0]})
    scan = flops_qwen3next.prefill_scan(run)
    assert scan["flops"] == 128 * 3 * 196 * 3_145_728
    assert scan["bytes"] == 128 * 3 * (2 * 196 * 12288 + 2_097_152)
    steps = flops_qwen3next.step_state(run)
    assert steps["flops"] == 384 * 20 * 3 * 3_145_728
    assert steps["bytes"] == 384 * 20 * 3 * (2 * 2_097_152 + 4 * 12288)
    assert steps["bytes"] / 20 / 1e9 == pytest.approx(4.89, abs=0.01)           # the issue's 4.83 GB a step, + the rows
    peaks = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["TPU v5 lite"]
    # both are bound by what they read
    for work in (scan, steps):
        assert work["bytes"] / peaks["hbm_bytes_per_s"] > work["flops"] / peaks["bf16_flops_per_s"]
    # the held experts: flops_dsa's count at experts of 2048 x 512: 6.3 MB of maps an expert visited
    held = flops_qwen3next.step_experts_held(run)
    assert held["flops"] == 2 * 3 * 2048 * 512 * 153600
    assert held["bytes"] == 2 * (3 * 20000 * 2048 * 512 + 2 * 153600 * 2048)


def test_every_new_metric_names_a_reader_and_a_scope_file_that_exist():
    import harness
    from reducers import trace_scope_ms

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW_METRICS) <= set(listed)
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "decode_captions_per_s", name
        spec = json.load(open(harness.metric_file(name)))
        assert all(spec[k] == listed[name][k] for k in ("unit", "moves", "layer", "source"))
        assert os.path.exists(os.path.join(BENCH_DIR, "reducers", spec["reducer"] + ".py"))
        rules = spec["args"].get("rules")
        if rules is not None:
            assert spec["args"]["pick"] in [b for b, _ in trace_scope_ms.load_rules(rules)]
        if "counts" in spec["args"]:
            module, _, fn = spec["args"]["counts"].rpartition(".")
            assert callable(getattr(__import__(module), fn))
        if "roofline" in name:
            assert listed[name]["unit"] == "%" and listed[name]["layer"] == "kernels"
    # the cell is on no list whose count assumes latent heads, an indexer, a window or every expert's maps
    for name, m in listed.items():
        if name.startswith(("lm_mla_", "lm_dsa_", "lm_dots3_", "lm_attn_gate", "lm_swa_", "lm_gqa_")) \
                or name == "lm_moe_experts_roofline_share":
            assert CELL not in m["workloads"], name
    # and on none that the benchmark's own tests hold to other cells
    assert all(CELL not in listed[name]["workloads"] for name in HELD_TO_OTHER_CELLS)


def test_the_scope_rules_claim_the_program_s_scopes():
    """Every mixer op lies under ``decoder/lm/attn`` (a DeltaNet layer's in
    the segment ``gdn``, the gated layer's in ``full``), so the accepted
    mixer bucket and the phases' buckets read them; the new rule files tell
    the chunked rule, the steps' recurrence and what surrounds them apart;
    the other configurations' rule files take none of them."""
    import re

    from reducers import trace_scope_ms

    src = open(os.path.join(ROOT, "sat_tpu", "models", "qwen3_next.py")).read()
    parts = {"gdn": ("proj", "conv", "gates", "scan", "state", "norm"), "full": ("qkv", "rope", "scores", "gate")}
    for segment, names in parts.items():
        for part in names:
            assert f'"decoder/lm/attn/{segment}/{part}"' in src, (segment, part)
    assert set(re.findall(r'"decoder/lm/attn/([a-z]+)', src)) == {"gdn", "full", "norm", "residual"}
    first = lambda rules, name: next(b for b, rx in trace_scope_ms.load_rules(rules) if rx.search(name))  # noqa: E731
    pre, loop = "jit(f)/beam/prefill/while/body/", "jit(f)/beam/loop/while/body/"
    for segment, names in parts.items():
        for part in names:
            for phase, where in ((pre, "prefill"), (loop, "step")):
                name = phase + f"decoder/lm/attn/{segment}/{part}/dot_general"
                assert first("lm_beam_search", name) == "mixer" and first("lm_beam_phases", name) == where
                want = {"scan": "gdn_scan" if where == "prefill" else "other",
                        "state": "gdn_state" if where == "step" else "other"}.get(part, "gdn_proj") \
                    if segment == "gdn" else "full"
                assert first("lm_gdn", name) == want, name
                phases = {("gdn", "scan", "prefill"): "prefill_scan", ("gdn", "state", "step"): "step_state"}
                assert first("lm_gdn_phases", name) == phases.get((segment, part, where), "other")
                for rules in ("lm_mla_absorb", "lm_swa_gate", "lm_swa", "lm_gqa_phases" if where == "prefill" else "lm_swa"):
                    assert first(rules, name) == "other", (rules, name)
    assert first("lm_gdn", loop + "decoder/lm/attn/norm/reduce") == "block" == first("lm_gdn", pre + "decoder/lm/attn/residual/add")
    assert first("lm_gdn_phases", loop + "decoder/lm/moe/experts/gmm") == "step_held_experts"
    assert first("lm_gdn_phases", pre + "decoder/lm/moe/experts/gmm") == "other"
    assert first("beam_search", loop + "beam/expand/beam/tile/gather") == "tile"        # the reorder of S
    assert first("lm_beam_search", pre + "decoder/lm/moe/shared/dot_general") == "other"


def test_the_cell_is_on_the_lists_of_the_metrics_its_mix_reports():
    import harness

    cell = harness.Cell(CELL)
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s", "decode_captions_per_s"]
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) <= names
    command_a = {m["name"] for m in harness.Cell("command-a-plus-eval-beam3-b4").per_layer()}
    assert names - command_a == set(NEW_METRICS)
    assert {n for n in command_a - names if not n.startswith("lm_gqa_")} == set()
    assert cell.entry["chips"] == 1 and len(cell.entry["why"]) <= 200
    assert [w["name"] for w in cell.bench["workloads"]].count(CELL) == 1
    mix = cell.mix
    assert mix["driver"] == "decode_offline_gdn" and mix["program"]["batch_size"] == 128
    assert (mix["distinct_images"], mix["image_ids"], mix["warm_batches"], mix["sample_batches"],
            mix["sample_rows"], mix["trace_seconds"]) == (2048, 65536, 4, 4, 8, 3.0)
    assert mix["calibration_images"] <= 64
    assert set(mix["limits"]) == LIMITS == set(mix["rehearsal"]["limits"])
    config = next(c for c in cell.bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == cell.config["reduced"] and config["source"] == cell.config["source_url"]
    assert [c["file"] for c in cell.bench["configs"]].count(config["file"]) == 1
