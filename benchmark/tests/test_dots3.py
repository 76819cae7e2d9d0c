"""The dots3-note-prev configuration's pieces under benchmark/, tiny, on the
CPU: the rehearsal of its cell (toy widths but for a stream 1,024 wide, a
96-px image's 36 positions, a window of 9, ``index_topk`` 16, 4 of 8 experts
held); a lower precision and the sabotaged programs (no window, no gate, no
rescale, the full layers' rope base in a sliding layer, a token, the
selection bias, the shared expert) each coming out not correct; the
parameter spec against the program's own tree and the file's arithmetic;
``flops_dots3`` against hand counts; the configuration file against the
catalog's rule; the new metric files against the readers and scope files
they name; BENCHMARK.json's lists against the mix."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

CELL = "dots3-eval-beam3-b8"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "sat-dots3-note-prev.json")
NEW_METRICS = ["lm_swa_prefill_device_ms", "lm_swa_step_device_ms", "lm_attn_gate_device_ms", "lm_swa_state_mb",
               "lm_swa_prefill_roofline_share", "lm_swa_step_roofline_share", "lm_dots3_full_prefill_roofline_share",
               "lm_dots3_full_step_roofline_share"]
# accepted metrics right for this cell whose lists the benchmark's own tests hold as they are (test_glm52.py: to that
# cell alone; test_span_overlap.py: to the four eval cells): a ``benchmark`` PR extends lists and asserts together
HELD_TO_OTHER_CELLS = {"lm_dsa_index_device_ms", "lm_dsa_select_device_ms", "lm_dsa_selected_share",
                       "lm_moe_held_pair_share", "lm_moe_held_experts_roofline_share", "decode_device_empty_share",
                       "decode_empty_detok_ms", "decode_empty_data_wait_ms", "decode_empty_dispatch_ms"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    # the two shares of the full layers' selection and of the held experts stay among the notes (below): their
    # metrics' lists are held to the glm52 cell by that cell's tests (HELD_TO_OTHER_CELLS)
    assert {"lm_moe_load_max_over_mean", "lm_state_mb", "lm_swa_state_mb"} <= set(last["per_layer_names"])
    notes = next(ln["notes"] for ln in lines if "notes" in ln)
    assert set(notes["control"]["fp8"]["fails"]) == {"score_gap", "score_gap_mean", "rank_gap", "route_agreement",
                                                     "select_agreement"}, notes
    assert notes["route_captions"] >= 8 and notes["select_agreement"] >= 0.95 and notes["moe_pairs_over"] == 0
    visible = sum(36 + t + 1 for t in range(20))
    assert notes["lm_dsa_selected_share"] == pytest.approx(20 * 16 / visible)        # 16 of 37..56 a step
    assert notes["lm_swa_attended_share"] == pytest.approx(20 * 9 / visible)         # 9 of 37..56
    assert notes["prefill_fused_blocks_by_kind"] == [[0, 2], [0, 2]]                 # the lax blocks, on the CPU
    # the state: two full layers' latents (64) and indexer keys (48) over 36 positions an image and 20 a beam;
    # two sliding layers' kept tail (8 of 36 latents, 72 wide) an image and 20 a beam; the two records
    window = 2 * 72 * 2 * (4 * 8 + 12 * 20)
    full = 2 * (64 + 48) * 2 * (4 * 36 + 12 * 20)
    records = 12 * 20 * (3 * 3 + 2 * 16) * 4
    assert notes["lm_swa_state_mb"] == pytest.approx(window / 1e6)
    assert notes["lm_state_mb"] == pytest.approx((window + full + records) / 1e6)
    kept = os.path.join(BENCH_DIR, ".work", CELL)
    assert len(os.listdir(kept)) == 1 and "models0" not in os.listdir(os.path.join(kept, os.listdir(kept)[0]))


@pytest.mark.parametrize("sabotage,failed", [
    ("no_window", "score_gap_mean"), ("no_gate", "score_gap_mean"), ("no_rescale", "score_gap_mean"),
    ("swa_theta", "score_gap"), ("token", "rank_gap"), ("no_expert_bias", "route_agreement"),
    ("no_shared_expert", "score_gap_mean"),
])
def test_a_broken_program_is_not_correct(sabotage, failed):
    """Sliding layers that attend all they see; no gate; no rescale; a
    sliding layer under the full layers' rope base; one served token
    altered; ``expert_bias`` zeroed in the checkpoint the program loads; the
    shared expert's output zeroed."""
    code = (
        "import sys, json, types; sys.argv=['run.py']; import run, harness;"
        f"a=types.SimpleNamespace(workload={CELL!r}, seed=2 ** 31 + 7, seconds=3.0, trace=0, cpu_rehearsal=True, rates=None);"
        f"cell, facts, out = run.run_cell(a, sabotage={sabotage!r});"
        "print(json.dumps({'checks': {c['name']: [c['value'], c.get('limit')] for c in out.checks},"
        " 'share': out.notes['lm_swa_attended_share'], 'mb': out.notes['lm_swa_state_mb']}))"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=BENCH_DIR, capture_output=True, text=True,
                          timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    value, limit = got["checks"][failed]
    assert (value > limit) if limit is not None else (value is False), got
    # a program without the window attends every visible position and keeps the whole prefix
    assert (got["share"] == 1.0) == (sabotage == "no_window")
    assert got["mb"] == pytest.approx(2 * 72 * 2 * (4 * (36 if sabotage == "no_window" else 8) + 12 * 20) / 1e6)


def test_param_spec_equals_the_program_s_tree_and_the_file_s_arithmetic():
    """Names, shapes AND dtypes, at the rehearsal's widths and (shapes only,
    nothing is made) at the published ones; the configuration's
    ``parameters`` recomputed from the spec."""
    import jax

    from sat_tpu.train.step import create_train_state

    import harness
    from reference import params_dots3

    for rehearsal in (True, False):
        cell = _cell(rehearsal)
        config = harness.program_config(cell, "/tmp/k", "/tmp/r", 1)
        shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes.params)
        program = {"params/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
                   for path, leaf in flat}
        spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype) in
                params_dots3.param_spec(cell.model).items()}
        assert program == spec
    count = lambda *parts: sum(int(np.prod(shape)) for name, (shape, _d) in spec.items()  # noqa: E731
                               if "/decoder/" in name and all(p in name for p in parts))
    said = cell.config["parameters"]
    assert count("") == said["decoder"] == 4_089_780_736 and said["decoder_bytes_bfloat16"] == 2 * count("")
    assert count("/connector/") == said["connector"] == 2_626_560
    assert count("/01/self_attn/") == said["full_attention"] + said["indexer"] == 134_678_016 + 9_371_904
    assert count("/indexer/") == 2 * said["indexer"]
    assert count("/02/self_attn/") == said["sliding_attention"] == 90_834_944
    assert count("/lm/layers/00/") == said["dense_layer_full_with_indexer"] == 356_396_800
    assert count("/lm/layers/01/") == said["expert_layer_share_full"] == 923_938_816
    assert count("/lm/layers/03/") == said["expert_layer_share_sliding"] == 870_723_840
    assert count("/01/feed_forward/gate") + count("/01/feed_forward/expert_bias") == said["router"]
    assert count("/01/feed_forward/shared/") == said["shared_expert"] == said["one_expert"] == count("/01/feed_forward/w1") * 3 // 32
    assert count("/lm/embed_tokens") == count("/lm/lm_head") == said["embedding_slice"] == said["head_slice"]
    whole = said["expert_layer_share_full"] + (256 - 32) * said["one_expert"]
    assert whole == said["expert_layer_whole_full"] and 2 * whole > 12e9           # no chip holds one whole beside the rest
    # the fall-back the issue names, should the chip refuse 32 experts: 16 held
    assert count("") - 4 * 16 * said["one_expert"] == 2_579_831_296


def test_the_configuration_keeps_every_published_width():
    """The catalog's rule: every number of the catalog's ``config`` under
    the same key, but for the keys in ``reduced``, each with its published
    value beside it and within the guide's floors."""
    cfg = json.load(open(CONFIG_FILE))
    row = next(json.loads(ln) for ln in open(CATALOG) if json.loads(ln)["name"] == "dots3-note-prev")
    assert cfg["source_url"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert all(cfg["published"][k] == row["config"][k] for k in cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in cfg["reduced"])
    kept = cfg["kept_layers"]
    assert kept == list(range(cfg["num_hidden_layers"])) and len(kept) - cfg["first_k_dense_replace"] >= 4
    assert [row["config"]["layer_types"][i] for i in kept] == cfg["kept_layer_types"] == cfg["model"]["layer_types"]
    assert cfg["kept_layer_types"][1:] == ["full_attention"] + ["sliding_attention"] * 3      # one whole period, 1:3
    assert cfg["n_routed_experts"] == cfg["model"]["experts_held"] >= 8
    assert cfg["model"]["num_experts"] == cfg["published"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] and cfg["model"]["vocabulary_size"] == cfg["vocab_size"]
    m, src = cfg["model"], row["config"]
    same = ["hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta", "index_n_heads",
            "index_head_dim", "index_topk", "num_experts_per_tok", "n_shared_experts", "routed_scaling_factor",
            "sliding_window_size"] + [k for k in src if k.startswith("swa_") and k not in
                                      ("swa_attention_gate_type", "swa_num_key_value_heads")]
    assert len(same) == 24 and all(m[k] == src[k] for k in same)
    assert m["norm_eps"] == src["rms_norm_eps"] and m["num_dense_layers"] == src["first_k_dense_replace"]
    assert m["attention_gate"] == src["attention_gate_type"] == src["swa_attention_gate_type"] == "headwise"
    assert m["mla_lora_rescale"] is src["apply_mla_qkv_lora_rescale"] is True
    assert "deployment" in cfg and len(cfg["assumed"]) >= 8 and "MTP" in cfg["not_run"]


def test_flops_dots3_against_hand_counts():
    import flops_dots3

    model = _cell(rehearsal=False).model
    # a sliding layer's prefill, one image: 513 x 514 / 2 + (4096 - 513) x 513 keys in the band
    assert flops_dots3.band_keys(513, 4096) == 513 * 514 // 2 + 3583 * 513 == 1_969_920
    assert flops_dots3.band_keys(513, 100) == 5050
    assert flops_dots3.swa_prefill_flops(model, 4096) == 2 * (4096 * 1024 * 64 * 320 + 1_969_920 * 64 * 384)
    assert flops_dots3.swa_prefill_bytes(model, 4096) == 2 * (1024 * 64 * 320 + 4096 * 1088 + 4096 * 64 * 256
                                                              + 4096 * 64 * 128)
    # a sliding layer's step at t = 0: a row sees itself and the tail's 512
    maps = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 5120 * 64
    per_row = maps + 64 * 192 * 1024 + 64 * 1088 * 513 + 64 * 1024 * 513 + 64 * 1024 * 128 + 64 * 128 * 5120
    assert flops_dots3.swa_step_flops(model, 24, 513) == 2 * 24 * per_row
    moved = maps + 1024 * 64 * 320 + 64 * 128 * 5120 + (8 * 512 + 24 * 1) * 1088 + 2 * 24 * 5120
    assert flops_dots3.swa_step_bytes(model, 8, 24, 512, 1) == 2 * moved
    run = types.SimpleNamespace(model=model, extras={"batch_size": 8, "beam_size": 3, "caption_steps": 20})
    assert flops_dots3.swa_prefill_attention(run)["flops"] == 8 * 3 * flops_dots3.swa_prefill_flops(model, 4096)
    steps = flops_dots3.swa_step_attention(run)
    assert steps["flops"] == 3 * 20 * flops_dots3.swa_step_flops(model, 24, 513)      # the window is always full
    assert steps["bytes"] == 3 * sum(flops_dots3.swa_step_bytes(model, 8, 24, 512 - t, t + 1) for t in range(20))
    # the full layers: flops_dsa's count at 128 heads of 128 + 64, values of 128, two layers
    full = flops_dots3.full_prefill_attention(run)
    assert full["flops"] == 8 * 2 * 2 * (4096 * 512 * 128 * 256 + 6_292_480 * 128 * 320)
    assert full["bytes"] == 8 * 2 * 2 * (512 * 128 * 256 + 4096 * 576 + 4096 * 128 * 192 + 4096 * 128 * 128)
    # the full layers' steps: flops_dsa's count of a layer WITH an indexer (64 heads of 128 off a bottleneck of
    # 1024), twice; at step t a row sees 4097 + t positions and attends 2048 of them
    select = flops_dots3.full_step_select(run)
    per_row = lambda seen: (128 * 128 * 512 + 128 * 576 * 2048 + 128 * 512 * 2048 + 128 * 512 * 128   # noqa: E731
                            + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64 + 64 * 128 * seen)
    assert select["flops"] == 2 * sum(2 * 24 * per_row(4097 + t) for t in range(20))
    moved = lambda t: (512 * 128 * 256 + min(24 * 2048, 8 * 4096 + 24 * (t + 1)) * 576 + 24 * 128 * 192   # noqa: E731
                       + 24 * 128 * 128 + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
                       + (8 * 4096 + 24 * (t + 1)) * 128 + 24 * (1024 + 5120))
    assert select["bytes"] == 2 * sum(2 * moved(t) for t in range(20))
    assert select["bytes"] / 819e9 > select["flops"] / 197e12     # bound by what it reads, as the glm52 cell's
    # by operations, an image: a sliding layer 0.27 TFLOP, a full layer 0.65 (the issue's numbers)
    assert flops_dots3.swa_prefill_flops(model, 4096) / 1e12 == pytest.approx(0.269, abs=0.001)
    assert full["flops"] / 16 / 1e12 == pytest.approx(0.653, abs=0.001)
    # a step's window attention is bound by reading its maps, the prefills by operations
    peaks = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["TPU v5 lite"]
    assert steps["bytes"] / peaks["hbm_bytes_per_s"] > steps["flops"] / peaks["bf16_flops_per_s"]
    for work in (full, flops_dots3.swa_prefill_attention(run)):
        assert work["flops"] / peaks["bf16_flops_per_s"] > work["bytes"] / peaks["hbm_bytes_per_s"]


def test_every_new_metric_names_a_reader_and_a_scope_file_that_exist():
    import harness
    from reducers import trace_scope_ms

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW_METRICS):]] == NEW_METRICS      # at the list's end
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
    # the cell is on no list whose count reckons every layer a full layer of one width
    for name in ("lm_dsa_prefill_roofline_share", "lm_dsa_step_roofline_share", "lm_mla_step_roofline_share",
                 "lm_moe_experts_roofline_share"):
        assert CELL not in listed[name]["workloads"]


def test_the_scope_rules_tell_a_sliding_layer_from_a_full_one():
    """A sliding layer's scopes lie under ``decoder/lm/attn/window``: the
    mixer's and the phases' buckets go on reading them, the glm52 rule
    files (full layers) do not take them, and the new rule files take them
    and nothing of a full layer's but its gate."""
    import re

    from reducers import trace_scope_ms

    src = open(os.path.join(ROOT, "sat_tpu", "models", "dots3_note.py")).read()
    assert re.search(r'WINDOW_SEGMENT = "window"', src)
    # the segment goes behind decoder/lm/attn of the scope a call site names in full
    shared = open(os.path.join(ROOT, "sat_tpu", "models", "deepseek_v3.py")).read()
    assert 'ATTN_SCOPE = "decoder/lm/attn"' in shared and 'ATTN_SCOPE + "/" + self.segment' in shared
    first = lambda rules, name: next(b for b, rx in trace_scope_ms.load_rules(rules) if rx.search(name))  # noqa: E731
    pre, loop = "jit(f)/beam/prefill/while/body/", "jit(f)/beam/loop/while/body/"
    for part in ("q", "latent", "expand", "scores", "gate", "out"):
        name = pre + f"decoder/lm/attn/window/{part}/dot_general"
        assert first("lm_beam_search", name) == "mixer" and first("lm_beam_phases", name) == "prefill"
        assert first("lm_swa", name) == "window_prefill"
        assert first("lm_dsa_phases", name) == "other" and first("lm_mla_query", name) == "other"
        assert first("lm_mla_absorb", name) == "other"
    for part in ("q", "latent", "absorb", "scores", "gate", "out"):
        name = loop + f"decoder/lm/attn/window/{part}/dot_general"
        assert first("lm_swa", name) == "window_step" and first("lm_swa_phases", name) == "swa_step_attention"
        assert first("lm_dsa_phases", name) == "other" and first("lm_mla_absorb", name) == "other"
    assert first("lm_swa_phases", pre + "decoder/lm/attn/window/scores/flash_prefill") == "swa_prefill_attention"
    assert first("lm_swa_phases", pre + "decoder/lm/attn/window/expand/dot_general") == "swa_prefill_attention"
    assert first("lm_swa_phases", pre + "decoder/lm/attn/window/q/dot_general") == "other"
    assert first("lm_swa_phases", pre + "decoder/lm/attn/scores/flash_prefill") == "full_prefill_attention"
    assert first("lm_swa_phases", pre + "decoder/lm/attn/index/dot_general") == "other"
    for part in ("index", "select", "absorb", "scores"):
        assert first("lm_swa_phases", loop + f"decoder/lm/attn/{part}/dot_general") == "full_step_select"
    for part in ("q", "latent", "gate", "out"):
        assert first("lm_swa_phases", loop + f"decoder/lm/attn/{part}/dot_general") == "other"
    # PR 38's query bucket reads the full layers' prefill query and no sliding layer's
    assert first("lm_mla_query", pre + "decoder/lm/attn/q/dot_general") == "query"
    assert first("lm_mla_query", loop + "decoder/lm/attn/q/dot_general") == "other"
    assert first("lm_swa", pre + "decoder/lm/attn/scores/flash_prefill") == "other"
    assert first("lm_swa_gate", pre + "decoder/lm/attn/gate/logistic") == "gate"
    assert first("lm_swa_gate", loop + "decoder/lm/attn/window/gate/mul") == "gate"
    assert first("lm_swa_gate", loop + "decoder/lm/attn/out/dot_general") == "other"
    assert first("lm_mla_absorb", loop + "decoder/lm/attn/absorb/dot_general") == "absorb"     # a full layer's


def test_the_cell_is_on_the_lists_of_the_metrics_its_mix_reports():
    import harness

    cell = harness.Cell(CELL)
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s", "decode_captions_per_s"]
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) <= names
    glm52 = {m["name"] for m in harness.Cell("glm52-eval-beam3-b8").per_layer()}
    assert glm52 - names == {"lm_dsa_prefill_roofline_share", "lm_dsa_step_roofline_share", "lm_mla_prefill_device_ms",
                             "lm_mla_step_device_ms"} | HELD_TO_OTHER_CELLS
    assert names - glm52 == set(NEW_METRICS)
    assert cell.entry["chips"] == 1 and len(cell.entry["why"]) <= 200 and cell.entry == cell.bench["workloads"][-1]
    mix, glm = cell.mix, harness.Cell("glm52-eval-beam3-b8").mix
    # the glm52 cell's traffic letter for letter, but for the driver's name, the description and the limits
    differ = {k for k in set(mix) | set(glm) if mix.get(k) != glm.get(k)}
    assert differ <= {"driver", "what", "limits", "limits_readings", "rehearsal"}
    assert mix["driver"] == "decode_offline_swa"
    assert set(mix["limits"]) == {"score_gap", "score_gap_mean", "rank_gap", "route_agreement_min",
                                  "select_agreement_min"}
