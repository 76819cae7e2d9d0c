"""The command-a-plus configuration's pieces under benchmark/, tiny, on the
CPU: the rehearsal of its cell (toy widths but for a stream 1,024 wide, a
96-px image's 36 positions, a window of 9, 8 query / 2 key-value heads of
16, 4 of 8 experts held beside four shared); a lower precision and the
sabotaged programs (no window, rope in the full layer, a serial block, the
shared experts summed, RMS for LayerNorm, a token) each coming out not
correct; the parameter spec against the program's own tree and the file's
arithmetic; ``flops_cohere2`` against hand counts; the configuration file
against the catalog's rule; the new metric files against the readers and
scope files they name; BENCHMARK.json's lists against the mix."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

CELL = "command-a-plus-eval-beam3-b4"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "sat-command-a-plus.json")
NEW_METRICS = ["lm_gqa_qkv_device_ms", "lm_gqa_window_prefill_device_ms", "lm_gqa_window_step_device_ms",
               "lm_gqa_window_state_mb", "lm_gqa_held_pair_share", "lm_gqa_window_prefill_roofline_share",
               "lm_gqa_step_roofline_share", "lm_gqa_held_experts_roofline_share"]
# accepted metrics right for this cell whose lists the benchmark's own tests hold to other cells (test_glm52.py,
# test_dots3.py: to their cell alone; test_span_overlap.py: to four eval cells): where the reading is wanted this
# configuration brings a metric file of a new name on the same reader; a ``benchmark`` PR extends lists and
# asserts together
HELD_TO_OTHER_CELLS = {"lm_moe_held_pair_share", "lm_moe_held_experts_roofline_share", "lm_swa_prefill_device_ms",
                       "lm_swa_step_device_ms", "lm_swa_state_mb", "decode_device_empty_share",
                       "decode_empty_detok_ms", "decode_empty_data_wait_ms", "decode_empty_dispatch_ms"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LIMITS = {"score_gap", "score_gap_mean", "rank_gap", "route_agreement_min"}


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
    assert {"lm_moe_load_max_over_mean", "lm_state_mb", "lm_gqa_window_state_mb",
            "lm_gqa_held_pair_share"} <= set(last["per_layer_names"])
    notes = next(ln["notes"] for ln in lines if "notes" in ln)
    assert set(notes["control"]["fp8"]["fails"]) == {"score_gap", "score_gap_mean", "rank_gap", "route_agreement"}, notes
    assert notes["route_captions"] >= 8 and notes["moe_pairs_over"] == 0
    visible = sum(36 + t + 1 for t in range(20))
    assert notes["lm_swa_attended_share"] == pytest.approx(20 * 9 / visible)         # 9 of 37..56
    assert notes["prefill_fused_blocks_by_kind"] == [[0, 1], [0, 3]]                 # the lax blocks, on the CPU
    # the state: keys and values 32 wide each; three sliding layers' kept tail (8 of 36 positions) an image and
    # 20 a beam, the full layer's 36 an image and 20 a beam; the record of routes
    window = 3 * 2 * 32 * 2 * (4 * 8 + 12 * 20)
    full = 2 * 32 * 2 * (4 * 36 + 12 * 20)
    records = 12 * 20 * (4 * 3) * 4
    assert notes["lm_swa_state_mb"] == pytest.approx(window / 1e6)
    assert notes["lm_state_mb"] == pytest.approx((window + full + records) / 1e6)
    kept = os.path.join(BENCH_DIR, ".work", CELL)
    assert len(os.listdir(kept)) == 1 and "models0" not in os.listdir(os.path.join(kept, os.listdir(kept)[0]))


@pytest.mark.parametrize("sabotage,failed", [
    ("no_window", "score_gap_mean"), ("rope_in_full", "score_gap_mean"), ("serial_block", "score_gap_mean"),
    ("shared_sum", "score_gap_mean"), ("rms_norm", "score_gap"), ("token", "rank_gap"),
])
def test_a_broken_program_is_not_correct(sabotage, failed):
    """Sliding layers that attend all they see; the full layer's queries and
    keys turned; a feed-forward that reads a norm of the stream AFTER
    attention; the four shared experts summed; the mean left in by the
    norm; one served token altered."""
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
    assert got["mb"] == pytest.approx(3 * 2 * 32 * 2 * (4 * (36 if sabotage == "no_window" else 8) + 12 * 20) / 1e6)


def test_param_spec_equals_the_program_s_tree_and_the_file_s_arithmetic():
    """Names, shapes AND dtypes, at the rehearsal's widths and (shapes only,
    nothing is made) at the published ones; the configuration's
    ``parameters`` recomputed from the spec."""
    import jax

    from sat_tpu.train.step import create_train_state

    import harness
    from reference import params_cohere2

    for rehearsal in (True, False):
        cell = _cell(rehearsal)
        config = harness.program_config(cell, "/tmp/k", "/tmp/r", 1)
        shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes.params)
        program = {"params/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
                   for path, leaf in flat}
        spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype) in
                params_cohere2.param_spec(cell.model).items()}
        assert program == spec
    count = lambda *parts: sum(int(np.prod(shape)) for name, (shape, _d) in spec.items()  # noqa: E731
                               if "/decoder/" in name and all(p in name for p in parts))
    said = cell.config["parameters"]
    assert count("") == said["decoder"] == 4_735_393_792 and said["decoder_bytes_bfloat16"] == 2 * count("")
    assert count("/connector/") == said["connector"] == 2_101_248
    assert count("/02/self_attn/") == count("/03/self_attn/") == said["attention"] == 142_606_336
    assert count("/03/feed_forward/shared/") == said["shared_experts"] == 4 * said["one_expert"] == 201_326_592
    assert count("/03/feed_forward/gate") == said["router"] == 4096 * 128 and not count("expert_bias")
    assert count("/03/input_norm") == said["block_norm"] == 4096
    routed = sum(count(f"/03/feed_forward/{w}") for w in ("w1", "w3", "w2"))       # the shared leaves lie under shared/
    assert routed == said["routed_experts_held"] == 16 * said["one_expert"]
    assert count("/lm/layers/03/") == said["layer_as_held"] == 1_149_767_680
    assert count("/lm/layers/03/") - routed == said["layer_without_routed_experts"] == 344_461_312
    assert count("/lm/embed_tokens") == said["embedding_slice_is_head"] == 134_217_728 and not count("lm_head")
    whole = said["layer_without_routed_experts"] + 128 * said["one_expert"]
    assert whole == said["expert_layer_whole"] and 2 * whole > 13.5e9           # no chip holds one whole
    # the fall-back the issue names, should the chip refuse 16 experts: 8 held as one of sixteen chips
    assert count("") - 4 * 8 * said["one_expert"] == said["fall_back_8_experts_of_sixteen_chips"] == 3_124_781_056


def test_the_configuration_keeps_every_published_width():
    """The catalog's rule: every number of the catalog's ``config`` under
    the same key, but for the keys in ``reduced``, each with its published
    value beside it and within the guide's floors."""
    cfg = json.load(open(CONFIG_FILE))
    row = next(json.loads(ln) for ln in open(CATALOG) if json.loads(ln)["name"] == "command-a-plus-05-2026")
    assert cfg["source_url"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert all(cfg["published"][k] == row["config"][k] for k in cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in cfg["reduced"])
    kept = cfg["kept_layers"]
    assert kept == list(range(cfg["num_hidden_layers"])) and len(kept) - cfg["first_k_dense_replace"] >= 4
    assert [row["config"]["layer_types"][i] for i in kept] == cfg["kept_layer_types"] == cfg["model"]["layer_types"]
    assert cfg["kept_layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]          # one whole period, 3:1
    assert cfg["num_experts"] == cfg["model"]["experts_held"] >= 8
    assert cfg["model"]["num_experts"] == cfg["published"]["num_experts"] == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] and cfg["model"]["vocabulary_size"] == cfg["vocab_size"]
    m, src = cfg["model"], row["config"]
    same = ["hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
            "rope_theta", "logit_scale", "norm_topk_prob", "tie_word_embeddings"]
    assert all(m[k] == src[k] for k in same)
    assert m["moe_intermediate_size"] == src["intermediate_size"] == row["expert_width"] == 4096
    assert m["sliding_window_size"] == src["sliding_window"] == 4096
    assert m["n_shared_experts"] == src["num_shared_experts"] == 4
    assert m["norm_eps"] == src["layer_norm_eps"] and m["num_dense_layers"] == src["first_k_dense_replace"] == 0
    assert m["use_expert_bias"] is False and m["routed_scaling_factor"] == 1.0
    assert src["use_parallel_block"] is True and src["shared_expert_combination_strategy"] == "average"
    assert m["image_size"] == 1536 and (m["image_size"] // 16) ** 2 == 9216 > 2 * m["sliding_window_size"]
    assert "deployment" in cfg and len(cfg["assumed"]) >= 8 and "vision tower" in cfg["not_run"]


def test_flops_cohere2_against_hand_counts():
    import flops_cohere2
    import flops_dsa

    model = _cell(rehearsal=False).model
    # a sliding layer's prefill, one image: 4096 x 4097 / 2 + (9216 - 4096) x 4096 keys in the band
    assert flops_cohere2.band_keys(4096, 9216) == 4096 * 4097 // 2 + 5120 * 4096 == 29_362_176
    assert flops_cohere2.band_keys(4096, 100) == 5050
    assert flops_cohere2.window_prefill_flops(model, 9216) == 2 * 29_362_176 * 128 * 256
    # queries in and outputs out over 128 heads, keys and values once over 8
    assert flops_cohere2.window_prefill_bytes(model, 9216) == 2 * 9216 * 128 * (128 + 128 + 8 + 8)
    run = types.SimpleNamespace(model=model, extras={"batch_size": 4, "beam_size": 3, "caption_steps": 20,
                                                     "step_held_pairs": [240.0], "step_experts_visited": [600.0]})
    prefill = flops_cohere2.window_prefill_attention(run)
    assert prefill["flops"] == 4 * 3 * flops_cohere2.window_prefill_flops(model, 9216)
    assert prefill["flops"] / 4 / 1e12 == pytest.approx(5.77, abs=0.01)          # the issue's 5.8 TFLOP an image
    # a layer's step: W_q and W_o 4096 x 16,384, W_k and W_v 4096 x 1,024, scores and sum over what a row sees
    maps = 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert flops_cohere2.step_flops(model, 12, 4096) == 2 * 12 * (maps + 2 * 128 * 128 * 4096)
    assert flops_cohere2.step_bytes(model, 4, 12, 4095, 1) == 2 * (maps + 2 * (4 * 4095 + 12 * 1) * 1024 + 2 * 12 * 4096)
    steps = flops_cohere2.step_attention(run)
    assert steps["flops"] == sum(3 * flops_cohere2.step_flops(model, 12, 4096)          # the window is always full
                                 + flops_cohere2.step_flops(model, 12, 9217 + t) for t in range(20))
    assert steps["bytes"] == sum(3 * flops_cohere2.step_bytes(model, 4, 12, 4095 - t, t + 1)
                                 + flops_cohere2.step_bytes(model, 4, 12, 9216, t + 1) for t in range(20))
    peaks = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["TPU v5 lite"]
    # the steps' attention is bound by what it reads, the prefill's by operations
    assert steps["bytes"] / peaks["hbm_bytes_per_s"] > steps["flops"] / peaks["bf16_flops_per_s"]
    assert prefill["flops"] / peaks["bf16_flops_per_s"] > prefill["bytes"] / peaks["hbm_bytes_per_s"]
    # the held experts: flops_dsa's count at experts of 4096 x 4096: 100.7 MB of maps an expert visited
    held = flops_dsa.step_held_experts(run)
    assert held["flops"] == 2 * 3 * 4096 * 4096 * 240
    assert held["bytes"] == 2 * (3 * 600 * 4096 * 4096 + 2 * 240 * 4096)


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
    # the cell is on no list whose count assumes latent heads, an indexer or every expert's maps
    for name, m in listed.items():
        if name.startswith(("lm_mla_", "lm_dsa_", "lm_dots3_", "lm_attn_gate")) or name in (
                "lm_moe_experts_roofline_share", "lm_swa_prefill_roofline_share", "lm_swa_step_roofline_share"):
            assert CELL not in m["workloads"], name
    # and on none that the benchmark's own tests hold to other cells
    assert all(CELL not in listed[name]["workloads"] for name in HELD_TO_OTHER_CELLS)


def test_the_scope_rules_claim_the_program_s_scopes():
    """Every attention op lies under ``decoder/lm/attn`` (a sliding layer's
    in the segment ``window``), so the mixer's and the phases' buckets read
    them; the new rule files tell the windowed kernel, the steps' attention
    and the maps with their rope apart; the latent-attention rule files
    take none of them."""
    import re

    from reducers import trace_scope_ms

    src = open(os.path.join(ROOT, "sat_tpu", "models", "cohere2_moe.py")).read()
    assert re.search(r'f"decoder/lm/attn/\{segment\}\{part\}"', src) and '"window/"' in src
    for part in ("qkv", "rope", "scores", "out"):
        assert f'"{part}"' in src
    assert '"decoder/lm/norm"' in src and '"decoder/lm/residual"' in src
    first = lambda rules, name: next(b for b, rx in trace_scope_ms.load_rules(rules) if rx.search(name))  # noqa: E731
    pre, loop = "jit(f)/beam/prefill/while/body/", "jit(f)/beam/loop/while/body/"
    for segment in ("", "window/"):
        for part in ("qkv", "rope", "scores", "out"):
            for phase, where in ((pre, "prefill"), (loop, "step")):
                name = phase + f"decoder/lm/attn/{segment}{part}/dot_general"
                assert first("lm_beam_search", name) == "mixer" and first("lm_beam_phases", name) == where
                assert first("lm_gqa", name) == ("qkv" if part in ("qkv", "rope") else "other")
                assert first("lm_swa", name) == (f"window_{where}" if segment else "other")
                assert first("lm_gqa_phases", name) == (
                    "step_attention" if where == "step" else
                    {"window/scores": "window_prefill_attention", "scores": "full_prefill_attention"}.get(
                        segment + part, "other"))
                for rules in ("lm_mla_absorb", "lm_swa_gate"):
                    assert first(rules, name) == "other"
                # the glm52 rule file reads a full layer's attn/scores as latent attention's: this cell is on no
                # list of a metric that reads it; a sliding layer's segment keeps its ops out
                if segment or part != "scores":
                    assert first("lm_dsa_phases", name) == "other"
    # lm_mla_query's rule (the prefill's decoder/lm/attn/q...) would read the full layer's qkv as a query: this
    # cell is on no list of a metric that reads it
    assert first("lm_mla_query", pre + "decoder/lm/attn/window/qkv/dot_general") == "other"
    assert first("lm_gqa_phases", pre + "decoder/lm/attn/window/scores/flash_prefill") == "window_prefill_attention"
    assert first("lm_gqa", pre + "decoder/lm/norm/reduce") == "block" == first("lm_gqa", loop + "decoder/lm/residual/add")
    assert first("lm_beam_search", pre + "decoder/lm/norm/reduce") == "other"         # scoped: not unscoped time
    assert first("lm_dsa_phases", loop + "decoder/lm/moe/experts/gmm") == "step_held_experts"
    assert first("lm_beam_search", pre + "decoder/lm/moe/shared/dot_general") == "other"


def test_the_cell_is_on_the_lists_of_the_metrics_its_mix_reports():
    import harness

    cell = harness.Cell(CELL)
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s", "decode_captions_per_s"]
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) <= names
    dots3 = {m["name"] for m in harness.Cell("dots3-eval-beam3-b8").per_layer()}
    assert names - dots3 == set(NEW_METRICS)
    assert dots3 - names == {"lm_mla_absorb_device_ms", "lm_mla_query_device_ms", "lm_attn_gate_device_ms",
                             "lm_swa_prefill_roofline_share", "lm_swa_step_roofline_share",
                             "lm_dots3_full_prefill_roofline_share", "lm_dots3_full_step_roofline_share",
                             "lm_swa_prefill_device_ms", "lm_swa_step_device_ms", "lm_swa_state_mb"}
    assert cell.entry["chips"] == 1 and len(cell.entry["why"]) <= 200 and cell.entry == cell.bench["workloads"][-1]
    mix = cell.mix
    assert mix["driver"] == "decode_offline_gqa" and mix["program"]["batch_size"] == 4
    assert (mix["distinct_images"], mix["image_ids"], mix["calibration_images"], mix["warm_batches"],
            mix["sample_batches"], mix["sample_rows"]) == (128, 4096, 4, 3, 4, 4)
    assert set(mix["limits"]) == LIMITS == set(mix["rehearsal"]["limits"])
    config = next(c for c in cell.bench["configs"] if c["name"] == "sat-command-a-plus")
    assert config == cell.bench["configs"][-1] and config["reduced"] == cell.config["reduced"]
    assert config["source"] == cell.config["source_url"]
