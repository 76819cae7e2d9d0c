"""The GLM-5.2 configuration's pieces under benchmark/, tiny, on the CPU: the
rehearsal of its cell (toy widths, a 96-px image's 36 positions,
``index_topk`` 16, 4 of 8 experts held); a lower precision and the sabotaged
programs (no selection, the indexer's rope, a token, the selection bias, the
shared expert) each coming out not correct; the parameter spec against the
program's own tree and the file's arithmetic; ``flops_dsa`` against hand
counts; the configuration file against the catalog's rule; the new metric
files against the readers and scope files they name; BENCHMARK.json's lists
against the mix."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

CELL = "glm52-eval-beam3-b8"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "sat-glm-5.2.json")
NEW_METRICS = ["lm_dsa_index_device_ms", "lm_dsa_select_device_ms", "lm_dsa_selected_share",
               "lm_moe_held_pair_share", "lm_dsa_prefill_roofline_share", "lm_dsa_step_roofline_share",
               "lm_moe_held_experts_roofline_share"]
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
    assert {"lm_moe_load_max_over_mean", "lm_state_mb", "lm_dsa_selected_share",
            "lm_moe_held_pair_share"} <= set(last["per_layer_names"])
    notes = next(ln["notes"] for ln in lines if "notes" in ln)
    assert set(notes["control"]["fp8"]["fails"]) >= {"score_gap_mean", "select_agreement"}, notes
    assert notes["route_captions"] >= 8 and notes["select_agreement"] >= 0.95 and notes["moe_pairs_over"] == 0
    # 16 of 37..56 visible positions a step; 4 of 8 experts held under a fitted router
    assert notes["lm_dsa_selected_share"] == pytest.approx(20 * 16 / sum(36 + t + 1 for t in range(20)))
    assert 0.4 < notes["lm_moe_held_pair_share"] < 0.6
    # the state: latents of 3 layers and indexer keys of 2, per image over 36 positions and per beam over 20
    # steps; the record of routes (2 expert layers x 3) and of chosen positions (2 full layers x 16)
    per_image, per_beam = 36 * (3 * 64 + 2 * 48) * 2, 20 * (3 * 64 + 2 * 48) * 2 + 20 * (6 + 32) * 4
    assert notes["lm_state_mb"] == pytest.approx((4 * per_image + 12 * per_beam) / 1e6)
    kept = os.path.join(BENCH_DIR, ".work", CELL)
    assert len(os.listdir(kept)) == 1 and "models0" not in os.listdir(os.path.join(kept, os.listdir(kept)[0]))


@pytest.mark.parametrize("sabotage,failed", [
    ("no_select", "select_agreement"), ("no_index_rope", "select_agreement"), ("token", "rank_gap"),
    ("no_expert_bias", "route_agreement"), ("no_shared_expert", "score_gap_mean"),
])
def test_a_broken_program_is_not_correct(sabotage, failed):
    """A program that attends every visible position; an indexer that leaves
    its rotary part unturned; one served token altered; ``expert_bias``
    zeroed in the checkpoint the program loads; the shared expert's output
    zeroed."""
    code = (
        "import sys, json, types; sys.argv=['run.py']; import run, harness;"
        f"a=types.SimpleNamespace(workload={CELL!r}, seed=2 ** 31 + 7, seconds=3.0, trace=0, cpu_rehearsal=True, rates=None);"
        f"cell, facts, out = run.run_cell(a, sabotage={sabotage!r});"
        "print(json.dumps({'checks': {c['name']: [c['value'], c.get('limit')] for c in out.checks},"
        " 'share': out.notes['lm_dsa_selected_share']}))"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=BENCH_DIR, capture_output=True, text=True,
                          timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    value, limit = got["checks"][failed]
    assert (value > limit) if limit is not None else (value is False), got
    assert (got["share"] == 1.0) == (sabotage == "no_select")


def test_param_spec_equals_the_program_s_tree_and_the_file_s_arithmetic():
    """Names, shapes AND dtypes, at the rehearsal's widths and (shapes only,
    nothing is made) at the published ones; the configuration's
    ``parameters`` recomputed from the spec."""
    import jax

    from sat_tpu.train.step import create_train_state

    import harness
    from reference import params_glm52

    for rehearsal in (True, False):
        cell = _cell(rehearsal)
        config = harness.program_config(cell, "/tmp/k", "/tmp/r", 1)
        shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes.params)
        program = {"params/" + "/".join(str(p.key) for p in path): (tuple(leaf.shape), str(leaf.dtype))
                   for path, leaf in flat}
        spec = {name: (tuple(shape), dtype) for name, (shape, _kind, dtype) in
                params_glm52.param_spec(cell.model).items()}
        assert program == spec
    count = lambda *parts: sum(int(np.prod(shape)) for name, (shape, _d) in spec.items()  # noqa: E731
                               if "/decoder/" in name and all(p in name for p in parts))
    said = cell.config["parameters"]
    assert count("") == said["decoder"] == 3_884_668_928 and said["decoder_bytes_bfloat16"] == 2 * count("")
    assert count("/connector/") == said["connector"] and count("/lm/") == said["stack"] == 3_881_517_056
    assert count("/01/self_attn/") == said["attention_per_layer"] == 165_022_208
    assert count("/indexer/") == 2 * said["indexer"] == 2 * 9_371_904
    assert count("/lm/layers/00/") == said["dense_layer_with_indexer"] == 400_898_816
    assert count("/lm/layers/01/") == said["expert_layer_share"] == 808_336_128
    assert count("/lm/layers/04/") == said["expert_layer_share_with_indexer"] == 817_708_032
    assert count("/01/feed_forward/gate") + count("/01/feed_forward/expert_bias") == said["router"]
    assert count("/01/feed_forward/shared/") == said["shared_expert"] == said["one_expert"] == count("/01/feed_forward/w1") * 3 // 16
    assert count("/lm/embed_tokens") == count("/lm/lm_head") == said["embedding_slice"] == said["head_slice"]
    whole = said["expert_layer_share"] + (256 - 16) * said["one_expert"]
    assert whole == said["expert_layer_whole"] and 2 * whole > 16e9           # no chip holds one whole


def test_the_configuration_keeps_every_published_width():
    """The catalog's rule: every number of the catalog's ``config`` under
    the same key, but for the keys in ``reduced``, each with its published
    value beside it and within the guide's floors."""
    cfg = json.load(open(CONFIG_FILE))
    row = next(json.loads(ln) for ln in open(CATALOG) if json.loads(ln)["name"] == "GLM-5.2")
    assert cfg["source_url"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert all(cfg["published"][k] == row["config"][k] for k in cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in cfg["reduced"])
    kept = cfg["kept_layers"]
    assert kept == list(range(kept[0], kept[0] + cfg["num_hidden_layers"]))
    assert [row["config"]["indexer_types"][i] for i in kept] == cfg["kept_indexer_types"] == cfg["model"]["indexer_types"]
    assert [row["config"]["mlp_layer_types"][i] for i in kept] == cfg["kept_mlp_layer_types"]
    assert cfg["kept_mlp_layer_types"].count("dense") == cfg["model"]["num_dense_layers"] == 1
    assert cfg["kept_indexer_types"][1:] == ["shared", "shared", "shared", "full"]      # one whole period
    assert cfg["n_routed_experts"] == cfg["model"]["experts_held"] >= 8
    assert cfg["model"]["num_experts"] == cfg["published"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] and cfg["model"]["vocabulary_size"] == cfg["vocab_size"]
    m = cfg["model"]
    for ours, theirs in (("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"), ("num_attention_heads", "num_attention_heads"),
                         ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"), ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"), ("index_n_heads", "index_n_heads"),
                         ("index_head_dim", "index_head_dim"), ("index_topk", "index_topk"),
                         ("num_experts_per_tok", "num_experts_per_tok"), ("n_shared_experts", "n_shared_experts"),
                         ("routed_scaling_factor", "routed_scaling_factor"), ("norm_eps", "rms_norm_eps")):
        assert m[ours] == row["config"][theirs], ours
    assert m["rope_theta"] == row["config"]["rope_parameters"]["rope_theta"]
    assert "deployment" in cfg and len(cfg["assumed"]) >= 8 and "num_nextn_predict_layers" in cfg["not_run"]


def test_flops_dsa_against_hand_counts():
    import types

    import flops_dsa

    model = _cell(rehearsal=False).model
    # the prefill's attention, one image, one layer: 2048 x 2049 / 2 + 2048 x 2048 attended keys
    assert flops_dsa.attended_keys(model, 4096) == 2048 * 2049 // 2 + 2048 * 2048 == 6_292_480
    assert flops_dsa.attended_keys(model, 100) == 5050
    assert flops_dsa.prefill_attention_flops(model, 4096) == 2 * (4096 * 512 * 64 * 448 + 6_292_480 * 64 * 512)
    assert flops_dsa.prefill_attention_bytes(model, 4096) == 2 * (512 * 64 * 448 + 4096 * 576 + 4096 * 64 * 256 * 2)
    # a step's row at t = 0 sees 4097 positions and attends 2048
    shared = 64 * 192 * 512 + 64 * 576 * 2048 + 64 * 512 * 2048 + 64 * 512 * 256
    assert flops_dsa.step_select_flops(model, 24, 4097, False) == 2 * 24 * shared
    indexer = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32 + 32 * 128 * 4097
    assert flops_dsa.step_select_flops(model, 24, 4097, True) == 2 * 24 * (shared + indexer)
    # 24 rows x 2048 chosen = 49152 latents are more than the 8 images' 32768 + 24: the prefix once per image
    moved = 512 * 64 * 448 + (8 * 4096 + 24) * 576 + 24 * 64 * 256 * 2
    assert flops_dsa.step_select_bytes(model, 8, 24, 4096, 1, False) == 2 * moved
    more = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32 + (8 * 4096 + 24) * 128 + 24 * (2048 + 6144)
    assert flops_dsa.step_select_bytes(model, 8, 24, 4096, 1, True) == 2 * (moved + more)
    run = types.SimpleNamespace(model=model, extras={"batch_size": 8, "beam_size": 3, "caption_steps": 20,
                                                     "step_held_pairs": [900.0, 1000.0, 1100.0],
                                                     "step_experts_visited": [600.0, 640.0, 700.0]})
    assert flops_dsa.prefill_attention(run)["flops"] == 8 * 5 * flops_dsa.prefill_attention_flops(model, 4096)
    steps = flops_dsa.step_select(run)
    assert steps["flops"] == sum(2 * flops_dsa.step_select_flops(model, 24, 4097 + t, True)
                                 + 3 * flops_dsa.step_select_flops(model, 24, 4097 + t, False) for t in range(20))
    held = flops_dsa.step_held_experts(run)
    assert held["flops"] == 2 * 3 * 6144 * 2048 * 1000 and held["bytes"] == 2 * (3 * 640 * 6144 * 2048 + 2 * 1000 * 6144)
    # a step's held experts are bound by reading their maps, its attention by reading W_kvb and the latents
    peaks = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["TPU v5 lite"]
    for work in (held, steps):
        assert work["bytes"] / peaks["hbm_bytes_per_s"] > work["flops"] / peaks["bf16_flops_per_s"]
    prefill = flops_dsa.prefill_attention(run)
    assert prefill["flops"] / peaks["bf16_flops_per_s"] > prefill["bytes"] / peaks["hbm_bytes_per_s"]


def test_every_new_metric_names_a_reader_and_a_scope_file_that_exist():
    import harness
    from reducers import trace_scope_ms

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL], name
        spec = json.load(open(harness.metric_file(name)))
        assert all(spec[k] == listed[name][k] for k in ("unit", "moves", "layer", "source"))
        assert os.path.exists(os.path.join(BENCH_DIR, "reducers", spec["reducer"] + ".py"))
        rules = spec["args"].get("rules")
        if rules is not None:
            buckets = [b for b, _ in trace_scope_ms.load_rules(rules)]
            assert spec["args"]["pick"] in buckets
        if "counts" in spec["args"]:
            module, _, fn = spec["args"]["counts"].rpartition(".")
            assert callable(getattr(__import__(module), fn))
        if "roofline" in name:
            assert listed[name]["unit"] == "%" and listed[name]["layer"] == "kernels"
    # the cell is on no list whose count functions reckon every expert's maps or every visible latent
    for name in ("lm_moe_experts_roofline_share", "lm_mla_step_roofline_share"):
        assert CELL not in listed[name]["workloads"]


def test_the_scope_rules_claim_the_program_s_scopes():
    """Every ``jax.named_scope`` of the decoder module falls in a bucket of
    the rules the new metrics read, and in the bucket meant."""
    import re

    from reducers import trace_scope_ms

    src = open(os.path.join(ROOT, "sat_tpu", "models", "glm_moe_dsa.py")).read()
    scopes = set(re.findall(r'named_scope\("(decoder/lm/[a-z_/]+)"\)', src))
    assert {"decoder/lm/attn/index", "decoder/lm/attn/select", "decoder/lm/attn/scores",
            "decoder/lm/attn/expand", "decoder/lm/attn/absorb"} <= scopes
    by_layer = trace_scope_ms.load_rules("lm_dsa")
    phases = trace_scope_ms.load_rules("lm_dsa_phases")
    first = lambda rules, name: next(b for b, rx in rules if rx.search(name))  # noqa: E731
    assert first(by_layer, "jit(f)/beam/loop/while/body/decoder/lm/attn/index/dot") == "index"
    assert first(by_layer, "jit(f)/beam/prefill/decoder/lm/attn/select/while") == "select"
    assert first(phases, "jit(f)/beam/prefill/while/body/decoder/lm/attn/scores/exp") == "prefill_attention"
    assert first(phases, "jit(f)/beam/prefill/while/body/decoder/lm/attn/index/dot") == "other"
    assert first(phases, "jit(f)/beam/loop/while/body/decoder/lm/attn/select/top_k") == "step_select"
    assert first(phases, "jit(f)/beam/loop/while/body/decoder/lm/moe/experts/gmm") == "step_held_experts"
    assert first(phases, "jit(f)/beam/prefill/while/body/decoder/lm/moe/experts/gmm") == "other"


def test_the_cell_is_on_the_lists_of_the_metrics_its_mix_reports():
    import harness

    cell = harness.Cell(CELL)
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s", "decode_captions_per_s"]
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) <= names
    kanana2 = {m["name"] for m in harness.Cell("kanana2-eval-beam3-b256").per_layer()}
    assert kanana2 - names == {"lm_moe_experts_roofline_share", "lm_mla_step_roofline_share"}
    assert cell.entry["chips"] == 1 and len(cell.entry["why"]) <= 200
    mix = cell.mix
    assert (mix["program"]["batch_size"], mix["distinct_images"], mix["image_ids"]) == (8, 256, 4096)
    assert (mix["warm_batches"], mix["sample_batches"], mix["sample_rows"], mix["trace_seconds"]) == (4, 4, 8, 4.0)
    assert set(mix["limits"]) == {"score_gap", "score_gap_mean", "rank_gap", "route_agreement_min",
                                  "select_agreement_min"}


def test_select_agreement_reads_the_share_of_the_program_s_positions_the_reference_holds():
    from drivers.decode_offline_dsa import select_agreement

    selections = np.zeros((1, 2, 3, 10), bool)            # [full layers, captions, steps, positions]
    selections[..., :4] = True
    chosen = np.tile(np.array([0, 1, 2, 3]), (2, 3, 1, 1))  # [captions, steps, full layers, k]
    assert select_agreement(chosen, selections) == 1.0
    chosen[0, 0, 0] = [0, 1, 8, 9]
    assert select_agreement(chosen, selections) == pytest.approx(22 / 24)
    chosen[1, 2, 0] = [0, -1, -1, -1]                      # fewer visible than k: not counted
    assert select_agreement(chosen, selections) == pytest.approx(19 / 21)


def test_a_loop_s_bucket_is_timed_by_the_passes_the_trace_holds(monkeypatch):
    """``roofline_share_per_pass``: 45 passes of a step body in the trace
    (an instruction of a loop nested in it runs 32 times a pass, one the
    trace cut runs 44), 20 passes a run: the bucket's seconds / 45 x 20,
    whatever the number of runs the module events give."""
    import types

    import harness
    from reducers import roofline_share_per_pass as per_pass
    from sat_tpu.telemetry import xla

    rows = [["%fusion.1", "f32[8]", False, "jit(f)/beam/loop/while/body/decoder/lm/moe/experts/gmm", False],
            ["%fusion.2", "f32[8]", False, "jit(f)/beam/loop/while/body/decoder/lm/moe/experts/mul", False],
            ["%fusion.3", "f32[8]", False, "jit(f)/beam/loop/while/body/decoder/lm/moe/experts/while/body/add", False],
            ["%fusion.4", "f32[8]", False, "jit(f)/beam/prefill/while/body/decoder/lm/moe/experts/gmm", False],
            ["%while.5", "(f32[8])", True, "jit(f)/beam/loop/while", False]]
    monkeypatch.setattr(xla, "entries", lambda: {"decode/beam_search": {
        "op_scopes": {"columns": ["name", "shape", "container", "op_name", "inherited"], "rows": rows}}})
    line = lambda name: f"{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop"  # noqa: E731
    trace = {"op_totals": {line("%fusion.1"): 0.090, line("%fusion.2"): 0.044, line("%fusion.3"): 0.0144,
                           line("%fusion.4"): 5.0, "%while.5 = (f32[8]{0}) while(%t)": 9.0},
             "op_counts": {line("%fusion.1"): 45, line("%fusion.2"): 44, line("%fusion.3"): 45 * 32,
                           line("%fusion.4"): 24, "%while.5 = (f32[8]{0}) while(%t)": 3},
             "modules": {"jit_beam_search": [1.4, 1.4, 1.1]}}
    cell = harness.Cell(CELL)
    run = harness.RunData(cell, (0, 1), {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    run.trace = trace
    run.extras.update(caption_steps=20, step_held_pairs=[1000.0], step_experts_visited=[640.0])
    ops = per_pass.bucket_ops(run, "decode/beam_search", "lm_dsa_phases", "step_held_experts")
    assert sorted(c for _, c in ops) == [44, 45, 1440] and per_pass.passes(ops) == 44.5
    spec = json.load(open(harness.metric_file("lm_moe_held_experts_roofline_share")))
    seconds = (0.090 + 0.044 + 0.0144) / 44.5 * 20
    import flops_dsa

    work = flops_dsa.step_held_experts(types.SimpleNamespace(model=run.model, extras=run.extras))
    want = 100.0 * max(work["flops"] / 197e12, work["bytes"] / 819e9) / seconds
    assert per_pass.read(run, **spec["args"]) == pytest.approx(want)
    run.trace = {k: v for k, v in trace.items() if k != "op_counts"}       # a trace from before the counts
    assert per_pass.read(run, **spec["args"]) is None
    run.trace = None
    assert per_pass.read(run, **spec["args"]) is None
