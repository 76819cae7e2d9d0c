"""Device-side diagnostics tests: in-graph model-health taps
(telemetry/device.py), the off-is-bitwise-identical guarantee, the
doubly-stochastic identity, the no-hidden-sync lint, the provenance
stamp, and the end-to-end ``--diag_level full`` artifact chain
(docs/OBSERVABILITY.md)."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sat_tpu import telemetry
from sat_tpu.telemetry import device as tdev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# tap math vs numpy references
# ---------------------------------------------------------------------------


def test_global_l2_matches_numpy():
    rng = np.random.default_rng(0)
    tree = {
        "a": rng.normal(size=(3, 4)).astype(np.float32),
        "b": {"c": rng.normal(size=(7,)).astype(np.float32)},
    }
    want = np.sqrt(
        np.sum(tree["a"] ** 2) + np.sum(tree["b"]["c"] ** 2)
    )
    got = tdev._l2(jax.tree.map(jnp.asarray, tree))
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(tdev._l2({})) == 0.0


def test_l2_accumulates_in_fp32_for_low_precision_leaves():
    # 4096 bf16 ones: naive bf16 accumulation saturates badly; the fp32
    # upcast keeps the norm exact (= 64)
    tree = {"w": jnp.ones((4096,), jnp.bfloat16)}
    assert float(tdev._l2(tree)) == pytest.approx(64.0, rel=1e-6)


def test_nonfinite_count_matches_numpy():
    tree = {
        "a": jnp.asarray([1.0, np.nan, np.inf, -np.inf]),
        "b": jnp.asarray([[0.0, 2.0], [np.nan, 3.0]]),
    }
    assert float(tdev._nonfinite_count(tree)) == 4.0
    assert float(tdev._nonfinite_count({})) == 0.0


def test_attention_entropy_uniform_and_onehot():
    B, T, N = 2, 3, 8
    uniform = jnp.full((B, T, N), 1.0 / N)
    masks = jnp.ones((B, T))
    assert float(tdev.attention_entropy(uniform, masks)) == pytest.approx(
        np.log(N), rel=1e-5
    )
    onehot = jnp.zeros((B, T, N)).at[..., 0].set(1.0)
    assert float(tdev.attention_entropy(onehot, masks)) == pytest.approx(
        0.0, abs=1e-6
    )


def test_attention_entropy_respects_masks():
    # row 0: uniform (entropy ln N); row 1: one-hot (entropy 0) but
    # masked OUT — the masked mean must see only row 0
    N = 4
    alphas = jnp.stack(
        [jnp.full((N,), 1.0 / N), jnp.zeros((N,)).at[0].set(1.0)]
    )[None]                                     # [1,2,N]
    masks = jnp.asarray([[1.0, 0.0]])
    assert float(tdev.attention_entropy(alphas, masks)) == pytest.approx(
        np.log(N), rel=1e-5
    )


def test_alpha_coverage_deviation_hand_computed():
    # B=1, T=2, N=2; masks all-on.  coverage_i = sum_t alpha_ti:
    # ctx0 -> 0.7+0.2 = 0.9, ctx1 -> 0.3+0.8 = 1.1
    # dev = mean((1-0.9)^2, (1-1.1)^2) = mean(0.01, 0.01) = 0.01
    alphas = jnp.asarray([[[0.7, 0.3], [0.2, 0.8]]])
    masks = jnp.ones((1, 2))
    assert float(
        tdev.alpha_coverage_deviation(alphas, masks)
    ) == pytest.approx(0.01, rel=1e-5)
    # masking out word 1 changes coverage to (0.7, 0.3):
    # dev = mean(0.09, 0.49) = 0.29
    masks = jnp.asarray([[1.0, 0.0]])
    assert float(
        tdev.alpha_coverage_deviation(alphas, masks)
    ) == pytest.approx(0.29, rel=1e-5)


def test_loss_taps_levels_and_values():
    B, T, N, V = 2, 3, 4, 7
    rng = np.random.default_rng(1)
    alphas = jax.nn.softmax(
        jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    )
    masks = jnp.ones((B, T))
    logits = jnp.asarray(rng.normal(size=(B, T, V)).astype(np.float32))
    assert tdev.loss_taps("off", alphas=alphas, masks=masks, logits=logits) == {}
    taps = tdev.loss_taps("basic", alphas=alphas, masks=masks, logits=logits)
    assert set(taps) == {
        "diag/attn_entropy",
        "diag/attn_entropy_frac",
        "diag/alpha_coverage_dev",
        "diag/logit_max",
    }
    assert float(taps["diag/logit_max"]) == pytest.approx(
        float(np.max(np.abs(np.asarray(logits)))), rel=1e-6
    )
    # entropy_frac normalizes by the uniform bound ln N
    assert float(taps["diag/attn_entropy_frac"]) == pytest.approx(
        float(taps["diag/attn_entropy"]) / np.log(N), rel=1e-5
    )
    assert 0.0 < float(taps["diag/attn_entropy_frac"]) <= 1.0


def test_grad_taps_levels_groups_and_ratio():
    rng = np.random.default_rng(2)

    def tree():
        return {
            "decoder": {
                "lstm": {"w": jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32))},
                "attend": {"w": jnp.asarray(rng.normal(size=(3,)).astype(np.float32))},
            }
        }

    grads, updates, params = tree(), tree(), tree()
    assert tdev.grad_taps("off", grads=grads, updates=updates, params=params) == {}
    basic = tdev.grad_taps("basic", grads=grads, updates=updates, params=params)
    assert set(basic) == {
        "diag/param_norm",
        "diag/update_norm",
        "diag/update_ratio",
    }
    assert float(basic["diag/update_ratio"]) == pytest.approx(
        float(basic["diag/update_norm"]) / float(basic["diag/param_norm"]),
        rel=1e-5,
    )
    full = tdev.grad_taps("full", grads=grads, updates=updates, params=params)
    assert set(basic) < set(full)
    assert full.keys() >= {
        "diag/grad_nonfinite",
        "diag/grad_norm/decoder.lstm",
        "diag/update_norm/decoder.attend",
        "diag/param_norm/decoder.lstm",
    }
    # per-group norm is the norm of just that subtree
    assert float(full["diag/grad_norm/decoder.lstm"]) == pytest.approx(
        float(np.sqrt(np.sum(np.asarray(grads["decoder"]["lstm"]["w"]) ** 2))),
        rel=1e-5,
    )
    assert float(full["diag/grad_nonfinite"]) == 0.0


# ---------------------------------------------------------------------------
# in-step semantics: off is bitwise-identical, coverage tap matches the
# doubly-stochastic loss term
# ---------------------------------------------------------------------------


def _tiny_config(**kw):
    from sat_tpu.config import Config

    return Config(
        phase="train",
        batch_size=4,
        image_size=32,
        dim_embedding=16,
        num_lstm_units=16,
        dim_initialize_layer=16,
        dim_attend_layer=16,
        dim_decode_layer=32,
        vocabulary_size=50,
        compute_dtype="float32",
        **kw,
    )


def _tiny_batch(config, seed=0):
    rng = np.random.default_rng(seed)
    B, T = config.batch_size, config.max_caption_length
    return {
        "images": jnp.asarray(
            rng.integers(0, 255, (B, config.image_size, config.image_size, 3),
                         np.uint8)
        ),
        "word_idxs": jnp.asarray(
            rng.integers(0, config.vocabulary_size, (B, T), np.int32)
        ),
        "masks": jnp.asarray(
            (np.arange(T)[None, :] < rng.integers(3, T, (B, 1))).astype(
                np.float32
            )
        ),
    }


@pytest.fixture(scope="module")
def diag_steps():
    """Two steps of the tiny model under every diag level, same seeds."""
    from sat_tpu.train.step import create_train_state, make_jit_train_step

    out = {}
    for level in ("off", "basic", "full"):
        config = _tiny_config(diag_level=level)
        step_fn = make_jit_train_step(config)
        state = create_train_state(jax.random.PRNGKey(0), config)
        metrics = None
        for i in range(2):
            state, metrics = step_fn(
                state, _tiny_batch(config, seed=i),
                jax.random.key(7, impl=config.rng_impl),
            )
        out[level] = (config, state, jax.device_get(metrics))
    return out


def test_diag_off_params_bitwise_identical_to_full(diag_steps):
    """The taps must be observation-only: enabling them cannot perturb
    training, down to the last bit."""
    _, state_off, _ = diag_steps["off"]
    _, state_full, _ = diag_steps["full"]
    off_leaves = jax.tree_util.tree_leaves(jax.device_get(state_off.params))
    full_leaves = jax.tree_util.tree_leaves(jax.device_get(state_full.params))
    assert len(off_leaves) == len(full_leaves)
    for a, b in zip(off_leaves, full_leaves):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_diag_metric_sets_nest_by_level(diag_steps):
    _, _, m_off = diag_steps["off"]
    _, _, m_basic = diag_steps["basic"]
    _, _, m_full = diag_steps["full"]
    assert not any(k.startswith("diag/") for k in m_off)
    basic_diag = {k for k in m_basic if k.startswith("diag/")}
    full_diag = {k for k in m_full if k.startswith("diag/")}
    assert basic_diag == {
        "diag/attn_entropy",
        "diag/attn_entropy_frac",
        "diag/alpha_coverage_dev",
        "diag/logit_max",
        "diag/param_norm",
        "diag/update_norm",
        "diag/update_ratio",
    }
    assert basic_diag < full_diag
    # full adds the per-layer-group split over the decoder blocks
    groups = {"word_embedding", "lstm", "initialize", "attend", "decode"}
    for g in groups:
        assert f"diag/grad_norm/decoder.{g}" in full_diag
    # non-diag metrics are level-invariant
    assert {k for k in m_off} == {
        k for k in m_full if not k.startswith("diag/")
    }
    for k, v in m_full.items():
        assert np.isfinite(v), f"{k} not finite"


def test_alpha_coverage_tap_matches_doubly_stochastic_loss(diag_steps):
    """attention_loss = factor * 0.5 * mean((1-Σα)²) — the tap is the
    unscaled penalty, so the identity ties it to the paper's eq. 14."""
    config, _, m = diag_steps["basic"]
    want = config.attention_loss_factor * 0.5 * m["diag/alpha_coverage_dev"]
    assert float(m["attention_loss"]) == pytest.approx(float(want), rel=1e-4)


# ---------------------------------------------------------------------------
# no-hidden-sync lint (static)
# ---------------------------------------------------------------------------

_SYNC_RE = re.compile(
    r"block_until_ready|\.item\(|(?<![\w.])float\(|np\.asarray\("
)


def _sync_lint_targets():
    """runtime.py plus every module of the serving AND resilience
    subsystems — the serve hot path (batcher dispatch chain, engine
    drain) carries the same zero-hidden-syncs contract as the
    train/decode loops, and the resilience observers (watchdog thread,
    sentinel, fault plan) run INSIDE those loops so a hidden sync there
    is a hidden sync in the loop.  ``data`` rides the same contract: the
    prefetch producers and the integrity verifier run host-side work
    that must never touch a device value."""
    targets = [os.path.join(REPO, "sat_tpu", "runtime.py")]
    # bulk rides the serve drain discipline: its decode loop drains the
    # slot-pool done flags whole-array, so it lints like serve does;
    # lifecycle's loader syncs once at candidate-staging time (declared)
    # and its controller/reloader threads run beside the serve loop
    for sub in ("serve", "resilience", "data", "bulk", "lifecycle"):
        sub_dir = os.path.join(REPO, "sat_tpu", sub)
        targets.extend(
            os.path.join(sub_dir, f)
            for f in sorted(os.listdir(sub_dir))
            if f.endswith(".py")
        )
    # the observability modules added by ISSUE 9 run on the serve request
    # path (tracectx, promtext) or inside loop-adjacent threads (slo,
    # profwin), so they carry the same contract — joined by ISSUE 10's
    # fleet plane and black box, which tick at the train-loop log
    # boundary; the rest of telemetry/ is exempt (exporters' attention
    # dump is an offline boundary)
    # quality.py and exemplar.py (ISSUE 19) run on the serve detok
    # thread per request — the quality plane's zero-new-syncs claim is
    # exactly this lint
    for mod in ("tracectx.py", "promtext.py", "slo.py", "profwin.py",
                "fleet.py", "blackbox.py", "quality.py", "exemplar.py"):
        targets.append(os.path.join(REPO, "sat_tpu", "telemetry", mod))
    # the encoder-quantization pass runs at serve load time inside the
    # engine boot path: its one-time calibration host syncs must be
    # declared, and nothing else in it may sync (the quantized encode is
    # AOT-compiled onto the same async dispatch chain as the fp32 one)
    targets.append(os.path.join(REPO, "sat_tpu", "nn", "quant.py"))
    return targets


def test_runtime_sync_sites_are_annotated():
    """Every host-sync construct in runtime.py and sat_tpu/serve/ must
    carry a `# sync-ok` marker naming its boundary — new unmarked syncs
    fail this lint, which is the guard behind the zero-extra-syncs claim
    of the diag taps and the serve path's one-drain-per-batch design."""
    bad = []
    for path in _sync_lint_targets():
        rel = os.path.relpath(path, REPO)
        for i, line in enumerate(open(path), 1):
            code = line.split("#", 1)[0]
            if _SYNC_RE.search(code) and "sync-ok" not in line:
                bad.append(f"{rel}:{i}: {line.strip()}")
    assert not bad, "unannotated host syncs:\n" + "\n".join(bad)


def test_device_tap_modules_never_sync():
    """device.py/xla.py build graph values and host reports; neither may
    force a transfer of its own."""
    for mod in ("device.py", "xla.py"):
        src = open(os.path.join(REPO, "sat_tpu", "telemetry", mod)).read()
        for needle in ("block_until_ready", ".item(", "device_get("):
            assert needle not in src, f"telemetry/{mod} contains {needle}"


def test_telemetry_core_is_jax_free():
    """The host-side telemetry core must import (and run) without jax —
    the jax-free parents (--supervise, the router) and the lint above
    both rely on this split."""
    code = (
        "import sys\n"
        "assert 'jax' not in sys.modules\n"
        "from sat_tpu import telemetry\n"
        "from sat_tpu.telemetry import exporters, heartbeat, spans\n"
        "from sat_tpu.telemetry import blackbox, fleet, profwin, promtext, slo, tracectx\n"
        "from sat_tpu.telemetry import exemplar, quality\n"
        "stamp = telemetry.bench_stamp()\n"
        "assert 'jax' not in sys.modules, 'telemetry core pulled in jax'\n"
        "assert 'platform' not in stamp['device']\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def test_fleet_router_is_jax_free():
    """The serving fleet's control plane (router + replica manager, and
    the lazy serve package itself) must import without jax: like the
    --supervise parent, the router exists to outlive replicas whose
    device runtime wedges, so it may never load the device stack.  Only
    touching an engine-side symbol pulls jax (PEP 562 laziness)."""
    code = (
        "import sys\n"
        "assert 'jax' not in sys.modules\n"
        "import sat_tpu.serve\n"
        "from sat_tpu.serve import replica, router, scheduler, tenants\n"
        "router.replica_weight(True, False, 0.25)\n"
        "replica.parse_endpoints('127.0.0.1:8710,127.0.0.1:8711')\n"
        # the multi-tenant plane (registry + DRR scheduler) rides the
        # router process too — parse, admit, and schedule without jax
        "reg = tenants.TenantRegistry.parse('a:4:10,b:1')\n"
        "assert reg.multi and reg.try_admit('a')\n"
        "drr = scheduler.DeficitRoundRobin(maxsize=2, weights=reg.weights())\n"
        "class _I:\n"
        "    tenant = 'b'\n"
        "drr.put_nowait(_I())\n"
        "assert drr.get_nowait().tenant == 'b'\n"
        "assert 'jax' not in sys.modules, 'router/replica/tenants pulled in jax'\n"
        "sat_tpu.serve.Rejected\n"
        "assert 'jax' in sys.modules, 'lazy engine-side export broken'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr


def test_lifecycle_control_plane_is_jax_free():
    """The model-lifecycle control plane (canary hash, reloader poll,
    controller state machine) must import and run without jax: the
    router forwards /reload-/promote-/rollback without owning a device
    stack, and the reloader/ledger logic unit-tests on jax-free hosts.
    Only the loader touches jax, and only inside load_candidate."""
    code = (
        "import sys\n"
        "assert 'jax' not in sys.modules\n"
        "import sat_tpu.lifecycle\n"
        "from sat_tpu.lifecycle import canary, controller, loader, reloader\n"
        "assert canary.assign_slot('req-1', 0.5) in ('incumbent', 'canary')\n"
        "assert canary.caption_divergence('a b', 'a b') == 0.0\n"
        "controller.STATE_CODES['CANARY']\n"
        "assert 'jax' not in sys.modules, 'lifecycle control plane pulled in jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr


def test_bulk_control_plane_is_jax_free():
    """The bulk subsystem's control plane (corpus walk, shard plan,
    manifest, output writer — everything resume touches before deciding
    there is work) must import and run without jax: a resume that finds
    all shards complete exits without ever booting the device runtime,
    and the --supervise parent may import the package for diagnostics."""
    code = (
        "import sys\n"
        "assert 'jax' not in sys.modules\n"
        "import sat_tpu.bulk\n"
        "from sat_tpu.bulk import corpus, manifest, runner, writer\n"
        "manifest.corpus_fingerprint(['a.jpg'], 4, 32)\n"
        "corpus.plan_shards(['a.jpg', 'b.jpg'], 1)\n"
        "writer.shard_filename(3)\n"
        "assert 'jax' not in sys.modules, 'bulk control plane pulled in jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# provenance stamp of a report (chaos_campaign.py writes it on every row)
# ---------------------------------------------------------------------------


def test_bench_stamp_schema_and_git_sha():
    stamp = telemetry.bench_stamp()
    assert stamp["schema_version"] == telemetry.SCHEMA_VERSION == 1
    assert stamp["run_id"] == telemetry.run_id()
    assert stamp["stamp_unix"] > 0
    # this test runs inside the repo, so the sha must resolve
    assert re.fullmatch(r"[0-9a-f]{12}", stamp["git_sha"])
    dev = stamp["device"]
    assert dev["host"] and dev["machine"] and dev["python"]
    # jax is imported in this process, so the device facts are present
    assert dev["platform"] == "cpu"
    assert dev["device_count"] >= 1


# ---------------------------------------------------------------------------
# end-to-end: full-diag train + attention-introspection eval
# ---------------------------------------------------------------------------

SMALL_MODEL = dict(
    image_size=32,
    dim_embedding=16,
    num_lstm_units=16,
    dim_initialize_layer=16,
    dim_attend_layer=16,
    dim_decode_layer=32,
    compute_dtype="float32",
    save_period=3,
    log_every=2,
    num_epochs=1,
    num_data_workers=2,
)


@pytest.fixture(scope="module")
def diag_run(coco_fixture, tmp_path_factory):
    """One full-diag telemetry train run + attention-mapped eval, shared
    by the artifact assertions below."""
    from sat_tpu import runtime

    tmp = tmp_path_factory.mktemp("diag_run")
    config = coco_fixture["config"].replace(
        **SMALL_MODEL,
        save_dir=str(tmp / "models"),
        summary_dir=str(tmp / "summary"),
        telemetry=True,
        heartbeat_interval=0.1,
        diag_level="full",
    )
    state = runtime.train(config)
    telemetry.disable()
    cfg_eval = config.replace(phase="eval", save_attention_maps=True)
    runtime.evaluate(cfg_eval, state=state)
    telemetry.disable()
    return config, cfg_eval, state


def test_e2e_diag_gauges_ride_log_boundaries(diag_run):
    config, _, _ = diag_run
    path = os.path.join(config.summary_dir, "telemetry", "telemetry.jsonl")
    rows = [json.loads(l) for l in open(path)]
    assert [r["step"] for r in rows] == [2, 4, 6]    # log_every=2, 6 steps
    for r in rows:
        diag = {k: v for k, v in r["gauges"].items() if k.startswith("diag/")}
        assert {
            "diag/attn_entropy",
            "diag/alpha_coverage_dev",
            "diag/param_norm",
            "diag/grad_nonfinite",
            "diag/grad_norm/decoder.lstm",
        } <= set(diag)
        for k, v in diag.items():
            assert np.isfinite(v), f"step {r['step']}: {k} not finite"
        assert r["gauges"]["diag/grad_nonfinite"] == 0


def test_e2e_metrics_jsonl_carries_diag_columns(diag_run):
    config, _, _ = diag_run
    rows = [
        json.loads(l)
        for l in open(os.path.join(config.summary_dir, "metrics.jsonl"))
    ]
    # log_every=2 over 6 steps -> rows at the 3 log boundaries
    assert [r["step"] for r in rows] == [2, 4, 6]
    for r in rows:
        assert 0.0 < r["diag/attn_entropy_frac"] <= 1.0
        assert r["diag/alpha_coverage_dev"] >= 0.0


def test_e2e_compile_report_schema(diag_run):
    config, _, _ = diag_run
    path = os.path.join(config.summary_dir, "telemetry", "compile_report.json")
    report = json.load(open(path))
    assert report["schema_version"] == telemetry.SCHEMA_VERSION
    assert report["backend"] == "cpu"
    fn = report["functions"]["train_step"]
    assert fn["compile_seconds"] > 0 and fn["lower_seconds"] > 0
    assert fn["cost"]["flops"] > 0
    assert fn["memory"]["temp_bytes"] > 0
    assert fn["memory"]["output_bytes"] > 0
    # donation facts: the step donates its state arguments
    assert 0 < fn["donation"]["donated_args"] <= fn["donation"]["total_args"]
    assert fn["argument_bytes_host_estimate"] > 0


def test_e2e_eval_compile_report_covers_decode_fns(diag_run):
    config, cfg_eval, _ = diag_run
    path = os.path.join(
        config.summary_dir, "telemetry", "compile_report-decode.json"
    )
    report = json.load(open(path))
    assert {"decode/encode", "decode/beam_search"} <= set(report["functions"])
    for fn in report["functions"].values():
        # 0.0 when the executable came back from a cache in under 0.5 ms
        assert fn["compile_seconds"] >= 0
        assert fn["cost"]["flops"] > 0


def test_e2e_heartbeat_carries_diag_and_device_facts(diag_run):
    config, _, _ = diag_run
    hb = json.load(
        open(os.path.join(config.summary_dir, "telemetry", "heartbeat.json"))
    )
    assert hb["device_platform"] == "cpu"
    assert "device_kind" in hb
    # last diag snapshot, gauge prefix stripped
    assert hb["diag"]["attn_entropy"] > 0
    assert hb["diag"]["alpha_coverage_dev"] >= 0
    # xla accounting summary rides along
    assert hb["xla"]["train_step/compile_s"] > 0


def test_e2e_attention_artifacts_schema(diag_run):
    _, cfg_eval, _ = diag_run
    out_dir = cfg_eval.eval_result_dir
    rows = [json.loads(l) for l in open(os.path.join(out_dir, "attn.jsonl"))]
    assert rows, "no attention records exported"
    for r in rows:
        assert r["run_id"]
        assert len(r["words"]) == len(r["entropy"]) == len(r["alphas"])
        assert r["grid"] ** 2 == r["num_ctx"] == len(r["alphas"][0])
        for h, grid_row in zip(r["entropy"], r["alphas"]):
            assert 0.0 <= h <= np.log(r["num_ctx"]) + 1e-3
            assert sum(grid_row) == pytest.approx(1.0, abs=0.01)
        assert 0.0 <= r["entropy_frac_mean"] <= 1.0
        assert r["coverage_dev"] >= 0.0
        assert 0.0 < r["alpha_max"] <= 1.0
    html = open(os.path.join(out_dir, "attn.html")).read()
    assert "<table" in html and "rgba(" in html
    for r in rows:
        assert r["caption"] in html and str(r["image_id"]) in html


def test_diag_off_run_leaves_no_diag_columns(coco_fixture, tmp_path):
    """Default off: metrics.jsonl must not grow diag columns (the
    bitwise-unchanged guarantee's observable face)."""
    from sat_tpu import runtime

    config = coco_fixture["config"].replace(
        **SMALL_MODEL,
        save_dir=str(tmp_path / "models"),
        summary_dir=str(tmp_path / "summary"),
        max_steps=2,
    )
    runtime.train(config)
    rows = [
        json.loads(l)
        for l in open(os.path.join(config.summary_dir, "metrics.jsonl"))
    ]
    assert rows
    for r in rows:
        assert not any(k.startswith("diag/") for k in r)


def test_cli_rejects_bad_diag_level():
    from sat_tpu.config import Config

    with pytest.raises(ValueError, match="diag_level"):
        Config(diag_level="verbose")
