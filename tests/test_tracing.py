"""Names shared by the host ring and the device trace (docs/OBSERVABILITY.md):
the ``arg`` column and the ``annotate`` hook of ``telemetry/spans.py``,
``op_scopes`` of ``telemetry/xla.py`` (named scopes of every device op),
the indices and set-up spans the train and decode loops leave behind, and
the stall record."""

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sat_tpu import runtime, telemetry
from sat_tpu.config import Config
from sat_tpu.telemetry import exporters
from sat_tpu.telemetry import xla as xla_acct
from sat_tpu.telemetry.spans import NULL_SPAN, NullTelemetry, Telemetry


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# spans.py: the arg column and the annotate hook
# ---------------------------------------------------------------------------


def test_arg_is_stored_and_returned_in_snapshot_order_across_a_wrapped_ring():
    tel = Telemetry(capacity=256)
    for k in range(300):                      # wraps: the first 44 are overwritten
        if k % 2:
            tel.record("odd", k, 1, arg=k)
        else:
            with tel.span("even", k):
                pass
    names, ids, t0s, durs, tids, args = tel.spans_snapshot(with_args=True)
    assert list(args) == list(range(44, 300)) == list(tel.span_args())
    assert [names[i] for i in ids[:2]] == ["even", "odd"]
    # no arg given: -1, through both doors
    tel.record("bare", 0, 1)
    with tel.span("bare"):
        pass
    assert list(tel.span_args()[-2:]) == [-1, -1]


def test_spans_snapshot_is_still_five_values():
    """benchmark/harness.RunData.take_spans, blackbox and slo unpack five."""
    tel = Telemetry(capacity=256)
    tel.record("x", 1, 2, arg=3)
    names, ids, t0s, durs, tids = tel.spans_snapshot()
    assert names == ["x"] and list(durs) == [2]
    assert len(NullTelemetry().spans_snapshot()) == 5
    assert len(NullTelemetry().spans_snapshot(with_args=True)) == 6


def test_null_telemetry_accepts_the_new_signatures():
    null = NullTelemetry()
    assert null.span("a", 3) is NULL_SPAN and null.span("a", arg=3) is NULL_SPAN
    null.record("a", 0, 1, 3)
    null.record("a", 0, 1, arg=3)
    assert null.span_args().size == 0 and null.annotate is None
    NULL_SPAN.drop()
    # the module-level doors dispatch to it while telemetry is off
    with telemetry.span("a", 3):
        telemetry.record("b", 0, 1, arg=4)


def test_chrome_trace_carries_the_index():
    tel = Telemetry(capacity=256)
    tel.record("train/dispatch", tel.anchor_ns + 1000, 500, arg=7)
    tel.record("train/summary", tel.anchor_ns + 2000, 500)
    events = {e["name"]: e for e in exporters.chrome_trace(tel)["traceEvents"] if e["ph"] == "X"}
    assert events["train/dispatch"]["args"] == {"i": 7}
    assert "args" not in events["train/summary"]


class _Annotation:
    log = []

    def __init__(self, name, **kw):
        self.what = (name, kw)

    def __enter__(self):
        self.log.append(("enter",) + self.what)

    def __exit__(self, *exc):
        self.log.append(("exit",) + self.what)


def test_annotate_factory_is_entered_and_exited_once_a_span():
    _Annotation.log = []
    tel = Telemetry(capacity=256)
    with tel.span("quiet", 1):                # unset: never called
        pass
    assert _Annotation.log == []
    tel.annotate = _Annotation
    with tel.span("decode/drain", 5):
        with tel.span("decode/drain/wait", 5):
            pass
    assert _Annotation.log == [
        ("enter", "decode/drain", {"i": 5}), ("enter", "decode/drain/wait", {"i": 5}),
        ("exit", "decode/drain/wait", {"i": 5}), ("exit", "decode/drain", {"i": 5})]
    # record() is told of an interval that is over: nothing to annotate
    tel.record("decode/batch", 0, 1, arg=5)
    # a dropped span closes its annotation and records nothing
    before = len(tel.spans_snapshot()[1])
    span = tel.span("decode/data_wait", 6)
    span.__enter__()
    span.drop()
    assert _Annotation.log[-2:] == [("enter", "decode/data_wait", {"i": 6}),
                                    ("exit", "decode/data_wait", {"i": 6})]
    assert len(tel.spans_snapshot()[1]) == before
    # telemetry off: the null object never sees a factory
    assert telemetry.get().span("x", 1) is NULL_SPAN


def test_timed_iter_numbers_its_waits_and_drops_the_last():
    tel = Telemetry(capacity=256)
    assert list(runtime._timed_iter(iter("abc"), tel, "train/data_wait", first=40)) == list("abc")
    names, ids, *_ , args = tel.spans_snapshot(with_args=True)
    assert [names[i] for i in ids] == ["train/data_wait"] * 3 and list(args) == [40, 41, 42]
    assert list(runtime._timed_iter(iter("ab"), NullTelemetry(), "x")) == ["a", "b"]


# ---------------------------------------------------------------------------
# the stall record
# ---------------------------------------------------------------------------


def test_stall_watch_records_one_span_and_the_deltas():
    tel = Telemetry(capacity=256)
    watch = runtime.StallWatch(tel, factor=3.0, every=4)
    for k in range(8):                        # the median settles at 10 ms
        watch.iteration(k, k * 10_000_000, 10_000_000)
    assert "host/stall" not in tel.aggregates()
    watch.iteration(8, 80_000_000, 25_000_000)        # 2.5 x: not a stall
    assert "host/stall" not in tel.aggregates()
    watch.iteration(9, 105_000_000, 400_000_000)      # 40 x
    assert tel.aggregates()["host/stall"][0] == 1
    names, ids, t0s, durs, tids, args = tel.spans_snapshot(with_args=True)
    assert (names[ids[-1]], int(durs[-1]), int(args[-1])) == ("host/stall", 400_000_000, 9)
    gauges = tel.gauges()
    assert gauges["host/stall_step"] == 9 and gauges["host/stall_ms"] == 400.0
    for key in ("host/stall_cpu_ms", "host/stall_nivcsw", "host/stall_majflt", "host/stall_gc",
                "host/stall_since_boundary_ms"):
        assert gauges[key] >= 0
    assert tel.counters()["host/stalls"] == 1


# ---------------------------------------------------------------------------
# the loops' account of the device standing empty
# ---------------------------------------------------------------------------


class _Handle:
    """What the recorder asks of a dispatched array: ``is_ready()``, counted."""

    def __init__(self, ready=False):
        self.ready, self.asked = ready, 0

    def is_ready(self):
        self.asked += 1
        return self.ready


class _Clock:
    def __init__(self):
        self.now = 1000

    def __call__(self):
        return self.now


def _empty_spans(tel, family="decode"):
    return _spans(tel).get(family + "/device_empty", [])


def test_a_stretch_opens_only_on_a_ready_handle_and_closes_at_the_next_dispatch():
    tel, clock = Telemetry(capacity=256), _Clock()
    occ = runtime.DeviceOccupancy(tel, "decode", clock=clock)
    occ.observe()                              # nothing enqueued yet: nothing to ask
    busy = _Handle(ready=False)
    occ.enqueued(busy, 4)
    clock.now = 2000
    occ.observe()
    occ.observe()
    assert busy.asked == 2 and _empty_spans(tel) == []
    busy.ready = True                          # the runtime's word that batch 4 is finished
    clock.now = 3000
    occ.observe()
    clock.now = 7500
    nxt = _Handle()
    occ.enqueued(nxt, 5)                       # dispatch 5 has returned
    assert _empty_spans(tel) == [(3000, 4500, 5)]
    # the handle that closed it is the one asked from now on
    occ.observe()
    assert (busy.asked, nxt.asked) == (3, 1) and len(_empty_spans(tel)) == 1


def test_an_open_stretch_asks_nothing_and_never_opens_twice():
    tel, clock = Telemetry(capacity=256), _Clock()
    occ = runtime.DeviceOccupancy(tel, "train", clock=clock)
    done = _Handle(ready=True)
    occ.enqueued(done, 9)
    occ.observe()
    for clock.now in (1100, 1200, 1300):       # log_io, data_wait, place: one comparison each
        occ.observe()
    assert done.asked == 1
    clock.now = 1400
    occ.enqueued(_Handle(), 10)
    assert _empty_spans(tel, "train") == [(1000, 400, 10)]      # from the FIRST observe, once
    occ.enqueued(_Handle(), 11)                # no stretch open: a dispatch records nothing
    assert len(_empty_spans(tel, "train")) == 1


def test_the_stretch_is_annotated_by_hand_with_the_index_it_should_end_at():
    _Annotation.log = []
    tel = Telemetry(capacity=256)
    tel.annotate = _Annotation
    occ = runtime.DeviceOccupancy(tel, "decode")
    occ.enqueued(_Handle(ready=True), 6)
    assert _Annotation.log == []
    occ.observe()
    with tel.span("decode/drain/detok", 5):    # the annotation outlives the phases inside it
        pass
    assert _Annotation.log[0] == ("enter", "decode/device_empty", {"i": 7})
    occ.enqueued(_Handle(ready=True), 7)
    assert _Annotation.log[-1] == ("exit", "decode/device_empty", {"i": 7})
    # the loop ends with a stretch open: its annotation is closed, nothing is recorded
    occ.observe()
    occ.close()
    assert _Annotation.log[-2:] == [("enter", "decode/device_empty", {"i": 8}),
                                    ("exit", "decode/device_empty", {"i": 8})]
    assert [a for _, _, a in _empty_spans(tel)] == [7]
    occ.observe()                              # closed: no handle is asked again
    assert len(_Annotation.log) == 6


def test_the_gauge_is_empty_time_over_time_since_the_first_dispatch():
    tel, clock = Telemetry(capacity=256), _Clock()
    occ = runtime.DeviceOccupancy(tel, "train", clock=clock)
    occ.publish()                              # before any dispatch: no gauge
    assert "train/device_empty_share" not in tel.gauges()
    occ.enqueued(_Handle(ready=True), 0)       # first dispatch at 1000
    clock.now = 1600
    occ.observe()
    clock.now = 1800
    occ.enqueued(_Handle(ready=True), 1)       # 200 empty of 800
    occ.publish()
    assert tel.gauges()["train/device_empty_share"] == pytest.approx(0.25)
    clock.now = 1900
    occ.observe()
    clock.now = 2000
    occ.publish()                              # the open stretch counts as far as it has run
    assert tel.gauges()["train/device_empty_share"] == pytest.approx(0.3)


def test_telemetry_off_asks_no_handle_anything():
    class Untouchable:
        def is_ready(self):
            raise AssertionError("is_ready() with telemetry off")

    null = runtime.NULL_OCCUPANCY
    null.enqueued(Untouchable(), 0)
    null.observe()
    null.publish()
    null.close()
    # and that is what both loops hold when telemetry is off
    import inspect

    source = inspect.getsource(runtime)
    assert source.count("if tel.enabled else NULL_OCCUPANCY") == 2
    assert source.count("DeviceOccupancy(tel, ") == 2
    # a handle that cannot say (a host array from a stubbed program) is never asked
    tel = Telemetry(capacity=256)
    occ = runtime.DeviceOccupancy(tel, "decode")
    occ.enqueued(np.zeros(3), 0)
    occ.observe()
    occ.enqueued(np.zeros(3), 1)
    assert _empty_spans(tel) == []


# ---------------------------------------------------------------------------
# xla.py: op_scopes
# ---------------------------------------------------------------------------

TINY = dict(
    image_size=32, dim_embedding=16, num_lstm_units=16, dim_initialize_layer=16,
    dim_attend_layer=16, dim_decode_layer=32, vocabulary_size=64, batch_size=4,
    max_caption_length=6, beam_size=3,
)

# the rules of benchmark/scopes/*.json, on op_name, first match wins
TRAIN_RULES = [("encoder", r"encoder"), ("decoder_bwd", r"transpose\("),
               ("scan_plumbing", r"^(?!.*decoder/).*while/body"), ("decoder_fwd", r"decoder/|loss"),
               ("other", r"optimizer|metrics")]
BEAM_RULES = [("topk", r"beam/topk"), ("attend_pad", r"decoder/attend/pad"), ("attend_core", r"decoder/attend"),
              ("tile", r"beam/tile"), ("lstm_logits", r"decoder/(lstm|logits|embed)"), ("other", r"beam/|decoder/")]


def _bucket(rules, op_name):
    return next((b for b, rx in rules if re.search(rx, op_name)), "unscoped")


@pytest.fixture(scope="module")
def analyzed():
    """xla.analyze of the three programs the benchmark's memory peak reads,
    at tiny widths; the Pallas kernel runs in interpret mode on the CPU."""
    from sat_tpu.models.captioner import encode
    from sat_tpu.ops import pallas_attention
    from sat_tpu.ops.beam_search import beam_search_jit
    from sat_tpu.train.step import create_train_state, make_jit_train_step

    config = Config(**TINY)
    tel = Telemetry(capacity=256)
    xla_acct.reset()
    # as runtime._telemetry_begin sets it: no executable out of a cache
    # that a build with other scope names filled
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    state = create_train_state(jax.random.PRNGKey(0), config)
    B, T = config.batch_size, config.max_caption_length
    batch = {"images": jnp.zeros((B, 32, 32, 3)), "word_idxs": jnp.zeros((B, T), jnp.int32),
             "masks": jnp.ones((B, T))}
    xla_acct.analyze("train_step", make_jit_train_step(config), state, batch, jax.random.key(1), tel=tel)

    @jax.jit
    def encode_fn(variables, images):
        return encode(variables, config, images, train=False)[0]

    variables = {"params": state.params}
    xla_acct.analyze("decode/encode", encode_fn, variables, batch["images"], tel=tel)
    contexts = jnp.zeros((B, config.num_ctx, config.dim_ctx))
    pallas_attention.FORCE_INTERPRET = True
    try:
        xla_acct.analyze("decode/beam_search", beam_search_jit, state.params["decoder"], config, contexts, 3,
                         beam_size=3, valid_size=60, return_alphas=False, tel=tel)
    finally:
        pallas_attention.FORCE_INTERPRET = False
    entries = xla_acct.entries()
    xla_acct.reset()
    return entries, tel


def test_entries_keep_the_names_the_benchmark_s_memory_peak_leans_on(analyzed):
    """benchmark/harness.program_temps reads these three by name (PERF.md
    section 7): a program that drops one falls under the memory floor."""
    entries, _ = analyzed
    assert set(entries) == {"train_step", "decode/encode", "decode/beam_search"}
    for entry in entries.values():
        assert entry["memory"]["temp_bytes"] >= 0
        assert entry["op_scopes"]["columns"] == list(xla_acct.OP_SCOPE_COLUMNS)
        json.dumps(entry)                      # compile_report.json holds it as it is


def test_op_map_is_timed_as_the_compile_accounting_span(analyzed):
    _, tel = analyzed
    count, total_ns, _ = tel.aggregates()["setup/compile_accounting"]
    assert count == 3 and 0 < total_ns < 3e9          # one a program, well under a second each


@pytest.mark.parametrize("program,rules", [("train_step", TRAIN_RULES), ("decode/beam_search", BEAM_RULES)])
def test_op_scopes_name_the_loop_bodies_and_every_rule_claims_an_instruction(analyzed, program, rules):
    rows = analyzed[0][program]["op_scopes"]["rows"]
    names = [r[0] for r in rows]
    assert len(names) == len(set(names)) and all(n.startswith("%") for n in names)
    containers = [r for r in rows if r[2]]
    assert containers and all(re.match(r"%(while|conditional|call)", r[0]) for r in containers)
    leaves = [r for r in rows if not r[2]]
    # the loop bodies are walked: every leaf there has its path
    in_body = [r for r in leaves if "while/body" in r[3]]
    assert len(in_body) >= 10
    claimed = {}
    for r in leaves:
        claimed.setdefault(_bucket(rules, r[3]), []).append(r[0])
    for bucket, _rx in rules:
        if bucket == "attend_pad" and program == "decode/beam_search":
            continue      # interpret mode inlines the kernel: checked below on its own
        assert claimed.get(bucket), f"no instruction of {program} falls to {bucket}: {sorted(claimed)}"
    share = len(claimed.get("unscoped", [])) / len(leaves)
    assert share < 0.15, (share, claimed.get("unscoped"))


def test_backward_pass_and_stacked_residuals_are_told_apart(analyzed):
    rows = analyzed[0]["train_step"]["op_scopes"]["rows"]
    ops = [r[3] for r in rows]
    assert any(re.search(r"transpose\(jvp\(loss\)\)/while/body/.*decoder/lstm", o) for o in ops)
    assert any(re.search(r"jvp\(loss\)/while/body/.*decoder/attend", o) and "transpose" not in o for o in ops)
    assert any(re.search(r"jvp\(loss\)/.*encoder/", o) for o in ops)
    assert any("optimizer" in o for o in ops)
    # under the scan and under no scope: what the forward pass stacks for the backward
    assert any(_bucket(TRAIN_RULES, o) == "scan_plumbing" for o in ops)


@pytest.mark.parametrize("beams", [1, 3])
def test_the_pad_round_the_kernel_carries_its_own_scope(beams):
    """One grid a row, and one grid an image under three beams: what is
    left round the kernel (the image axis padded to a block, the rows
    regrouped by image) is named apart from it."""
    from sat_tpu.ops.pallas_attention import fused_attend

    text = fused_attend.lower(jnp.zeros((3, 5, 16)), jnp.zeros((3 * beams, 16)), jnp.zeros((16, 1)),
                              jnp.zeros((3, 5, 8)), interpret=True).as_text(debug_info=True)
    assert "decoder/attend/pad" in text and "fused_attend" in text


def test_the_latent_attention_s_scopes_reach_op_scopes():
    """``decoder="deepseek_v3"``: every scope the benchmark's ``lm_mla_*`` and
    ``lm_moe_shared_device_ms`` metrics read names at least one leaf
    instruction of the beam program, by phase (the expanded form under
    ``beam/prefill``, the absorbed one under ``beam/loop``'s body), and the
    rules of benchmark/scopes/lm_mla.json claim them."""
    from sat_tpu.models import decoders
    from sat_tpu.ops.beam_search import beam_search_jit

    config = Config(
        decoder="deepseek_v3", image_size=32, hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
        # three layers: the prefill keeps latents alone, so the LAST layer's
        # experts are dead code there and an expert layer has to come before it
        num_hidden_layers=3, num_dense_layers=1, num_attention_heads=4, num_experts=8, num_experts_per_tok=3,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_shared_experts=2,
        tie_word_embeddings=False, layer_types=("latent_attention",) * 3, vocabulary_size=100,
        max_caption_length=6, beam_size=3, batch_size=4,
    )
    params = decoders.init_params(jax.random.PRNGKey(0), config)
    contexts = jnp.zeros((4, config.num_ctx, config.dim_ctx))
    tel = Telemetry(capacity=64)
    xla_acct.reset()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    xla_acct.analyze("decode/beam_search", beam_search_jit, params, config, contexts, 1,
                     beam_size=3, valid_size=100, return_alphas=False, tel=tel)
    rows = xla_acct.entries()["decode/beam_search"]["op_scopes"]["rows"]
    xla_acct.reset()
    ops = [r[3] for r in rows if not r[2]]
    both = ("q", "latent", "scores", "out")
    for scope in [f"beam/prefill.*decoder/lm/attn/{s}" for s in both + ("expand",)] + \
                 [f"while/body.*decoder/lm/attn/{s}" for s in both + ("absorb",)] + \
                 ["beam/prefill.*decoder/lm/moe/shared", "while/body.*decoder/lm/moe/shared"]:
        assert any(re.search(scope, o) for o in ops), scope
    assert not any(re.search(r"beam/prefill.*attn/absorb|while/body.*attn/expand", o) for o in ops)
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmark", "scopes", "lm_mla.json")) as f:
        rules = [tuple(r) for r in json.load(f)["rules"]]
    claimed = {_bucket(rules, o) for o in ops}
    assert {"prefill_attn", "step_attn", "shared", "other"} <= claimed


def _dsa_config(**changes):
    return Config(**{**dict(
        decoder="glm_moe_dsa", image_size=96, hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
        num_hidden_layers=3, num_dense_layers=1, num_attention_heads=4, num_experts=8, num_experts_per_tok=3,
        experts_held=4, first_expert=2, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, n_shared_experts=1, index_n_heads=4, index_head_dim=16, index_topk=16,
        indexer_types=("full", "shared", "full"), tie_word_embeddings=False,
        layer_types=("latent_attention",) * 3, vocabulary_size=100, max_caption_length=6, beam_size=3, batch_size=2,
    ), **changes})


def test_the_sparse_attention_s_scopes_and_gauges_exist(tmp_path):
    """``decoder="glm_moe_dsa"``: the indexer's and the selection's scopes
    name leaf instructions of the beam program in both phases (``index``
    in the prefill only past ``index_topk`` positions: 36 > 16 here), the
    rules of benchmark/scopes/lm_dsa*.json claim them, and what the decoder
    counts of a batch is what the drain's two gauges are set from."""
    from sat_tpu.models import decoders
    from sat_tpu.ops.beam_search import beam_search_jit

    config = _dsa_config()
    params = decoders.init_params(jax.random.PRNGKey(0), config)
    contexts = jnp.zeros((2, config.num_ctx, config.dim_ctx))
    tel = Telemetry(capacity=64)
    xla_acct.reset()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    xla_acct.analyze("decode/beam_search", beam_search_jit, params, config, contexts, 1,
                     beam_size=3, valid_size=100, return_alphas=False, tel=tel)
    rows = xla_acct.entries()["decode/beam_search"]["op_scopes"]["rows"]
    xla_acct.reset()
    ops = [r[3] for r in rows if not r[2]]
    for scope in [f"beam/prefill.*decoder/lm/attn/{s}" for s in ("q", "latent", "index", "select", "expand", "scores", "out")] + \
                 [f"beam/loop.*decoder/lm/attn/{s}" for s in ("q", "latent", "index", "select", "absorb", "scores", "out")] + \
                 ["beam/prefill.*decoder/lm/moe/experts", "beam/loop.*decoder/lm/moe/experts"]:
        assert any(re.search(scope, o) for o in ops), scope
    scopes = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "scopes")
    for name, wanted in (("lm_dsa", {"index", "select", "other"}),
                         ("lm_dsa_phases", {"prefill_attention", "step_select", "step_held_experts", "other"})):
        with open(os.path.join(scopes, name + ".json")) as f:
            rules = [tuple(r) for r in json.load(f)["rules"]]
        assert wanted <= {_bucket(rules, o) for o in ops}, name
    out = beam_search_jit(params, config, contexts, 1, beam_size=3, valid_size=100)
    stats = out.decoder_stats
    assert {"dsa_attended", "moe_pairs", "step_selected", "state_bytes"} <= set(stats)
    attended, visible = (float(x) for x in stats["dsa_attended"])
    assert attended / visible == pytest.approx(6 * 16 / sum(36 + t + 1 for t in range(6)))
    pairs = np.asarray(stats["moe_pairs"])
    assert pairs.shape == (2, 3) and (pairs[:, 2] == 0).all() and (pairs[:, 0] <= pairs[:, 1]).all()
    import inspect

    from sat_tpu import runtime

    drain = inspect.getsource(runtime)
    assert '"decode/lm_dsa_selected_share"' in drain and '"decode/lm_moe_held_pair_share"' in drain
    # the lax blocks ran here (the CPU): no query block went through the kernel, and the drain says so
    assert np.asarray(stats["prefill_fused_blocks"]).tolist() == [0, 1]
    assert '"decode/lm_dsa_prefill_fused_share"' in drain
    # and the lax combine (tests/test_moe_combine.py forces the kernel): no
    # call of the prefill's expert layers went through it, and every call
    # fetched a row for every routed pair, so the two gauges the drain
    # makes of these would read 0.0 and 1.0
    combine = np.asarray(stats["moe_combine"])              # [prefill | steps, rows fetched | through the kernel | calls]
    assert combine.shape == (2, 3) and (combine[:, 1] == 0).all() and (combine[:, 2] > 0).all()
    assert (combine[:, 0] == pairs[:, 1]).all()
    assert '"decode/lm_moe_combine_fused_share"' in drain and '"decode/lm_moe_combine_rows_share"' in drain


def _dots3_config(**changes):
    return Config(**{**dict(
        decoder="dots3_note", image_size=96, hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
        num_hidden_layers=3, num_dense_layers=1, num_attention_heads=4, num_experts=8, num_experts_per_tok=3,
        experts_held=4, first_expert=2, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, n_shared_experts=1, index_n_heads=4, index_head_dim=16, index_topk=16,
        swa_num_attention_heads=2, swa_q_lora_rank=40, swa_kv_lora_rank=28, swa_qk_nope_head_dim=24,
        swa_qk_rope_head_dim=16, swa_v_head_dim=16, sliding_window_size=9, attention_gate="headwise",
        mla_lora_rescale=True, tie_word_embeddings=False,
        layer_types=("full_attention", "sliding_attention", "sliding_attention"),
        vocabulary_size=100, max_caption_length=6, beam_size=3, batch_size=2,
    ), **changes})


@pytest.mark.parametrize("fused", [False, True], ids=["lax", "kernel"])
def test_a_sliding_layer_s_scopes_lie_apart_under_the_mixer_s_and_the_drain_has_its_gauges(monkeypatch, fused):
    """``decoder="dots3_note"``: a sliding layer's seven scopes name leaf
    instructions of the beam program under ``decoder/lm/attn/window`` in
    both phases (with the windowed kernel under its test hook, its call
    sits under ``window/scores``), the full layer keeps its own and has a
    gate; the rules of benchmark/scopes/lm_swa*.json claim them, the glm52
    cell's rule files take none of a sliding layer's for a full layer's,
    and what the decoder counts of a batch is what the drain's gauges are
    set from."""
    from sat_tpu.models import decoders, lm_common
    from sat_tpu.ops import flash_prefill
    from sat_tpu.ops.beam_search import beam_search_jit

    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 12)
    monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", fused)
    # the hook is no part of a trace's key: a Config of its own a case, so that neither meets the other's trace
    config = _dots3_config(num_data_workers=8 + fused)
    params = decoders.init_params(jax.random.PRNGKey(0), config)
    contexts = jnp.zeros((2, config.num_ctx, config.dim_ctx))
    tel = Telemetry(capacity=64)
    xla_acct.reset()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    xla_acct.analyze("decode/beam_search", beam_search_jit, params, config, contexts, 1,
                     beam_size=3, valid_size=100, return_alphas=False, tel=tel)
    rows = xla_acct.entries()["decode/beam_search"]["op_scopes"]["rows"]
    xla_acct.reset()
    ops = [r[3] for r in rows if not r[2]]
    for scope in [f"beam/prefill.*decoder/lm/attn/window/{s}/" for s in ("q", "latent", "expand", "scores", "gate", "out")] + \
                 [f"beam/loop.*decoder/lm/attn/window/{s}/" for s in ("q", "latent", "absorb", "scores", "gate", "out")] + \
                 [f"beam/{phase}.*decoder/lm/attn/{s}/" for phase in ("prefill", "loop") for s in ("q", "index", "gate", "out")]:
        assert any(re.search(scope, o) for o in ops), scope
    scopes = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "scopes")

    def rules_of(name):
        with open(os.path.join(scopes, name + ".json")) as f:
            return [tuple(r) for r in json.load(f)["rules"]]

    for name, wanted in (("lm_swa", {"window_prefill", "window_step", "other"}), ("lm_swa_gate", {"gate", "other"}),
                         ("lm_swa_phases", {"swa_prefill_attention", "swa_step_attention", "full_prefill_attention", "other"})):
        assert wanted <= {_bucket(rules_of(name), o) for o in ops}, name
    window = [o for o in ops if "decoder/lm/attn/window/" in o]
    assert {_bucket(rules_of("lm_beam_search"), o) for o in window} == {"mixer"}
    for name in ("lm_dsa", "lm_dsa_phases", "lm_mla_absorb", "lm_mla_query"):
        assert {_bucket(rules_of(name), o) for o in window} == {"other"}, name
    # the kernel's call, where it runs: under each kind's own scores scope
    text = beam_search_jit.lower(params, config, contexts, 1, beam_size=3, valid_size=100).compile().as_text()
    kernel = set(re.findall(r'op_name="([^"]*flash_prefill[^"]*)"', text))
    assert sorted({_bucket(rules_of("lm_swa_phases"), o) for o in kernel}) == \
        (["full_prefill_attention", "swa_prefill_attention"] if fused else [])
    out = beam_search_jit(params, config, contexts, 1, beam_size=3, valid_size=100)
    stats = out.decoder_stats
    assert {"swa_attended", "state_bytes_window", "state_bytes", "prefill_fused_blocks_by_kind"} <= set(stats)
    attended, visible = (float(x) for x in stats["swa_attended"])
    assert attended / visible == pytest.approx(6 * 9 / sum(36 + t + 1 for t in range(6)))
    assert int(stats["state_bytes_window"]) == 2 * 2 * 44 * (2 * 8 + 6 * 6) < int(stats["state_bytes"])
    assert np.asarray(stats["prefill_fused_blocks_by_kind"]).tolist() == [[3 * fused, 3], [6 * fused, 6]]
    import inspect

    from sat_tpu import runtime

    drain = inspect.getsource(runtime)
    assert '"decode/lm_swa_state_mb"' in drain and '"decode/lm_swa_attended_share"' in drain


def test_the_fused_prefill_kernel_s_call_carries_the_scope_its_roofline_share_reads(monkeypatch):
    """``ops/flash_prefill.py``'s call sits under
    ``beam/prefill/.../decoder/lm/attn/scores``: the rule of
    benchmark/scopes/lm_dsa_phases.json that feeds
    ``lm_dsa_prefill_roofline_share`` claims it, so the share keeps reading
    the prefill's attention when the kernel is what runs it (traced with the
    kernel under its test hook; 36 positions in query blocks of 12)."""
    from sat_tpu.models import decoders, lm_common
    from sat_tpu.ops import flash_prefill
    from sat_tpu.ops.beam_search import beam_search_jit

    monkeypatch.setattr(lm_common, "QUERY_BLOCK", 12)
    monkeypatch.setattr(flash_prefill, "FORCE_INTERPRET", True)
    config = _dsa_config(max_caption_length=4, beam_size=2)
    params = decoders.init_params(jax.random.PRNGKey(0), config)
    contexts = jnp.zeros((2, config.num_ctx, config.dim_ctx))
    text = beam_search_jit.lower(params, config, contexts, 1, beam_size=2, valid_size=100).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*flash_prefill[^"]*)"', text))
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmark", "scopes", "lm_dsa_phases.json")) as f:
        rules = [tuple(r) for r in json.load(f)["rules"]]
    assert names and {_bucket(rules, n) for n in names} == {"prefill_attention"}, names
    out = beam_search_jit(params, config, contexts, 1, beam_size=2, valid_size=100)
    assert np.asarray(out.decoder_stats["prefill_fused_blocks"]).tolist() == [3, 3]


@pytest.mark.parametrize("decoder", ["glm_moe_dsa", "dots3_note"])
def test_the_combine_s_kernel_carries_the_scope_the_route_bucket_reads(decoder, monkeypatch):
    """``ops/moe_combine.py``'s call sits under
    ``beam/prefill/.../decoder/lm/moe/combine``: the rule of
    benchmark/scopes/lm_beam_search.json that feeds
    ``lm_moe_route_device_ms`` claims it, so the bucket keeps reading the
    combine when the kernel is what runs it (traced with the kernel under
    its test hook, a prefill's line lowered to the toy's 36 positions x 3,
    a stream of 128 lanes); and what the search then reports is what the
    drain's two gauges are made of: every call of the prefill through the
    kernel, the held pairs fetched there and every routed pair in the
    steps."""
    from sat_tpu.models import decoders
    from sat_tpu.ops import moe_combine
    from sat_tpu.ops.beam_search import beam_search_jit

    monkeypatch.setattr(moe_combine, "FORCE_INTERPRET", True)
    monkeypatch.setattr(moe_combine, "_MIN_PAIRS", 36 * 3)
    config = (_dsa_config if decoder == "glm_moe_dsa" else _dots3_config)(
        hidden_size=128, max_caption_length=4, beam_size=2
    )
    params = decoders.init_params(jax.random.PRNGKey(0), config)
    contexts = jnp.zeros((2, config.num_ctx, config.dim_ctx))
    text = beam_search_jit.lower(params, config, contexts, 1, beam_size=2, valid_size=100).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*moe_combine[^"]*)"', text))
    assert names and all(re.search(r"beam/prefill.*decoder/lm/moe/combine/", n) for n in names), names
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmark", "scopes", "lm_beam_search.json")) as f:
        rules = [tuple(r) for r in json.load(f)["rules"]]
    assert {_bucket(rules, n) for n in names} == {"route"}, names
    out = beam_search_jit(params, config, contexts, 1, beam_size=2, valid_size=100)
    pairs, combine = np.asarray(out.decoder_stats["moe_pairs"]), np.asarray(out.decoder_stats["moe_combine"])
    assert combine[0, 1] == combine[0, 2] > 0 and combine[1, 1] == 0     # fused_share 1.0; the steps keep the lax form
    rows_share = combine[:, 0].sum() / pairs[:, 1].sum()
    held_share = pairs[:, 0].sum() / pairs[:, 1].sum()
    assert combine[0, 0] == pairs[0, 0] and combine[1, 0] == pairs[1, 1]
    assert held_share < rows_share < 1.0        # the held share + the steps' pairs not held


def test_parse_op_scopes_on_a_written_module():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inner.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/never/seen"}
}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[]{:T(128)}, f32[4]{0:T(128)}) parameter(0)
  %get-tuple-element.3 = f32[4]{0} get-tuple-element(%t), index=1
  %fusion.4 = f32[4]{0:T(128)S(1)} fusion(%get-tuple-element.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/decoder/lstm/add" stack_frame_id=3}
  ROOT %tuple.5 = (s32[]{:T(128)}, f32[4]{0}) tuple(%get-tuple-element.3, %fusion.4)
}

%cond.6 (t: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.7 = pred[] compare(%t.1, %t.1), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

%branch_a.20 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %negate.21 = f32[4]{0} negate(%x), metadata={op_name="jit(f)/cond/branch_0_fun/neg"}
}

%branch_b.22 (y: f32[4]) -> f32[4] {
  %y = f32[4]{0} parameter(0)
  ROOT %exp.23 = f32[4]{0} exponential(%y), metadata={op_name="jit(f)/cond/branch_1_fun/exp"}
}

ENTRY %main.8 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %copy.9 = f32[4]{0:T(128)} copy(%a)
  %conditional.24 = f32[4]{0} conditional(%a, %a, %a), branch_computations={%branch_a.20, %branch_b.22}, metadata={op_name="jit(f)/cond"}
  %while.10 = (s32[]{:T(128)}, /*index=1*/f32[4]{0:T(128)}) while(%copy.9), condition=%cond.6, body=%body.2, metadata={op_name="jit(f)/while"}
  ROOT %reduce.11 = f32[4]{0} reduce(%while.10, %a), dimensions={0}, to_apply=%fused_computation.1, metadata={op_name="jit(f)/reduce_sum"}
}
"""
    rows = {r[0]: r for r in xla_acct.parse_op_scopes(text)}
    # parameters, tuples and the insides of the fusion are no device events
    assert set(rows) == {"%copy.9", "%while.10", "%reduce.11", "%fusion.4", "%lt.7",
                         "%conditional.24", "%negate.21", "%exp.23"}      # both branches are walked
    assert rows["%conditional.24"][2] is True
    assert rows["%while.10"] == ["%while.10", "(s32[],f32[4])", True, "jit(f)/while", False]
    assert rows["%fusion.4"][1:] == ["f32[4]", False, "jit(f)/while/body/decoder/lstm/add", False]
    # the compiler's own copy has no path: it only feeds the loop, and says whose name it took
    assert rows["%copy.9"][2:] == [False, "jit(f)/while", True]
    assert rows["%reduce.11"][2] is False              # to_apply of a reduce is no call


# ---------------------------------------------------------------------------
# runtime: the spans a tiny train run and a tiny eval run leave behind
# ---------------------------------------------------------------------------

SMALL_MODEL = dict(
    image_size=32, dim_embedding=16, num_lstm_units=16, dim_initialize_layer=16,
    dim_attend_layer=16, dim_decode_layer=32, compute_dtype="float32",
    save_period=0, log_every=2, num_epochs=2, num_data_workers=2, beam_size=2,
)


def _spans(tel):
    names, ids, t0s, durs, tids, args = tel.spans_snapshot(with_args=True)
    out = {}
    for i, t0, dur, arg in zip(ids, t0s, durs, args):
        out.setdefault(names[int(i)], []).append((int(t0), int(dur), int(arg)))
    return out


@pytest.fixture(scope="module")
def traced(coco_fixture, tmp_path_factory):
    """``--phase=train`` then ``--phase=eval`` through cli.main with
    ``--telemetry``, as the benchmark's drivers start them: spans, gauges
    and the saved breakdown of each."""
    import types

    from sat_tpu import cli

    tmp = tmp_path_factory.mktemp("traced_runs")
    config = coco_fixture["config"].replace(
        **SMALL_MODEL, save_dir=str(tmp / "models"), summary_dir=str(tmp / "summary"),
        eval_result_dir=str(tmp / "results"), eval_result_file=str(tmp / "results.json"),
        max_eval_ann_num=None, shard_cache="off", heartbeat_interval=0.0,
    )
    path = str(tmp / "config.json")
    config.save(path)
    assert cli.main(["--phase=train", "--config", path, "--telemetry"]) == 0
    tdir = os.path.join(config.summary_dir, "telemetry")
    out = types.SimpleNamespace(train=_spans(telemetry.get()), train_gauges=telemetry.get().gauges(),
                                report=json.load(open(os.path.join(tdir, "breakdown.json"))),
                                compile_report=json.load(open(os.path.join(tdir, "compile_report.json"))),
                                jsonl=[json.loads(line) for line in open(os.path.join(tdir, "telemetry.jsonl"))])
    assert cli.main(["--phase=eval", "--beam_size=2", "--config", path, "--telemetry"]) == 0
    out.decode, out.decode_gauges = _spans(telemetry.get()), telemetry.get().gauges()
    out.decode_report = json.load(open(os.path.join(tdir, "breakdown-decode.json")))
    telemetry.disable()
    return out


@pytest.fixture(scope="module")
def traced_runs(traced):
    return traced.train, traced.report, traced.compile_report, traced.decode


def test_train_spans_carry_the_step(traced_runs):
    train, _, _, _ = traced_runs
    steps = list(range(12))                              # 24 annotations / batch 4, two epochs
    for name in ("train/data_wait", "train/place", "train/dispatch", "train/step"):
        assert [a for _, _, a in train[name]] == steps, name
    # a log boundary every second step: the sync and the IO behind it name the step they waited for
    assert [a for _, _, a in train["train/log_sync"]] == steps[1::2]
    assert [a for _, _, a in train["train/log_io"]] == steps[1::2]
    for (s0, d0, a0), (s1, _d1, a1) in zip(train["train/log_sync"], train["train/log_io"]):
        assert a0 == a1 and s1 >= s0 + d0                # log_io starts where log_sync ended
    # loader threads: the batch's number in its pass (six batches an epoch, twice)
    assert sorted(a for _, _, a in train["data/decode_batch"]) == sorted(list(range(6)) * 2)
    assert sorted(a for _, _, a in train["feed/device_put"]) == sorted(list(range(6)) * 2)


def test_train_phases_still_sum_to_the_step(traced_runs):
    _, report, _, _ = traced_runs
    assert report["steps"] == 12
    assert {"train/place", "train/log_io"} <= set(report["phases"])
    # the residual "other" (here mostly the first step's compile accounting)
    # makes the sum exact by construction, as docs/OBSERVABILITY.md says
    assert report["phase_total_s"] == pytest.approx(report["wall_s"], rel=0.05)
    assert report["phases"]["train/place"]["count"] == 12
    assert report["phases"]["train/log_io"]["count"] == 6


def test_setup_spans_cover_data_restore_state_and_first_dispatch(traced_runs):
    train, _, compile_report, decode = traced_runs
    for name in ("setup/data", "setup/state", "setup/first_dispatch", "setup/compile_accounting"):
        assert name in train, name
        assert all(a == -1 for _, _, a in train[name])
    assert len(train["setup/first_dispatch"]) == 1      # the first call of train_step alone
    first = train["setup/first_dispatch"][0]
    d0 = train["train/dispatch"][0]
    assert d0[0] <= first[0] and first[0] + first[1] <= d0[0] + d0[1]     # inside train/dispatch #0
    assert "setup/restore" not in train                  # nothing to restore without --load
    # eval: restore + data + one first dispatch a program, all before the second batch
    assert len(decode["setup/restore"]) == 1 and len(decode["setup/first_dispatch"]) == 2
    assert len(decode["setup/compile_accounting"]) == 2
    assert "setup/data" in decode and "setup/state" in decode
    second = decode["decode/dispatch"][1][0]
    assert all(s + d <= second for s, d, _ in decode["setup/first_dispatch"])
    # written where an operator reads a /profile trace
    rows = compile_report["functions"]["train_step"]["op_scopes"]["rows"]
    assert any("decoder/lstm" in r[3] for r in rows), sorted({r[3] for r in rows})[:40]


def test_decode_spans_carry_the_batch_and_drain_names_the_one_before(traced_runs):
    _, _, _, decode = traced_runs
    n = len(decode["decode/dispatch"])
    assert n == 3                                        # 12 images / batch 4
    batches = list(range(n))
    for name in ("decode/data_wait", "decode/dispatch", "decode/dispatch/encode", "decode/dispatch/beam",
                 "decode/batch"):
        assert [a for _, _, a in decode[name]] == batches, name
    for name in ("decode/drain", "decode/drain/wait", "decode/drain/detok"):
        assert [a for _, _, a in decode[name]] == batches, name      # the last one after the loop
    for b in range(1, n):
        # drain of batch b-1 lies inside iteration b, after dispatch b
        it0, itd, _ = decode["decode/batch"][b]
        s, d, a = decode["decode/drain"][b - 1]
        assert a == b - 1 and it0 <= s and s + d <= it0 + itd
        ds, dd, _ = decode["decode/dispatch"][b]
        assert ds + dd <= s
    for (s, d, _), (ws, wd, _), (ts, td, _) in zip(decode["decode/drain"], decode["decode/drain/wait"],
                                                   decode["decode/drain/detok"]):
        assert s <= ws and ws + wd <= ts and ts + td <= s + d       # wait then detok, both inside the drain
        assert wd + td >= 0.9 * d
    for (s, d, _), (es, ed, _), (bs, bd, _) in zip(decode["decode/dispatch"], decode["decode/dispatch/encode"],
                                                   decode["decode/dispatch/beam"]):
        assert s <= es and es + ed <= bs and bs + bd <= s + d


def _ends(spans):
    return {a: s + d for s, d, a in spans}


def test_train_device_empty_opens_behind_every_log_sync_and_ends_at_the_next_dispatch(traced):
    train = traced.train
    empty = train["train/device_empty"]
    dispatched, step_end = _ends(train["train/dispatch"]), _ends(train["train/step"])
    for (s, d, a), nxt in zip(empty, empty[1:] + [None]):
        assert d > 0 and s >= dispatched[a - 1]          # what it saw finished was enqueued before
        assert dispatched[a] <= s + d <= step_end[a]     # closed when dispatch a had returned
        assert nxt is None or s + d <= nxt[0]            # no two overlap
    assert len({a for _, _, a in empty}) == len(empty)
    # the loop's one sync: the step it waited for is finished, so a stretch opens between the sync's
    # end and the IO behind it, and the next step's dispatch ends it
    starts = {a: s for s, _, a in empty}
    io = {a: s for s, _, a in train["train/log_io"]}
    for k, sync_end in _ends(train["train/log_sync"]).items():
        if k + 1 in dispatched:
            assert sync_end <= starts[k + 1] <= io[k], k
    # device_empty overlaps the phases: it is none of them, and the phases still sum to the step
    assert "train/device_empty" not in runtime._TRAIN_PHASES + runtime._DECODE_PHASES
    assert "train/device_empty" not in traced.report["phases"]


def test_decode_device_empty_lies_between_a_drain_s_wait_and_the_next_encode(traced):
    decode = traced.decode
    empty = decode["decode/device_empty"]
    assert empty                                         # three batches: the stretch batch 2's encode ended
    waited, encoded = _ends(decode["decode/drain/wait"]), _ends(decode["decode/dispatch/encode"])
    beam_start = {a: s for s, _, a in decode["decode/dispatch/beam"]}
    for (s, d, a), nxt in zip(empty, empty[1:] + [None]):
        # opened at the first boundary after dispatch a-1: behind the wait of drain a-2 (batch 1: no
        # drain yet, behind its own data wait)
        assert d > 0 and s >= (waited[a - 2] if a >= 2 else _ends(decode["decode/data_wait"])[1])
        assert encoded[a] <= s + d <= beam_start[a]      # closed when encode a had returned
        assert nxt is None or s + d <= nxt[0]
    assert "decode/device_empty" not in traced.decode_report["phases"]


def test_device_empty_share_reaches_the_gauges_the_jsonl_and_the_breakdown(traced):
    assert 0.0 < traced.train_gauges["train/device_empty_share"] < 1.0
    assert 0.0 <= traced.decode_gauges["decode/device_empty_share"] < 1.0
    # set before the boundary's row is written: every row of telemetry.jsonl has it
    assert traced.jsonl and all("train/device_empty_share" in row["gauges"] for row in traced.jsonl)
    for report, span in ((traced.report, "train/device_empty"), (traced.decode_report, "decode/device_empty")):
        entry = report["device_empty"]
        assert entry["count"] == len(getattr(traced, span.split("/")[0])[span])
        assert 0.0 < entry["share"] < 1.0 and entry["ms_per_step"] > 0.0
        assert entry["total_s"] == pytest.approx(entry["share"] * report["wall_s"], rel=1e-2, abs=2e-6)
        line = [ln for ln in exporters.format_breakdown(report).splitlines() if "device known empty" in ln]
        assert len(line) == 1 and "ms a step" in line[0] and "%" in line[0]
    # a run that recorded none has no such line
    tel = Telemetry(capacity=256)
    tel.record("train/step", 0, 100, 0)
    bare = exporters.step_breakdown(tel, "train/step", runtime._TRAIN_PHASES)
    assert "device_empty" not in bare and "device known empty" not in exporters.format_breakdown(bare)


def test_second_decode_of_a_sweep_starts_fresh(coco_fixture, tmp_path):
    """cli.main hands its telemetry to one loop; a caller that passes none
    (evaluate_sweep's later decodes, the tests) gets fresh buffers."""
    config = coco_fixture["config"].replace(
        **SMALL_MODEL, save_dir=str(tmp_path / "m"), summary_dir=str(tmp_path / "s"), telemetry=True,
        eval_result_dir=str(tmp_path / "r"), eval_result_file=str(tmp_path / "r.json"),
    )
    first = runtime._telemetry_begin(config)
    first.record("setup/data", 0, 1)
    assert runtime._telemetry_begin(config) is not first
    assert first.annotate is jax.profiler.TraceAnnotation
    off = runtime._telemetry_begin(config.replace(telemetry=False))
    assert isinstance(off, NullTelemetry) and off.annotate is None
