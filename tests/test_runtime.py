"""End-to-end runtime tests at fixture scale (SURVEY.md §2.10-2.11, §4)."""

import json
import os
import struct

import numpy as np
import pytest

from sat_tpu.cli import build_config
from sat_tpu import runtime
from sat_tpu.train.checkpoint import latest_checkpoint
from sat_tpu.utils.summary import SummaryWriter, _masked_crc


SMALL_MODEL = dict(
    image_size=32,
    dim_embedding=16,
    num_lstm_units=16,
    dim_initialize_layer=16,
    dim_attend_layer=16,
    dim_decode_layer=32,
    compute_dtype="float32",
    save_period=3,
    log_every=1,
    num_epochs=1,
    num_data_workers=2,
)


@pytest.fixture(scope="module")
def trained(coco_fixture):
    """Train one epoch on the fixture; shared by eval/test phases below."""
    config = coco_fixture["config"].replace(**SMALL_MODEL)
    state = runtime.train(config)
    return config, state


def test_train_loop_end_to_end(trained):
    config, state = trained
    # 24 anns / batch 4 = 6 steps
    assert int(state.step) == 6
    ckpt = latest_checkpoint(config.save_dir)
    assert ckpt is not None and ckpt.endswith("6.npz")
    # summaries: jsonl rows with finite losses at every step
    rows = [
        json.loads(line)
        for line in open(os.path.join(config.summary_dir, "metrics.jsonl"))
    ]
    assert [r["step"] for r in rows] == list(range(1, 7))
    for r in rows:
        assert np.isfinite(r["total_loss"])
        assert np.isfinite(r["cross_entropy_loss"])
    # tfevents file exists and is non-trivial
    events = [
        f for f in os.listdir(config.summary_dir) if f.startswith("events.out")
    ]
    assert events


def test_eval_end_to_end(trained):
    config, state = trained
    scores = runtime.evaluate(config, state=state)
    for key in ("Bleu_1", "Bleu_4", "METEOR", "ROUGE_L", "CIDEr"):
        assert key in scores
        assert 0.0 <= scores[key] <= 1.0 or key == "CIDEr" and scores[key] >= 0
    # results.json written, one entry per unique eval image, valid captions
    results = json.load(open(config.eval_result_file))
    ids = [r["image_id"] for r in results]
    assert len(ids) == len(set(ids)) > 0
    for r in results:
        # a barely-trained model may produce an eos-first beam, which
        # detokenizes to "" (never pad-token noise or a bare ".")
        assert r["caption"] == "" or r["caption"].endswith(".")


def test_test_end_to_end(trained):
    config, state = trained
    results = runtime.test(config, state=state)
    assert len(results) == 12                      # all fixture images
    import pandas as pd

    # keep_default_na: an eos-first beam's empty caption must read back
    # as "" not NaN (vocabulary.load's rule)
    df = pd.read_csv(config.test_result_file, keep_default_na=False)
    assert list(df["caption"]) == [r["caption"] for r in results]
    # a captioned JPG per input image
    rendered = [f for f in os.listdir(config.test_result_dir) if f.endswith(".jpg")]
    assert len(rendered) == 12


def test_restore_0_tensors_is_an_error(coco_fixture, tmp_path):
    config = coco_fixture["config"].replace(
        **SMALL_MODEL, save_dir=str(tmp_path / "empty")
    )
    np.savez(
        tmp_path / "empty_ckpt.npz", global_step=np.asarray(3, np.int32)
    )
    with pytest.raises(ValueError, match="0 tensors"):
        runtime.setup_state(
            config, load=True, model_file=str(tmp_path / "empty_ckpt.npz")
        )


@pytest.mark.parametrize("trimmed", [False, True], ids=["full", "trimmed"])
def test_a_restore_initialises_only_what_the_checkpoint_lacks(trained, tmp_path, monkeypatch, trimmed):
    """``setup_state`` builds shapes only where a checkpoint is about to
    fill the tree: a full checkpoint initialises NOTHING (no state is made
    to be overwritten at once), a trimmed one (no optimizer slots) only
    what it lacks, and either way the restored leaves are the file's."""
    import jax

    from sat_tpu.train.checkpoint import load_flat, state_to_flat, trim_checkpoint

    config, _ = trained
    path = latest_checkpoint(config.save_dir)
    if trimmed:
        path, full = str(tmp_path / "trimmed.npz"), path
        trim_checkpoint(full, path)
    made = []
    real = runtime.create_train_state

    def counted(rng, cfg):
        out = real(rng, cfg)
        made.append(any(not isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(out)))
        return out

    monkeypatch.setattr(runtime, "create_train_state", counted)
    state = runtime.setup_state(config, load=True, model_file=path)
    # the shape pass traces (abstract leaves); a concrete call is an initialisation
    assert made.count(True) == (1 if trimmed else 0), made
    assert not any(isinstance(x, jax.ShapeDtypeStruct) for x in jax.tree_util.tree_leaves(state))
    got, want = state_to_flat(state), load_flat(path)
    for name, value in want.items():
        np.testing.assert_array_equal(got[name], value, err_msg=name)
    assert set(got) >= set(want) and any(k.startswith("optimizer/") for k in got)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_flag_parity():
    config, cli = build_config(
        ["--phase=eval", "--beam_size=5", "--train_cnn", "--load",
         "--model_file=/x/y.npz", "--set", "batch_size=7",
         "--set", "max_train_ann_num=none", "--set", "compute_dtype=float32"]
    )
    assert config.phase == "eval"
    assert config.beam_size == 5
    assert config.train_cnn is True
    assert config.batch_size == 7
    assert config.max_train_ann_num is None
    assert config.compute_dtype == "float32"
    assert cli["load"] is True and cli["model_file"] == "/x/y.npz"


def test_env_path_rerooting(monkeypatch):
    """SAT_DATA_ROOT / SAT_LOG_ROOT re-root default paths (the reference's
    clusterone get_data_path/get_logs_path capability); explicit --set
    overrides are left alone."""
    monkeypatch.setenv("SAT_DATA_ROOT", "/mnt/datasets")
    monkeypatch.setenv("SAT_LOG_ROOT", "/mnt/experiments")
    config, _ = build_config(
        ["--phase=train", "--set", "train_image_dir=/my/custom/images"]
    )
    assert config.train_image_dir == "/my/custom/images"      # --set wins
    assert config.train_caption_file == "/mnt/datasets/data/train/captions_train2014.json"
    assert config.vocabulary_file == "/mnt/datasets/data/vocabulary.csv"
    assert config.save_dir == "/mnt/experiments/data/models/"
    assert config.summary_dir == "/mnt/experiments/summary/"

    monkeypatch.delenv("SAT_DATA_ROOT")
    monkeypatch.delenv("SAT_LOG_ROOT")
    config, _ = build_config(["--phase=train"])
    assert config.train_image_dir == "./data/train/images/"   # untouched


def test_config_rejects_knob_typos():
    from sat_tpu.config import Config

    with pytest.raises(ValueError, match="cnn"):
        Config(cnn="alexnet")
    with pytest.raises(ValueError, match="optimizer"):
        Config(optimizer="adam")  # case-sensitive, like the reference
    with pytest.raises(ValueError, match="num_attend_layers"):
        Config(num_attend_layers=3)
    with pytest.raises(ValueError, match="phase"):
        build_config(["--set", "phase=evaluate"])


def test_cli_eval_sweep(trained, capsys):
    config, _ = trained
    from sat_tpu.cli import main

    args = ["--phase=eval", "--sweep", "--beam_size=2"] + [
        x
        for k, v in config.to_dict().items()
        if isinstance(v, (str, int, float, bool)) and v != ""
        and k in ("save_dir", "summary_dir", "eval_image_dir",
                  "eval_caption_file", "vocabulary_file", "eval_result_dir",
                  "eval_result_file", "batch_size", "vocabulary_size",
                  "image_size", "dim_embedding", "num_lstm_units",
                  "dim_initialize_layer", "dim_attend_layer",
                  "dim_decode_layer", "compute_dtype", "max_eval_ann_num")
        for x in ("--set", f"{k}={v}")
    ]
    assert main(args) == 0
    out = capsys.readouterr().out
    assert "step 3:" in out and "step 6:" in out and "Bleu_4=" in out


def test_cli_rejects_unknown_field():
    with pytest.raises(SystemExit):
        build_config(["--set", "definitely_not_a_field=1"])


# ---------------------------------------------------------------------------
# summary writer wire format
# ---------------------------------------------------------------------------


def _read_records(path):
    """Decode TFRecord framing, verifying both masked CRCs."""
    records = []
    with open(path, "rb") as f:
        while True:
            header = f.read(8)
            if not header:
                return records
            (length,) = struct.unpack("<Q", header)
            (hcrc,) = struct.unpack("<I", f.read(4))
            assert hcrc == _masked_crc(header)
            payload = f.read(length)
            (pcrc,) = struct.unpack("<I", f.read(4))
            assert pcrc == _masked_crc(payload)
            records.append(payload)


def test_summary_writer_tfevents_roundtrip(tmp_path):
    with SummaryWriter(str(tmp_path)) as w:
        w.scalars(1, {"loss": 2.5, "acc": 0.5})
        w.scalars(2, {"loss": float("nan"), "acc": 1.0})  # nan: jsonl only

    event_file = [f for f in os.listdir(tmp_path) if f.startswith("events.out")][0]
    records = _read_records(os.path.join(tmp_path, event_file))
    # file_version event + 2 scalar events
    assert len(records) == 3
    assert b"brain.Event:2" in records[0]
    assert b"loss" in records[1] and b"acc" in records[1]
    # step-2 record must only contain the finite scalar
    assert b"acc" in records[2] and b"loss" not in records[2]
    # float payload of loss=2.5 present in record 1
    assert struct.pack("<f", 2.5) in records[1]

    rows = [json.loads(x) for x in open(tmp_path / "metrics.jsonl")]
    assert rows[0]["step"] == 1 and rows[0]["loss"] == 2.5 and rows[0]["acc"] == 0.5
    # non-finite values can't enter the tfevents wire format but must
    # still leave a trace of the divergence in metrics.jsonl (ADVICE r1)
    assert rows[1]["step"] == 2 and rows[1]["acc"] == 1.0 and rows[1]["loss"] == "nan"
    # every row is stamped for post-hoc joins (docs/OBSERVABILITY.md)
    for row in rows:
        assert isinstance(row["wall_time"], float)
        assert isinstance(row["mono_ns"], int)
        assert isinstance(row["run_id"], str) and row["run_id"]
    assert rows[0]["run_id"] == rows[1]["run_id"]
    assert rows[1]["mono_ns"] >= rows[0]["mono_ns"]


def _decode_histo(histo: bytes):
    """Minimal HistogramProto reader: returns dict of scalar fields plus
    bucket_limit/bucket arrays."""
    out = {"bucket_limit": [], "bucket": []}
    names = {1: "min", 2: "max", 3: "num", 4: "sum", 5: "sum_squares"}
    i = 0
    while i < len(histo):
        key = histo[i]
        field, wire = key >> 3, key & 7
        i += 1
        if wire == 1:
            (v,) = struct.unpack("<d", histo[i : i + 8])
            out[names[field]] = v
            i += 8
        elif wire == 2:
            ln = 0
            shift = 0
            while True:
                b = histo[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            vals = struct.unpack(f"<{ln // 8}d", histo[i : i + ln])
            out["bucket_limit" if field == 6 else "bucket"] = list(vals)
            i += ln
        else:
            raise AssertionError(f"unexpected wire type {wire}")
    return out


def test_summary_writer_histograms(tmp_path):
    values = np.asarray([1.0, -1.0, 0.5, 0.5, 1e6])
    with SummaryWriter(str(tmp_path)) as w:
        w.histograms(7, {"weights": values})

    event_file = [f for f in os.listdir(tmp_path) if f.startswith("events.out")][0]
    records = _read_records(os.path.join(tmp_path, event_file))
    rec = records[1]
    assert b"weights" in rec
    # walk to the histo submessage: Event.summary(5) > Value(1) > histo(5),
    # each preceded by the tag(1) string "weights"
    idx = rec.index(b"weights") + len(b"weights")
    assert rec[idx] == 0x2A  # field 5 (histo), wire type 2
    i = idx + 1
    ln = shift = 0
    while True:  # varint length (histos exceed 127 bytes)
        b = rec[i]
        i += 1
        ln |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            break
    histo = _decode_histo(rec[i : i + ln])
    assert histo["num"] == 5
    assert histo["min"] == -1.0 and histo["max"] == 1e6
    assert histo["sum"] == pytest.approx(1e6 + 1.0)
    assert histo["sum_squares"] == pytest.approx(1e12 + 2.5)
    assert sum(histo["bucket"]) == 5
    assert len(histo["bucket"]) == len(histo["bucket_limit"])
    # limits are bucket *upper* edges: the first retained limit is the
    # upper edge of the bucket holding the min (just above it, within one
    # 1.1× growth step), and the last covers the max
    lims = histo["bucket_limit"]
    assert -1.0 <= lims[0] <= -1.0 / 1.1
    assert lims[-1] >= 1e6


def test_histograms_stay_consistent_under_nonfinite(tmp_path):
    """A diverged run (NaN/inf values) must still produce a well-formed
    proto: NaNs dropped everywhere, infs clamped into the edge buckets."""
    values = np.asarray([np.nan, np.inf, -np.inf, 1.0])
    with SummaryWriter(str(tmp_path)) as w:
        w.histograms(1, {"diverged": values})
    event_file = [f for f in os.listdir(tmp_path) if f.startswith("events.out")][0]
    rec = _read_records(os.path.join(tmp_path, event_file))[1]
    idx = rec.index(b"diverged") + len(b"diverged")
    assert rec[idx] == 0x2A
    i = idx + 1
    ln = shift = 0
    while True:
        b = rec[i]
        i += 1
        ln |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            break
    histo = _decode_histo(rec[i : i + ln])
    assert histo["num"] == 3                      # NaN dropped
    assert sum(histo["bucket"]) == 3              # counts match num
    assert np.isfinite([histo["min"], histo["max"], histo["sum"]]).all()
    assert len(histo["bucket"]) == len(histo["bucket_limit"])


def test_variable_stats_include_histograms(tmp_path):
    tree = {"w": np.linspace(-1, 1, 101, dtype=np.float32),
            "b": np.zeros((4,), dtype=np.float32)}
    with SummaryWriter(str(tmp_path)) as w:
        w.variable_stats(3, tree, prefix="params")
    event_file = [f for f in os.listdir(tmp_path) if f.startswith("events.out")][0]
    records = _read_records(os.path.join(tmp_path, event_file))
    # record 1 = scalar stats, record 2 = histograms
    assert b"params/w/mean" in records[1]
    histo_rec = records[2]
    for tag in (b"params/w", b"params/b"):
        assert tag in histo_rec
    # num encoded as double 101 for w somewhere in the histo record
    assert struct.pack("<d", 101.0) in histo_rec


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 1)])
def test_eval_renders_attention_panels(trained, tmp_path, mesh_shape):
    """save_attention_maps: per-word attention figures land next to the
    eval results and each result row carries normalized [len, N] maps —
    on the plain path and through single-host mesh decoding."""
    config, state = trained
    config = config.replace(
        save_attention_maps=True,
        mesh_shape=mesh_shape,
        eval_result_dir=str(tmp_path / "attn"),
        eval_result_file=str(tmp_path / "attn.json"),
    )
    from sat_tpu.runtime import decode_dataset
    from sat_tpu.data.dataset import prepare_eval_data

    runtime.evaluate(config, state=state)
    panels = [f for f in os.listdir(tmp_path / "attn") if f.endswith("_attention.jpg")]
    assert panels, "no attention panels rendered"

    _, ds, vocab = prepare_eval_data(config)
    rows = decode_dataset(config, state, ds, vocab)
    for r in rows:
        assert len(r["words"]) == r["alphas"].shape[0]
        assert r["alphas"].shape[1] == config.num_ctx
        np.testing.assert_allclose(r["alphas"].sum(-1), 1.0, rtol=1e-4)


def test_eval_sweep_scores_every_checkpoint(trained, monkeypatch):
    config, _ = trained
    # the sweep must pay the expensive invariants ONCE: one eval-data
    # preparation and one state-skeleton init across every checkpoint
    # (the reference's eval.sh pays both per checkpoint, eval.sh:1-9)
    prep_calls, init_calls = [], []
    real_prep = runtime.prepare_eval_data
    real_init = runtime.create_train_state
    monkeypatch.setattr(
        runtime, "prepare_eval_data",
        lambda *a, **k: (prep_calls.append(1), real_prep(*a, **k))[1],
    )
    monkeypatch.setattr(
        runtime, "create_train_state",
        lambda *a, **k: (init_calls.append(1), real_init(*a, **k))[1],
    )
    # a third checkpoint so the sweep is N=3 (save_period=3 over 6 steps
    # leaves two; clone the last as step 9)
    import shutil

    shutil.copy(
        os.path.join(config.save_dir, "6.npz"),
        os.path.join(config.save_dir, "9.npz"),
    )
    sweep = runtime.evaluate_sweep(config)
    assert sorted(sweep) == [3, 6, 9]
    for step, scores in sweep.items():
        assert "Bleu_4" in scores
        assert os.path.exists(os.path.join(config.save_dir, f"{step}.txt"))
    # the cloned checkpoint must score identically to its source
    assert sweep[9] == sweep[6]
    assert len(prep_calls) == 1, "eval data re-prepared per checkpoint"
    assert len(init_calls) == 1, "state skeleton re-initialized per checkpoint"


def test_preempt_and_resume_equals_uninterrupted(coco_fixture, tmp_path):
    """Kill-and-resume: a run preempted mid-epoch (after a checkpoint) and
    resumed must produce bitwise the params of an uninterrupted run.  Batch
    order is a pure function of (seed, epoch) and dropout keys of the global
    step, so the resumed run replays the identical sequence — the
    checkpoint cursor story VERDICT r1 item 9 asks to prove."""
    base = coco_fixture["config"].replace(**SMALL_MODEL)

    # uninterrupted oracle: 2 epochs (24 anns / batch 4 = 6 steps/epoch)
    cfg_full = base.replace(
        num_epochs=2,
        save_dir=str(tmp_path / "full"), summary_dir=str(tmp_path / "fs"),
    )
    want = runtime.train(cfg_full)
    assert int(want.step) == 12

    # preempted run: hard-stopped mid-epoch-2 at step 8 (save on exit)
    cfg_a = base.replace(
        num_epochs=2, max_steps=8,
        save_dir=str(tmp_path / "resume"), summary_dir=str(tmp_path / "rs"),
    )
    state_a = runtime.train(cfg_a)
    assert int(state_a.step) == 8
    assert latest_checkpoint(cfg_a.save_dir).endswith("8.npz")

    # resume in a FRESH process-equivalent: new state skeleton, restore,
    # continue to completion
    cfg_b = cfg_a.replace(max_steps=0)
    state_b = runtime.setup_state(cfg_b, load=True)
    assert int(state_b.step) == 8
    state_b = runtime.train(cfg_b, state=state_b)
    assert int(state_b.step) == 12

    from sat_tpu.train.checkpoint import state_to_flat

    got, ref = state_to_flat(state_b), state_to_flat(want)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)


def test_dataset_seek_replays_exact_sequence(coco_fixture):
    """DataSet.seek(e, b) must reproduce the tail of epoch e exactly as an
    uninterrupted pass over that epoch produced it."""
    from sat_tpu.data.dataset import prepare_train_data

    config = coco_fixture["config"]
    ds = prepare_train_data(config)
    orders = []
    for _ in range(3):  # epochs 0..2 as a fresh run sees them
        epoch_files = []
        for batch in ds:
            epoch_files.append(tuple(batch[0]))
        orders.append(epoch_files)
    assert orders[0] != orders[1]  # shuffling actually happens

    ds2 = prepare_train_data(config)
    ds2.seek(1, 2)  # resume mid-epoch-1 at batch 2
    replay = [tuple(b[0]) for b in ds2]
    assert replay == orders[1][2:]
    # and the following epoch continues the same sequence
    assert [tuple(b[0]) for b in ds2] == orders[2]


def test_train_with_profiler_and_var_stats(coco_fixture, tmp_path):
    """Profiler trace + per-variable stats hooks (SURVEY.md §5 tracing)."""
    config = coco_fixture["config"].replace(
        **{**SMALL_MODEL,
           "save_dir": str(tmp_path / "models"),
           "summary_dir": str(tmp_path / "summary"),
           "var_summary_period": 3,
           "profile_dir": str(tmp_path / "profile"),
           "profile_start_step": 1,
           "profile_num_steps": 2}
    )
    runtime.train(config)
    # a trace directory with at least one artifact was produced
    produced = []
    for root, _, files in os.walk(tmp_path / "profile"):
        produced += files
    assert produced, "no profiler trace written"
    # variable stats rows present at the configured cadence
    rows = [
        json.loads(line)
        for line in open(os.path.join(config.summary_dir, "metrics.jsonl"))
    ]
    stat_rows = [r for r in rows if any(k.startswith("params/") for k in r)]
    assert {r["step"] for r in stat_rows} == {3, 6}
    # attention stats ride along with normal metrics
    assert any("attention/mean" in r for r in rows)


def test_empty_dataset_raises_clear_error(coco_fixture, tmp_path):
    """All captions filtered out (max_caption_length below every fixture
    caption) must fail with a diagnosis, not ZeroDivisionError deep in the
    resume fast-forward.  Own cache paths: the session fixture's
    anns.csv/data.npy were tokenized under the default caption length and
    would bypass the cap-length filter entirely."""
    from sat_tpu import runtime

    cfg = coco_fixture["config"].replace(
        max_caption_length=2,
        vocabulary_file=str(tmp_path / "vocab.csv"),
        temp_annotation_file=str(tmp_path / "anns.csv"),
        temp_data_file=str(tmp_path / "data.npy"),
    )
    with pytest.raises(ValueError, match="filtered out"):
        runtime.train(cfg)


def test_quality_run_loss_curve_keeps_final_segment(tmp_path):
    """The committed-evidence loss curve must come from the FINAL run when
    an earlier run appended to the same metrics.jsonl (step reset marks
    the boundary)."""
    import json as _json
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    from quality_run import read_loss_curve

    p = tmp_path / "metrics.jsonl"
    rows = [{"step": s, "total_loss": 3.0} for s in (10, 140, 400)]
    rows += [{"step": s, "total_loss": 2.0} for s in range(10, 1210, 10)]
    p.write_text("".join(_json.dumps(r) + "\n" for r in rows))
    steps = [s for s, _ in read_loss_curve(str(p))]
    assert steps[-1] == 1200
    assert all(b > a for a, b in zip(steps, steps[1:]))
    assert all(loss == 2.0 for _, loss in read_loss_curve(str(p)))


def test_cli_accepts_reference_misspelled_keys():
    """The reference's config attributes are literally typo'd
    (num_initalize_layers, /root/reference/config.py:12-13); its users'
    override lists must port verbatim."""
    config, _ = build_config(
        ["--set", "num_initalize_layers=1", "--set", "dim_initalize_layer=64"]
    )
    assert config.num_initialize_layers == 1
    assert config.dim_initialize_layer == 64


def test_cli_print_config(capsys):
    from sat_tpu.cli import main

    assert main(["--print_config", "--set", "batch_size=11"]) == 0
    cfg = json.loads(capsys.readouterr().out)
    assert cfg["batch_size"] == 11
    assert cfg["cnn"] == "vgg16"


class TestProgress:
    """Per-batch progress reporting (reference tqdm parity,
    base_model.py:49-50,82,131)."""

    def test_non_tty_prints_every_n_and_final(self):
        import io

        from sat_tpu.utils.progress import Progress

        out = io.StringIO()  # StringIO.isatty() is False
        with Progress(10, desc="epoch 1/3", stream=out, every=4) as bar:
            for _ in range(10):
                bar.update()
        lines = out.getvalue().strip().splitlines()
        assert lines[0].startswith("epoch 1/3: 4/10")
        assert lines[1].startswith("epoch 1/3: 8/10")
        assert lines[-1].startswith("epoch 1/3: 10/10")
        assert len(lines) == 3  # no duplicate final line, no spam

    def test_non_tty_no_duplicate_when_total_on_cadence(self):
        import io

        from sat_tpu.utils.progress import Progress

        out = io.StringIO()
        with Progress(8, stream=out, every=4) as bar:
            for _ in range(8):
                bar.update()
        lines = out.getvalue().strip().splitlines()
        assert len(lines) == 2  # 4/8 and 8/8 — close() adds nothing

    def test_tty_redraws_one_line(self):
        import io

        from sat_tpu.utils.progress import Progress

        class Tty(io.StringIO):
            def isatty(self):
                return True

        out = Tty()
        with Progress(5, desc="d", stream=out, min_interval_s=0.0) as bar:
            for _ in range(5):
                bar.update()
        v = out.getvalue()
        assert v.count("\r") == 6  # 5 redraws + final
        assert v.endswith("d: 5/5 " + v[v.rindex("["):])  # final line present
        assert "\n" in v  # close() terminates the bar line

    def test_track_wraps_iterables(self):
        import io

        from sat_tpu.utils.progress import track

        out = io.StringIO()
        seen = list(track(range(6), 6, desc="t", stream=out, every=2))
        assert seen == list(range(6))
        assert "t: 6/6" in out.getvalue()


def test_eval_decode_with_profiler_window(coco_fixture, tmp_path):
    """decode_dataset honors the same profiler knobs as train: an eval run
    with profile_dir set produces a trace over the decode loop."""
    config = coco_fixture["config"].replace(
        **{**SMALL_MODEL,
           "save_dir": str(tmp_path / "models"),
           "summary_dir": str(tmp_path / "summary"),
           "eval_result_file": str(tmp_path / "results.json"),
           "num_epochs": 1}
    )
    state = runtime.train(config)
    # profile_start_step left at its train default (5), far past this
    # tiny eval's batch count — the decode window must clamp and still fire
    cfg_prof = config.replace(
        profile_dir=str(tmp_path / "eval_profile"),
        profile_num_steps=1,
    )
    runtime.evaluate(cfg_prof, state=state)
    produced = []
    for root, _, files in os.walk(tmp_path / "eval_profile"):
        produced += files
    assert produced, "no eval profiler trace written"


def test_config_seed_controls_the_run(coco_fixture, tmp_path):
    """config.seed drives param init, the dropout key stream, and the
    shuffle order end-to-end: identical seeds reproduce the trained
    params bitwise, a different seed diverges.  (The reference exposes no
    seed control at all.)"""
    import jax.tree_util as jtu

    def run(seed, tag):
        cfg = coco_fixture["config"].replace(
            **{**SMALL_MODEL,
               "seed": seed,
               "max_steps": 3,
               "save_dir": str(tmp_path / f"m{tag}"),
               "summary_dir": str(tmp_path / f"s{tag}")}
        )
        return runtime.train(cfg)

    a = run(7, "a")
    b = run(7, "b")
    c = run(8, "c")
    flat_a = jtu.tree_leaves(a.params)
    flat_b = jtu.tree_leaves(b.params)
    flat_c = jtu.tree_leaves(c.params)
    for xa, xb in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    assert any(
        not np.array_equal(np.asarray(xa), np.asarray(xc))
        for xa, xc in zip(flat_a, flat_c)
    )


def test_sigkill_and_cli_resume_bitwise_matches_control(coco_fixture, tmp_path):
    """The preemption story with a REAL process kill (VERDICT r03 #8): a
    CLI training child is SIGKILLed mid-epoch — past at least one ASYNC
    checkpoint, possibly mid-write — then relaunched with --load.  The
    continued run's per-step metrics and final checkpoint must bitwise
    match an uninterrupted control.  (Capability exceeded: the reference
    resumes at its last save but loses the mid-epoch cursor entirely,
    /root/reference/base_model.py:257-278.)"""
    import signal
    import subprocess
    import sys
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = coco_fixture["config"].replace(
        **{**SMALL_MODEL,
           "num_epochs": 2, "save_period": 2, "async_checkpoint": True,
           "save_dir": str(tmp_path / "models"),
           "summary_dir": str(tmp_path / "summary")}
    )
    cfg_path = tmp_path / "config.json"
    cfg.save(str(cfg_path))

    # the child pins jax to CPU and enters the real CLI, which switches
    # the persistent compile cache on itself
    child_code = (
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"sys.path.insert(0, {repo!r})\n"
        "from sat_tpu import cli\n"
        "sys.exit(cli.main(sys.argv[1:]))\n"
    )

    import threading

    def launch(*extra):
        # drain stdout concurrently: a child blocked on a full stdout
        # pipe (the XLA cache loader alone writes tens of KB of
        # warnings) would never reach the checkpoint the kill waits for
        proc = subprocess.Popen(
            [sys.executable, "-u", "-c", child_code,
             "--phase=train", "--config", str(cfg_path), *extra],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=repo, start_new_session=True,
        )
        chunks = []

        def drain():
            for line in proc.stdout:
                chunks.append(line)

        threading.Thread(target=drain, daemon=True).start()
        return proc, chunks

    # 24 anns / batch 4 = 6 steps/epoch, 12 total; checkpoints at 2,4,...
    victim, victim_out = launch()
    deadline = time.time() + 420
    try:
        # kill once a mid-epoch async checkpoint (step 4) has landed —
        # the writer may be mid-write on the NEXT one, which must not
        # corrupt the resume (atomic rename)
        while time.time() < deadline:
            if victim.poll() is not None:
                out = "".join(victim_out)
                raise AssertionError(f"child exited early rc={victim.returncode}\n{out[-3000:]}")
            if os.path.exists(os.path.join(cfg.save_dir, "4.npz")):
                break
            time.sleep(0.2)
        else:
            raise AssertionError("child never reached checkpoint step 4")
        os.killpg(victim.pid, signal.SIGKILL)
    finally:
        victim.wait()

    latest = latest_checkpoint(cfg.save_dir)
    killed_at = int(os.path.basename(latest).split(".")[0])
    assert killed_at >= 4 and killed_at < 12

    resumed, resumed_out = launch("--load")
    try:
        assert resumed.wait(timeout=420) == 0, "".join(resumed_out)[-3000:]
    finally:
        if resumed.poll() is None:  # hung: don't leak a detached trainer
            os.killpg(resumed.pid, signal.SIGKILL)
            resumed.wait()
    assert latest_checkpoint(cfg.save_dir).endswith("12.npz")

    # uninterrupted control, in-process (same seed, fresh dirs)
    ctl = cfg.replace(
        save_dir=str(tmp_path / "ctl_models"),
        summary_dir=str(tmp_path / "ctl_summary"),
        async_checkpoint=False,
    )
    want_state = runtime.train(ctl)
    assert int(want_state.step) == 12

    # final checkpoints bitwise equal
    got = dict(np.load(os.path.join(cfg.save_dir, "12.npz")))
    want = dict(np.load(os.path.join(ctl.save_dir, "12.npz")))
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)

    # the resumed run's metrics rows (steps after the kill) bitwise match
    # the control's rows for the same steps — same batches, same losses
    def metrics(d):
        return {
            r["step"]: r for r in (
                json.loads(line)
                for line in open(os.path.join(d, "metrics.jsonl"))
            )
        }

    got_rows, want_rows = metrics(cfg.summary_dir), metrics(ctl.summary_dir)
    resumed_steps = [s for s in sorted(got_rows) if s > killed_at]
    assert resumed_steps and resumed_steps[-1] == 12
    for s in resumed_steps:
        for key in ("total_loss", "cross_entropy_loss", "accuracy"):
            assert got_rows[s][key] == want_rows[s][key], (s, key)
