"""Data-plane immune system: record integrity, quarantine, repair.

Covers the detection half (data/integrity.py: crc32c sidecars,
verify-on-gather modes, --repair_shards), the containment half
(resilience/quarantine.py: ledger, deterministic substitution, the
systemic-corruption ceiling and its exit code), the hardened prefetch
path (data/images.py), the satellites (prefetch error context, vocab
compatibility guard, serve bad-input handling), and — as one
subprocess test — the chaos-campaign acceptance e2e and the rows of
its report.

Everything but the campaign test is in-process and jax-free.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import zlib

import numpy as np
import pytest

from sat_tpu import telemetry
from sat_tpu.data import integrity
from sat_tpu.data.integrity import (
    SAMPLE_EVERY,
    VERIFY_MODES,
    crc32c_rows,
    read_row_crcs,
    repair_shards,
    sidecar_path,
    write_row_crcs,
)
from sat_tpu.data.shards import ShardCache, build_shard_cache, cache_dir_for
from sat_tpu.resilience.quarantine import (
    DATA_CORRUPTION_EXIT_CODE,
    MIN_RECORDS_FOR_CEILING,
    QuarantineManager,
    SystemicCorruption,
    ledger_path_for,
)
from sat_tpu.resilience.watchdog import WATCHDOG_EXIT_CODE
from sat_tpu.utils import summary

from tests.fixtures import LEDGER_TRAIN_STEP_MS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StubLoader:
    """Deterministic cv2-free image source keyed on basename."""

    def __init__(self, size: int = 16):
        self.size = size
        self.raw = True
        self.calls: list = []

    def load_raw(self, image_file: str) -> np.ndarray:
        self.calls.append(image_file)
        seed = zlib.crc32(os.path.basename(image_file).encode())
        rng = np.random.default_rng(seed)
        return rng.integers(0, 256, (self.size, self.size, 3), dtype=np.uint8)


def _build_cache(tmp_path, n=10, size=16, rows_per_shard=4):
    files = [str(tmp_path / f"img_{i:03d}.jpg") for i in range(n)]
    loader = StubLoader(size)
    cache_dir = str(tmp_path / "cache")
    build_shard_cache(files, cache_dir, size, rows_per_shard=rows_per_shard,
                      loader=loader)
    return files, loader, cache_dir, ShardCache.open(cache_dir, size)


def _corrupt_row(cache_dir: str, shard: int = 0, row: int = 1) -> None:
    path = os.path.join(cache_dir, f"shard-{shard:05d}.npy")
    mm = np.load(path, mmap_mode="r+")
    mm[row, 0, 0, :] ^= 0xFF
    mm.flush()
    del mm


@pytest.fixture
def tel():
    t = telemetry.enable(capacity=4096)
    yield t
    telemetry.disable()


# ---------------------------------------------------------------------------
# crc32c batching
# ---------------------------------------------------------------------------


def test_crc32c_rows_matches_scalar_oracle(rng):
    # lengths below/above the vectorization threshold, power-of-two
    # lanes, and ragged tails must all agree with the scalar crc
    for L in (1, 16, 1023, 4096, 4097, 12288):
        rows = rng.integers(0, 256, (3, L), dtype=np.uint8)
        got = crc32c_rows(rows)
        want = np.array(
            [summary.crc32c(rows[i].tobytes()) for i in range(3)], np.uint32
        )
        np.testing.assert_array_equal(got, want, err_msg=f"L={L}")
    assert crc32c_rows(np.empty((0, 8), np.uint8)).shape == (0,)


def test_crc32c_rows_accepts_image_shaped_input(rng):
    rows = rng.integers(0, 256, (2, 16, 16, 3), dtype=np.uint8)
    flat = rows.reshape(2, -1)
    np.testing.assert_array_equal(crc32c_rows(rows), crc32c_rows(flat))


# ---------------------------------------------------------------------------
# sidecars
# ---------------------------------------------------------------------------


def test_build_writes_sidecars_matching_shard_bytes(tmp_path):
    _, _, cache_dir, cache = _build_cache(tmp_path)
    shard_files = sorted(
        f for f in os.listdir(cache_dir)
        if f.startswith("shard-") and f.endswith(".npy")
        and not f.endswith(integrity.CRC_SUFFIX)
    )
    assert len(shard_files) == 3  # 10 rows / 4 per shard
    for name in shard_files:
        path = os.path.join(cache_dir, name)
        assert os.path.exists(sidecar_path(path))
        crcs = read_row_crcs(path)
        data = np.asarray(np.load(path, mmap_mode="r"))
        np.testing.assert_array_equal(crcs, crc32c_rows(data))


def test_sidecar_roundtrip_and_missing(tmp_path):
    shard = str(tmp_path / "shard-00000.npy")
    assert read_row_crcs(shard) is None
    crcs = np.array([1, 2, 0xFFFFFFFF], np.uint32)
    assert write_row_crcs(shard, crcs) == sidecar_path(shard)
    np.testing.assert_array_equal(read_row_crcs(shard), crcs)


def test_legacy_cache_sidecar_retrofit(tmp_path):
    files, _, cache_dir, _ = _build_cache(tmp_path, n=4)
    sc = sidecar_path(os.path.join(cache_dir, "shard-00000.npy"))
    os.unlink(sc)  # pretend the cache predates sidecars
    cache = ShardCache.open(cache_dir, 16)
    cache.enable_integrity("full")
    cache.gather(files[:4])  # first verify retrofits the sidecar
    assert os.path.exists(sc)


# ---------------------------------------------------------------------------
# verify-on-gather
# ---------------------------------------------------------------------------


def test_full_mode_detects_and_fallback_recovers(tmp_path, tel):
    files, loader, cache_dir, cache = _build_cache(tmp_path)
    clean = cache.gather(files)
    _corrupt_row(cache_dir, shard=0, row=1)
    cache = ShardCache.open(cache_dir, 16)  # fresh mmaps
    cache.enable_integrity("full")
    bad_rows: list = []
    out = cache.gather(files, fallback=loader.load_raw, bad_rows=bad_rows)
    # the fallback re-decode IS the canonical row: recovery is bitwise
    np.testing.assert_array_equal(out, clean)
    assert bad_rows == []  # fallback succeeded: nothing to quarantine
    counters = tel.counters()
    assert counters.get("data/corrupt_rows", 0) >= 1
    assert counters.get("data/decode_fallback", 0) >= 1


def test_full_mode_without_fallback_raises(tmp_path):
    files, _, cache_dir, _ = _build_cache(tmp_path)
    _corrupt_row(cache_dir)
    cache = ShardCache.open(cache_dir, 16)
    cache.enable_integrity("full")
    with pytest.raises(KeyError, match="crc_mismatch"):
        cache.gather(files)


def test_full_mode_fallback_failure_reports_bad_row(tmp_path):
    files, _, cache_dir, _ = _build_cache(tmp_path)
    _corrupt_row(cache_dir, row=2)
    cache = ShardCache.open(cache_dir, 16)
    cache.enable_integrity("full")

    def broken(_f):
        raise ValueError("decoder down")

    bad_rows: list = []
    out = cache.gather(files, fallback=broken, bad_rows=bad_rows)
    assert len(bad_rows) == 1
    i, f, reason, exc = bad_rows[0]
    assert i == 2 and f == files[2]
    assert reason == "crc_mismatch+live_decode_failed"
    assert isinstance(exc, ValueError)
    assert not out[2].any()  # zero-filled for the quarantine substitution


def test_open_mode_scans_each_shard_once(tmp_path):
    files, loader, cache_dir, _ = _build_cache(tmp_path)
    _corrupt_row(cache_dir, shard=0, row=1)
    cache = ShardCache.open(cache_dir, 16)
    cache.enable_integrity("open")
    bad_rows: list = []
    cache.gather(files, bad_rows=bad_rows)
    assert [(i, r) for i, _, r, _ in bad_rows] == [(1, "crc_mismatch")]
    # shard 0 is now known: later gathers consult the cached bad-row
    # set without re-hashing, and clean shards report nothing
    assert cache.integrity._bad_rows[0] == {1}
    bad_rows2: list = []
    cache.gather(files[4:], bad_rows=bad_rows2)
    assert bad_rows2 == []
    bad_rows3: list = []
    cache.gather([files[1]], bad_rows=bad_rows3)
    assert [(i, r) for i, _, r, _ in bad_rows3] == [(0, "crc_mismatch")]


def test_sample_mode_scrubs_on_cadence(tmp_path, tel):
    files, loader, cache_dir, _ = _build_cache(tmp_path, n=4)
    _corrupt_row(cache_dir, row=0)
    cache = ShardCache.open(cache_dir, 16)
    cache.enable_integrity("sample")
    for _ in range(SAMPLE_EVERY * 2):
        cache.gather([files[0]], fallback=loader.load_raw)
    # single-row batches: the rotating cursor always lands on the bad
    # row, and exactly every SAMPLE_EVERY-th gather pays a verification
    counters = tel.counters()
    assert counters.get("data/corrupt_rows", 0) == 2
    assert counters.get("data/verify_rows", 0) == 2


def test_verify_mode_vocabulary(tmp_path):
    assert VERIFY_MODES == ("off", "sample", "open", "full")
    _, _, _, cache = _build_cache(tmp_path, n=4)
    with pytest.raises(ValueError, match="verify_shards"):
        cache.enable_integrity("sometimes")


def test_config_rejects_bad_integrity_knobs(coco_fixture):
    config = coco_fixture["config"]
    with pytest.raises(ValueError, match="verify_shards"):
        config.replace(verify_shards="sometimes")
    with pytest.raises(ValueError, match="quarantine_max_fraction"):
        config.replace(quarantine_max_fraction=0.0)


# ---------------------------------------------------------------------------
# quarantine ledger
# ---------------------------------------------------------------------------


def test_ledger_roundtrip_dedup_and_torn_tail(tmp_path):
    path = str(tmp_path / "q.jsonl")
    q = QuarantineManager(path)
    q.note_rows(100)
    q.quarantine("/data/b.jpg", "decode_failed", exc=ValueError("boom"))
    q.quarantine("/data/./b.jpg", "decode_failed")  # same file: deduped
    q.quarantine("", "caption_all_oov", kind="caption", pos=(0, 3, 1))
    with open(path) as f:
        entries = [json.loads(line) for line in f]
    assert len(entries) == 2
    assert entries[0]["reason"] == "decode_failed"
    assert entries[0]["error"] == "ValueError: boom"
    assert entries[1]["kind"] == "caption" and entries[1]["pos"] == [0, 3, 1]
    with open(path, "a") as f:
        f.write('{"file": "/torn')  # crash mid-append
    q2 = QuarantineManager(path)
    assert q2.total == 2  # torn tail tolerated, good lines preloaded
    assert q2.known_bad_file("/data/b.jpg")
    assert q2.known_bad_pos(0, 3, 1)
    assert q2.files() == [os.path.normpath("/data/b.jpg")]


def test_ledger_path_for(coco_fixture):
    config = coco_fixture["config"]
    assert ledger_path_for(config) == os.path.join(
        config.summary_dir, "quarantine.jsonl"
    )
    explicit = config.replace(quarantine_ledger="/runs/led.jsonl")
    assert ledger_path_for(explicit) == "/runs/led.jsonl"


def test_ceiling_needs_min_records(tmp_path):
    q = QuarantineManager(str(tmp_path / "q.jsonl"), max_fraction=0.1)
    q.note_rows(4)
    for i in range(MIN_RECORDS_FOR_CEILING - 1):
        q.quarantine(f"/rot/{i}.jpg", "decode_failed")  # sporadic: no abort


def test_ceiling_trips_with_distinct_exit_code(tmp_path):
    assert DATA_CORRUPTION_EXIT_CODE == 87
    assert DATA_CORRUPTION_EXIT_CODE != WATCHDOG_EXIT_CODE
    q = QuarantineManager(str(tmp_path / "q.jsonl"), max_fraction=0.5)
    q.note_rows(10)
    with pytest.raises(SystemicCorruption, match="systemic data corruption"):
        for i in range(MIN_RECORDS_FOR_CEILING + 1):
            q.quarantine(f"/rot/{i}.jpg", "decode_failed")
    # the abort happened ON the tripping quarantine, which was ledgered
    assert q.total == MIN_RECORDS_FOR_CEILING


def test_substitute_index_stable_and_in_range():
    for key in ("image:/a/b.jpg", "caption:0:3:1", ""):
        for n in (1, 2, 7, 64):
            j = QuarantineManager.substitute_index(key, n)
            assert 0 <= j < n
            assert j == QuarantineManager.substitute_index(key, n)


# ---------------------------------------------------------------------------
# hardened prefetch path
# ---------------------------------------------------------------------------


def _fixture_files(coco_fixture):
    d = coco_fixture["train_img_dir"]
    return [os.path.join(d, f) for f in sorted(os.listdir(d))]


def _caption_batch(files, T=6):
    word_idxs = np.tile(np.arange(1, T + 1, dtype=np.int32), (len(files), 1))
    masks = np.ones((len(files), T), np.float32)
    masks[:, -1] = 0.0  # below the overlength threshold
    return (list(files), word_idxs, masks)


def test_prefetch_error_carries_file_and_coordinates(tmp_path):
    from sat_tpu.data.images import ImageLoader, PrefetchDecodeError, PrefetchLoader

    missing = str(tmp_path / "missing.jpg")
    loader = PrefetchLoader(
        [[missing]], ImageLoader(size=16, raw=True), num_workers=1
    )
    with pytest.raises(PrefetchDecodeError) as ei:
        list(loader)
    err = ei.value
    assert err.image_file == missing
    assert err.batch_index == 0 and err.row == 0
    assert isinstance(err.__cause__, FileNotFoundError)
    assert missing in str(err) and "batch 0, row 0" in str(err)


def test_decode_failure_quarantined_and_replay_is_bitwise(
    coco_fixture, tmp_path, monkeypatch
):
    from sat_tpu.data.images import ImageLoader, PrefetchLoader
    from sat_tpu.resilience.faultinject import reset_io_faults

    files = _fixture_files(coco_fixture)
    bad = [f for f in files
           if zlib.crc32(os.path.basename(f).encode()) % 6 == 0]
    assert len(bad) == 1  # SAT_FI_BAD_IMAGE_EVERY=6 poisons one fixture file
    batch_files = [files[0], bad[0], files[1], files[2]]
    ledger = str(tmp_path / "led.jsonl")

    def run_pass():
        loader = PrefetchLoader(
            [_caption_batch(batch_files)],
            ImageLoader(size=32, raw=True),
            num_workers=2,
            quarantine=QuarantineManager(ledger),
        )
        batches = list(loader)
        assert len(batches) == 1
        return batches[0]

    monkeypatch.setenv("SAT_FI_BAD_IMAGE_EVERY", "6")
    b1 = run_pass()
    monkeypatch.delenv("SAT_FI_BAD_IMAGE_EVERY")
    reset_io_faults()

    with open(ledger) as f:
        entries = [json.loads(line) for line in f]
    assert len(entries) == 1
    assert entries[0]["kind"] == "image"
    assert entries[0]["reason"] == "decode_failed"
    assert "injected decode failure" in entries[0]["error"]
    assert entries[0]["file"] == os.path.normpath(bad[0])

    # geometry preserved; the bad row now carries a healthy batchmate
    assert b1["images"].shape == (4, 32, 32, 3)
    assert b1["files"][1] != bad[0] and b1["files"][1] in batch_files

    # replay with the SAME ledger and no fault armed: the known-bad file
    # is substituted proactively (never re-decoded) and the batch is
    # bitwise-identical — and the ledger is not re-appended
    b2 = run_pass()
    assert b2["files"] == b1["files"]
    np.testing.assert_array_equal(b2["images"], b1["images"])
    np.testing.assert_array_equal(b2["word_idxs"], b1["word_idxs"])
    np.testing.assert_array_equal(b2["masks"], b1["masks"])
    with open(ledger) as f:
        assert len(f.readlines()) == 1


def test_caption_anomalies_quarantined_by_position(coco_fixture, tmp_path):
    from sat_tpu.data.images import ImageLoader, PrefetchLoader

    files = _fixture_files(coco_fixture)[:4]
    batch = _caption_batch(files)
    batch[2][1] = 1.0  # row 1: every mask slot set -> overlength
    batch[2][2] = 0.0  # row 2: no valid token -> all-OOV
    ledger = str(tmp_path / "led.jsonl")
    loader = PrefetchLoader(
        [batch], ImageLoader(size=32, raw=True), num_workers=2,
        quarantine=QuarantineManager(ledger),
    )
    out = list(loader)[0]
    with open(ledger) as f:
        entries = [json.loads(line) for line in f]
    assert [(e["kind"], e["reason"], e["pos"]) for e in entries] == [
        ("caption", "caption_overlength", [0, 0, 1]),
        ("caption", "caption_all_oov", [0, 0, 2]),
    ]
    # both rows were substituted wholesale from a healthy batchmate
    for row in (1, 2):
        j = out["files"].index(out["files"][row])
        assert out["files"][row] in (files[0], files[3])
        np.testing.assert_array_equal(out["masks"][row], out["masks"][j])
        assert out["masks"][row, -1] == 0.0


def test_all_rows_bad_is_systemic(coco_fixture, tmp_path):
    from sat_tpu.data.images import ImageLoader, PrefetchLoader

    files = _fixture_files(coco_fixture)[:2]
    batch = _caption_batch(files)
    batch[2][:] = 0.0  # every caption row is anomalous
    loader = PrefetchLoader(
        [batch], ImageLoader(size=32, raw=True), num_workers=2,
        quarantine=QuarantineManager(str(tmp_path / "led.jsonl")),
    )
    with pytest.raises(SystemicCorruption, match="no healthy row"):
        list(loader)


# ---------------------------------------------------------------------------
# --repair_shards
# ---------------------------------------------------------------------------


def test_repair_shards_rebuilds_only_suspects_bitwise(coco_fixture, tmp_path):
    size = 16
    config = coco_fixture["config"].replace(
        image_size=size,
        shard_cache_dir=str(tmp_path / "shards"),
        quarantine_ledger=str(tmp_path / "led.jsonl"),
    )
    files = [str(tmp_path / f"src_{i:03d}.jpg") for i in range(8)]
    loader = StubLoader(size)
    cache_dir = cache_dir_for(config)
    build_shard_cache(files, cache_dir, size, rows_per_shard=4, loader=loader)
    reference_dir = str(tmp_path / "reference")
    build_shard_cache(files, reference_dir, size, rows_per_shard=4,
                      loader=StubLoader(size))

    # shard 0: silent bit-rot; shard 1: a ledger-quarantined source file
    _corrupt_row(cache_dir, shard=0, row=2)
    QuarantineManager(config.quarantine_ledger).quarantine(
        files[5], "decode_failed"
    )
    report = repair_shards(config, loader=loader)
    assert report["shards_rebuilt"] == 2
    assert report["rows_rebuilt"] == 8
    assert report["unrepairable"] == []
    suspects = {s["shard"]: s for s in report["suspect_shards"]}
    assert suspects["shard-00000.npy"]["crc_mismatch_rows"] == [2]
    assert suspects["shard-00001.npy"]["quarantined_files"] == [
        os.path.normpath(files[5])
    ]

    # repaired cache is bitwise-identical to a clean rebuild, sidecars
    # included, and reopens with a consistent manifest
    for name in ("shard-00000.npy", "shard-00001.npy"):
        got = np.load(os.path.join(cache_dir, name))
        want = np.load(os.path.join(reference_dir, name))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            read_row_crcs(os.path.join(cache_dir, name)),
            read_row_crcs(os.path.join(reference_dir, name)),
        )
    cache = ShardCache.open(cache_dir, size)
    cache.enable_integrity("full")
    bad_rows: list = []
    np.testing.assert_array_equal(
        cache.gather(files, bad_rows=bad_rows),
        ShardCache.open(reference_dir, size).gather(files),
    )
    assert bad_rows == []

    # a second repair: the crc-mismatch shard is clean now, but the
    # ledgered file stays suspect (append-only evidence) until the
    # operator clears the ledger — only ITS shard is rebuilt again
    report2 = repair_shards(config, loader=loader)
    assert report2["shards_rebuilt"] == 1
    assert [s["shard"] for s in report2["suspect_shards"]] == [
        "shard-00001.npy"
    ]
    assert report2["suspect_shards"][0]["crc_mismatch_rows"] == []


def test_repair_shards_without_cache_raises(coco_fixture, tmp_path):
    config = coco_fixture["config"].replace(
        shard_cache_dir=str(tmp_path / "nowhere")
    )
    with pytest.raises(FileNotFoundError):
        repair_shards(config, loader=StubLoader())


# ---------------------------------------------------------------------------
# fault injection knobs
# ---------------------------------------------------------------------------


def test_fault_plan_data_knobs(tmp_path, monkeypatch):
    from sat_tpu.resilience.faultinject import (
        FaultPlan,
        consume_caption_fault,
        consume_decode_fault,
        reset_io_faults,
    )

    plan = FaultPlan.from_env({
        "SAT_FI_CORRUPT_SHARD_ROW": "2",
        "SAT_FI_BAD_IMAGE_EVERY": "3",
        "SAT_FI_BAD_CAPTION_AT": "7",
    })
    assert not plan.inert
    assert (plan.corrupt_shard_row, plan.bad_image_every,
            plan.bad_caption_at) == (2, 3, 7)
    assert FaultPlan.from_env({}).inert

    # shard corruption is idempotent: arming it across a restart must
    # not corrupt a second row
    cache_dir = str(tmp_path / "cache")
    build_shard_cache(
        [str(tmp_path / f"f{i}.jpg") for i in range(4)],
        cache_dir, 8, rows_per_shard=4, loader=StubLoader(8),
    )
    armed = FaultPlan.from_env({"SAT_FI_CORRUPT_SHARD_ROW": "1"})
    armed.maybe_corrupt_shard_row(cache_dir)
    once = open(os.path.join(cache_dir, "shard-00000.npy"), "rb").read()
    armed.maybe_corrupt_shard_row(cache_dir)
    twice = open(os.path.join(cache_dir, "shard-00000.npy"), "rb").read()
    assert once == twice

    # decode faults key on the file BASENAME hash: stable under
    # thread-pool reordering and path prefixes
    monkeypatch.setenv("SAT_FI_BAD_IMAGE_EVERY", "6")
    bad = "COCO_fixture_000000000008.jpg"
    assert zlib.crc32(bad.encode()) % 6 == 0
    with pytest.raises(ValueError, match="injected decode failure"):
        consume_decode_fault(f"/anywhere/{bad}")
    consume_decode_fault("/anywhere/COCO_fixture_000000000000.jpg")
    monkeypatch.delenv("SAT_FI_BAD_IMAGE_EVERY")

    monkeypatch.setenv("SAT_FI_BAD_CAPTION_AT", "3")
    reset_io_faults()
    assert [consume_caption_fault() for _ in range(5)] == [
        False, False, True, False, False,
    ]
    monkeypatch.delenv("SAT_FI_BAD_CAPTION_AT")
    reset_io_faults()


# ---------------------------------------------------------------------------
# vocab/checkpoint compatibility guard (satellite)
# ---------------------------------------------------------------------------


def test_vocab_fingerprint_and_restore_guard(tmp_path):
    from sat_tpu.data.vocabulary import Vocabulary, vocab_fingerprint
    from sat_tpu.resilience import lineage
    from sat_tpu.train.checkpoint import VocabMismatchError, _check_vocab

    vocab_file = str(tmp_path / "vocabulary.csv")
    v = Vocabulary(50)
    v.build(["a man rides a horse .", "a dog runs fast .",
             "the horse jumps ."])
    v.save(vocab_file)
    fp = vocab_fingerprint(vocab_file, 50)
    assert set(fp) == {"sha256", "size"} and fp["size"] == len(v.words)
    assert vocab_fingerprint(vocab_file, 50) == fp  # memoized, stable
    assert vocab_fingerprint(str(tmp_path / "absent.csv"), 50) is None

    ckpt = str(tmp_path / "3.npz")
    with open(ckpt, "wb") as f:
        f.write(b"not really a checkpoint")
    lineage.write_sidecar(ckpt, vocab=fp)
    assert lineage.read_sidecar_meta(ckpt)["vocab"] == fp

    _check_vocab(ckpt, fp)  # matching fingerprint: silent
    _check_vocab(ckpt, None)  # run without a fingerprint: checks nothing
    other = {"sha256": "0" * 64, "size": 999}
    with pytest.raises(VocabMismatchError, match=r"vocab mismatch \(got 999"):
        _check_vocab(ckpt, other)

    legacy = str(tmp_path / "6.npz")
    with open(legacy, "wb") as f:
        f.write(b"older checkpoint")
    lineage.write_sidecar(legacy)  # pre-vocab sidecar: nothing recorded
    _check_vocab(legacy, fp)  # and therefore nothing to mismatch


# ---------------------------------------------------------------------------
# serve bad-input handling (satellite)
# ---------------------------------------------------------------------------


def test_serve_rejects_undecodable_post_cleanly(coco_fixture, tel):
    from sat_tpu.serve.server import CaptionServer

    class StubEngine:
        def __init__(self, config):
            self.config = config

        def preprocess(self, body):
            raise ValueError("not a JPEG/PNG")

    config = coco_fixture["config"]
    server = CaptionServer(config, StubEngine(config))
    assert server.handle_caption(b"\xff\xd8garbage")[0] == 503  # not ready
    server._ready = True
    status, payload = server.handle_caption(b"\xff\xd8garbage")
    assert status == 400
    assert payload["error"] == "bad image"
    assert "cannot decode image bytes" in payload["detail"]
    assert tel.counters().get("serve/bad_input", 0) == 1


# ---------------------------------------------------------------------------
# chaos campaign (acceptance e2e)
# ---------------------------------------------------------------------------


def test_chaos_campaign_poison_and_systemic_abort(tmp_path):
    """One command runs the poison e2e (shard rot + decode faults ->
    clean completion, populated ledger, heartbeat gauges, bitwise
    replay) and the systemic-abort scenario (exit 87, supervisor does
    not restart), and its report carries one stamped row per scenario
    plus the pass rate."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("SAT_FI_")}
    report = tmp_path / "chaos_report.json"
    proc = subprocess.run(
        [sys.executable, os.path.join("scripts", "chaos_campaign.py"),
         "--only", "poison_quarantine_replay,systemic_no_restart",
         "--out", str(report), "--workdir", str(tmp_path / "wd")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = json.loads(report.read_text())
    metrics = {r["metric"]: r for r in rows}
    assert metrics["chaos_poison_quarantine_replay"]["value"] == 1.0
    assert metrics["chaos_systemic_no_restart"]["value"] == 1.0
    assert metrics["chaos_pass_rate"]["value"] == 1.0
    assert metrics["chaos_pass_rate"]["scenarios"] == 2
    assert all(
        r["schema_version"] == telemetry.SCHEMA_VERSION for r in rows
    )


def test_sampled_shard_verification_under_one_percent_of_a_step(tmp_path):
    """What `verify_shards=sample` adds to a `ShardCache.gather` (one
    crc32c of one row every SAMPLE_EVERY gathers, amortized): < 1% of
    the train cell's device step."""
    files, _, _, cache = _build_cache(tmp_path, n=16, size=32,
                                      rows_per_shard=16)
    batches = [files[i:i + 4] for i in range(0, len(files), 4)]

    def per_gather_s(mode, iters):
        cache.enable_integrity(mode)
        t0 = time.perf_counter()
        for i in range(iters):
            cache.gather(batches[i % len(batches)])
        return (time.perf_counter() - t0) / iters

    per_gather_s("sample", 64)  # warm: page cache, sidecars
    added = per_gather_s("sample", 256) - per_gather_s("off", 256)
    assert 1e3 * added < 0.01 * LEDGER_TRAIN_STEP_MS
