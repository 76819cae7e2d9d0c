"""Bulk offline captioning subsystem tests (docs/BULK.md).

Pins the contracts the bulk ISSUE promises:

* corpus resolution — directory walk (non-image files skipped with a
  named counter, never a crash) and file-list mode, both yielding a
  deterministic sorted corpus, sharded purely by position;
* the resume manifest — atomic round-trip, torn-write tolerance,
  corpus fingerprint sensitivity (files / shard rows / image size, and
  deliberately NOT chip count — elastic resume);
* the sharded JSONL writer — crc32c sidecars, tamper detection, tmp
  orphans from a mid-shard kill never surviving into outputs;
* crash-only resume — completed shards are verified and skipped, a
  missing / torn / corrupt shard is re-decoded, and the final output
  bytes are identical to an uninterrupted run (kill between shards and
  mid-shard both);
* quarantine containment — a poison image is ledgered and substituted
  with a shard-deterministic healthy row, the marker carries no
  run-dependent detail, and a ledger replay reproduces the bytes;
* zero steady-state recompiles across a multi-shard run;
* the ``--phase bulk`` CLI end-to-end.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from sat_tpu import telemetry
from sat_tpu.bulk import corpus as bulk_corpus
from sat_tpu.bulk import manifest as bulk_manifest
from sat_tpu.bulk import writer as bulk_writer
from sat_tpu.bulk.corpus import CorpusError, plan_shards, resolve_corpus
from sat_tpu.bulk.manifest import (
    corpus_fingerprint,
    load_manifest,
    manifest_path_for,
    mark_completed,
    new_manifest,
    write_manifest,
)
from sat_tpu.bulk.writer import (
    ShardWriter,
    encode_row,
    shard_filename,
    sidecar_path,
    verify_shard,
)
from sat_tpu.data.images import walk_images

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Corpus resolution (jax-free)
# ---------------------------------------------------------------------------


def _touch(path, data=b"x"):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(data)


def test_walk_images_skips_nonimage_files_with_counter(tmp_path):
    root = str(tmp_path)
    _touch(os.path.join(root, "a.jpg"))
    _touch(os.path.join(root, "sub", "b.PNG"))
    _touch(os.path.join(root, "sub", "notes.txt"))
    _touch(os.path.join(root, "README.md"))
    _touch(os.path.join(root, "c.webp"))
    tel = telemetry.enable()
    try:
        found = walk_images(root)
        assert [os.path.basename(f) for f in found] == ["a.jpg", "c.webp", "b.PNG"]
        assert all(os.path.isabs(f) for f in found)
        assert tel.counters().get("data/skipped_nonimage") == 2
    finally:
        telemetry.disable()


def test_walk_images_order_is_deterministic(tmp_path):
    root = str(tmp_path)
    for name in ("z/1.jpg", "a/2.jpg", "m.jpeg"):
        _touch(os.path.join(root, name))
    assert walk_images(root) == sorted(walk_images(root))
    assert walk_images(root) == walk_images(root)


def test_resolve_corpus_directory_and_empty(tmp_path):
    _touch(str(tmp_path / "x.bmp"))
    assert resolve_corpus(str(tmp_path)) == [str(tmp_path / "x.bmp")]
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(CorpusError):
        resolve_corpus(str(empty))
    with pytest.raises(CorpusError):
        resolve_corpus(str(tmp_path / "nonexistent"))


def test_resolve_corpus_file_list(tmp_path):
    _touch(str(tmp_path / "imgs" / "b.jpg"))
    _touch(str(tmp_path / "imgs" / "a.jpg"))
    listing = tmp_path / "corpus.txt"
    listing.write_text(
        "# a comment\n"
        "imgs/b.jpg\n"
        "\n"
        f"{tmp_path}/imgs/a.jpg\n"
        "imgs/b.jpg\n"  # duplicate collapses
    )
    files = resolve_corpus(str(listing))
    assert files == [str(tmp_path / "imgs" / "a.jpg"),
                     str(tmp_path / "imgs" / "b.jpg")]


def test_plan_shards_remainder_and_validation():
    files = [f"{i}.jpg" for i in range(10)]
    shards = plan_shards(files, 4)
    assert [len(s) for s in shards] == [4, 4, 2]
    assert sum(shards, []) == files  # positional, order-preserving
    assert plan_shards([], 4) == []
    with pytest.raises(ValueError):
        plan_shards(files, 0)


# ---------------------------------------------------------------------------
# Manifest (jax-free)
# ---------------------------------------------------------------------------


FILES = [f"/corpus/{i:03d}.jpg" for i in range(7)]


def test_manifest_round_trip(tmp_path):
    path = manifest_path_for(str(tmp_path))
    m = new_manifest(FILES, 3, 32)
    mark_completed(m, 0, shard_filename(0), 3, 1234)
    write_manifest(path, m)
    loaded = load_manifest(path)
    assert loaded == m
    assert loaded["completed"]["0"] == {
        "file": "captions_00000.jsonl", "rows": 3, "crc32c": 1234,
    }
    assert loaded["num_shards"] == 3 and loaded["total_images"] == 7


def test_manifest_torn_write_returns_none(tmp_path):
    path = manifest_path_for(str(tmp_path))
    write_manifest(path, new_manifest(FILES, 3, 32))
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: len(data) // 2])  # torn tail
    assert load_manifest(path) is None
    assert load_manifest(str(tmp_path / "missing.json")) is None


def test_manifest_rejects_foreign_or_bogus_payloads(tmp_path):
    path = str(tmp_path / "m.json")
    for payload in (
        {"format": 999, "completed": {}},
        {"format": 1, "completed": {"x": {"file": "f", "rows": 1, "crc32c": 2}}},
        {"format": 1, "completed": {"0": {"rows": 1}}},
        {"format": 1, "completed": [1, 2]},
        [1, 2, 3],
    ):
        with open(path, "w") as f:
            json.dump(payload, f)
        assert load_manifest(path) is None, payload


def test_fingerprint_tracks_corpus_geometry_not_chips():
    base = corpus_fingerprint(FILES, 3, 32)
    assert base == corpus_fingerprint(FILES, 3, 32)  # pure
    assert base != corpus_fingerprint(FILES[:-1], 3, 32)
    assert base != corpus_fingerprint(FILES, 4, 32)
    assert base != corpus_fingerprint(FILES, 3, 64)
    # by construction the fingerprint has no device/topology input: a
    # resume after a chip-count change must keep the same frontier
    import inspect

    assert "device" not in inspect.getsource(corpus_fingerprint)


# ---------------------------------------------------------------------------
# Shard writer + verification (jax-free)
# ---------------------------------------------------------------------------


ROWS = [
    {"file": "/corpus/a.jpg", "captions": [{"caption": "a dog", "prob": 0.5}]},
    {"file": "/corpus/b.jpg", "captions": [], "quarantined": True},
]


def _write_shard(out_dir, idx=0, rows=ROWS):
    w = ShardWriter(out_dir, idx)
    for r in rows:
        w.write_row(r)
    return w.finish()


def test_shard_writer_round_trip_and_verify(tmp_path):
    fname, rows, crc = _write_shard(str(tmp_path))
    assert fname == "captions_00000.jsonl" and rows == 2
    path = os.path.join(str(tmp_path), fname)
    assert verify_shard(path)
    assert verify_shard(path, expect_rows=2, expect_crc=crc)
    got = [json.loads(l) for l in open(path)]
    assert got == ROWS
    assert not os.path.exists(path + ".tmp")


def test_encode_row_is_key_order_invariant():
    assert encode_row({"b": 1, "a": 2}) == encode_row({"a": 2, "b": 1})


def test_verify_shard_detects_tamper(tmp_path):
    fname, rows, crc = _write_shard(str(tmp_path))
    path = os.path.join(str(tmp_path), fname)
    data = open(path, "rb").read()
    with open(path, "wb") as f:  # single byte flip
        f.write(data[:5] + bytes([data[5] ^ 1]) + data[6:])
    assert not verify_shard(path)
    with open(path, "wb") as f:
        f.write(data)
    assert verify_shard(path)
    assert not verify_shard(path, expect_rows=rows + 1)
    assert not verify_shard(path, expect_crc=crc ^ 1)
    with open(path, "wb") as f:  # truncated: row + whole-file crc both off
        f.write(data.splitlines(keepends=True)[0])
    assert not verify_shard(path)


def test_verify_shard_requires_intact_sidecar(tmp_path):
    fname, _, _ = _write_shard(str(tmp_path))
    path = os.path.join(str(tmp_path), fname)
    side = sidecar_path(path)
    data = open(side, "rb").read()
    with open(side, "wb") as f:
        f.write(data[: len(data) // 2])
    assert not verify_shard(path)
    os.unlink(side)
    assert not verify_shard(path)


def test_shard_writer_abort_removes_tmp(tmp_path):
    w = ShardWriter(str(tmp_path), 3)
    w.write_row(ROWS[0])
    assert os.path.exists(w.tmp)
    w.abort()
    assert not os.path.exists(w.tmp)
    assert not os.path.exists(w.path)


# ---------------------------------------------------------------------------
# End-to-end runs (jax; tiny trained checkpoint, compiles ride the cache)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bulk_env(coco_fixture, tmp_path_factory):
    """Tiny trained checkpoint + a completed reference bulk run."""
    from sat_tpu import runtime
    from tests.test_runtime import SMALL_MODEL

    root = str(tmp_path_factory.mktemp("bulk"))
    train_config = coco_fixture["config"].replace(
        **SMALL_MODEL,
        save_dir=os.path.join(root, "models"),
        summary_dir=os.path.join(root, "summary"),
    )
    runtime.train(train_config)
    tel = telemetry.enable(capacity=16384)
    runtime._install_compile_listener()
    config = train_config.replace(
        phase="bulk",
        beam_size=2,
        serve_slot_pages=2,
        serve_page_width=2,
        shard_cache="off",
        heartbeat_interval=0.0,
        bulk_input=coco_fixture["train_img_dir"],
        bulk_output=os.path.join(root, "out0"),
        bulk_shard_rows=5,
    )
    from sat_tpu.bulk.runner import run_bulk

    rc = run_bulk(config)
    assert rc == 0
    yield {"config": config, "root": root, "tel": tel, "run_bulk": run_bulk}
    telemetry.disable()


def _outputs(out_dir):
    return {
        f: open(os.path.join(out_dir, f), "rb").read()
        for f in sorted(os.listdir(out_dir))
        if f.startswith("captions_") and not f.endswith(".tmp")
    }


def _clone_output(bulk_env, name):
    """An independent output dir seeded with the reference run's state."""
    dst = os.path.join(bulk_env["root"], name)
    shutil.copytree(bulk_env["config"].bulk_output, dst)
    return bulk_env["config"].replace(bulk_output=dst)


def test_run_bulk_covers_the_corpus(bulk_env):
    config = bulk_env["config"]
    files = resolve_corpus(config.bulk_input)
    blobs = _outputs(config.bulk_output)
    shard_names = [f for f in blobs if f.endswith(".jsonl")]
    assert len(shard_names) == (len(files) + 4) // 5
    rows = [
        json.loads(l)
        for f in shard_names
        for l in blobs[f].decode().splitlines()
    ]
    assert [r["file"] for r in rows] == files  # corpus order, no dup/miss
    assert all(
        r["captions"] and isinstance(r["captions"][0]["caption"], str)
        for r in rows
    )
    m = load_manifest(manifest_path_for(config.bulk_output))
    assert sorted(m["completed"], key=int) == [
        str(i) for i in range(len(shard_names))
    ]
    for k, entry in m["completed"].items():
        assert verify_shard(
            os.path.join(config.bulk_output, entry["file"]),
            expect_rows=entry["rows"],
            expect_crc=entry["crc32c"],
        )


def test_zero_steady_state_recompiles_across_shards(bulk_env):
    gauges = bulk_env["tel"].gauges()
    assert gauges.get("bulk/steady_compiles") == 0
    assert gauges.get("bulk/shards_done", 0) >= 2  # multi-shard run
    assert gauges.get("bulk/images_done") == gauges.get("bulk/images_total")
    assert gauges.get("bulk/decode_steps", 0) > 0


def test_resume_noop_leaves_outputs_untouched(bulk_env):
    config = bulk_env["config"]
    before = _outputs(config.bulk_output)
    mtimes = {
        f: os.stat(os.path.join(config.bulk_output, f)).st_mtime_ns
        for f in before
    }
    assert bulk_env["run_bulk"](config) == 0
    assert _outputs(config.bulk_output) == before
    after = {
        f: os.stat(os.path.join(config.bulk_output, f)).st_mtime_ns
        for f in before
    }
    assert after == mtimes  # verified-complete shards are never rewritten


def test_resume_after_kill_between_shards_is_bitwise(bulk_env):
    reference = _outputs(bulk_env["config"].bulk_output)
    config = _clone_output(bulk_env, "out_between")
    # a kill after shard 0 committed: later shards never happened
    mpath = manifest_path_for(config.bulk_output)
    m = load_manifest(mpath)
    for k in [k for k in m["completed"] if k != "0"]:
        os.unlink(os.path.join(config.bulk_output, m["completed"][k]["file"]))
        os.unlink(
            sidecar_path(
                os.path.join(config.bulk_output, m["completed"][k]["file"])
            )
        )
        del m["completed"][k]
    write_manifest(mpath, m)
    assert bulk_env["run_bulk"](config) == 0
    assert _outputs(config.bulk_output) == reference


def test_resume_after_kill_mid_shard_is_bitwise(bulk_env):
    reference = _outputs(bulk_env["config"].bulk_output)
    config = _clone_output(bulk_env, "out_mid")
    mpath = manifest_path_for(config.bulk_output)
    m = load_manifest(mpath)
    # mid-shard kill: shard 1 has only a torn tmp, no committed file
    entry = m["completed"].pop("1")
    shard = os.path.join(config.bulk_output, entry["file"])
    os.unlink(sidecar_path(shard))
    os.rename(shard, shard + ".tmp")
    with open(shard + ".tmp", "ab") as f:
        f.write(b'{"torn')
    write_manifest(mpath, m)
    assert bulk_env["run_bulk"](config) == 0
    assert _outputs(config.bulk_output) == reference
    assert not os.path.exists(shard + ".tmp")


def test_resume_redecodes_corrupt_committed_shard(bulk_env):
    reference = _outputs(bulk_env["config"].bulk_output)
    config = _clone_output(bulk_env, "out_rot")
    shard = os.path.join(config.bulk_output, shard_filename(0))
    data = open(shard, "rb").read()
    with open(shard, "wb") as f:  # bitrot in a manifest-committed shard
        f.write(data[:3] + bytes([data[3] ^ 0x40]) + data[4:])
    assert bulk_env["run_bulk"](config) == 0
    assert _outputs(config.bulk_output) == reference


def test_corpus_change_restarts_frontier(bulk_env):
    config = _clone_output(bulk_env, "out_refreshed").replace(
        bulk_shard_rows=4
    )  # geometry change == new corpus fingerprint
    assert bulk_env["run_bulk"](config) == 0
    m = load_manifest(manifest_path_for(config.bulk_output))
    files = resolve_corpus(config.bulk_input)
    assert m["corpus_sha"] == corpus_fingerprint(files, 4, config.image_size)
    assert len(m["completed"]) == (len(files) + 3) // 4


def _poisoning(monkeypatch, poisoned_basename):
    """Make ImageLoader.load_raw fail for one corpus file."""
    from sat_tpu.data.images import ImageLoader

    orig = ImageLoader.load_raw

    def load_raw(self, image_file):
        if os.path.basename(image_file) == poisoned_basename:
            raise ValueError(f"poisoned test image {image_file}")
        return orig(self, image_file)

    monkeypatch.setattr(ImageLoader, "load_raw", load_raw)


def test_quarantine_substitution_is_deterministic(bulk_env, monkeypatch):
    config = bulk_env["config"]
    files = resolve_corpus(config.bulk_input)
    victim = os.path.basename(files[2])
    _poisoning(monkeypatch, victim)
    runs = []
    for name in ("poison_a", "poison_b"):
        cfg = config.replace(
            bulk_output=os.path.join(bulk_env["root"], name),
            quarantine_ledger=os.path.join(bulk_env["root"], name + ".jsonl"),
        )
        assert bulk_env["run_bulk"](cfg) == 0
        runs.append((cfg, _outputs(cfg.bulk_output)))
    (cfg_a, blobs_a), (_, blobs_b) = runs
    assert blobs_a == blobs_b  # independent poisoned runs match bitwise
    rows = [
        json.loads(l)
        for f in sorted(blobs_a)
        if f.endswith(".jsonl")
        for l in blobs_a[f].decode().splitlines()
    ]
    marked = [r for r in rows if r.get("quarantined")]
    assert len(marked) == 1 and os.path.basename(marked[0]["file"]) == victim
    # the marker is run-independent: provenance but no detection reason
    assert set(marked[0]) == {"file", "captions", "quarantined",
                              "substituted_from"}
    donor = marked[0]["substituted_from"]
    assert os.path.basename(donor) != victim
    donor_row = [r for r in rows if r["file"] == donor][0]
    assert marked[0]["captions"] == donor_row["captions"]
    ledger = [
        json.loads(l)
        for l in open(os.path.join(bulk_env["root"], "poison_a.jsonl"))
    ]
    assert [os.path.basename(e["file"]) for e in ledger] == [victim]
    assert ledger[0]["reason"] == "decode_failed"


def test_ledger_replay_reproduces_poisoned_bytes(bulk_env, monkeypatch):
    config = bulk_env["config"]
    files = resolve_corpus(config.bulk_input)
    victim = os.path.basename(files[2])
    ledger = os.path.join(bulk_env["root"], "poison_a.jsonl")
    if not os.path.exists(ledger):
        pytest.skip("poisoned reference run did not execute")
    cfg = config.replace(
        bulk_output=os.path.join(bulk_env["root"], "poison_replay"),
        quarantine_ledger=ledger,
    )
    # loader fully healthy this time: the inherited ledger alone must
    # force the same substitution (a repaired file cannot change a replay)
    assert bulk_env["run_bulk"](cfg) == 0
    assert _outputs(cfg.bulk_output) == _outputs(
        os.path.join(bulk_env["root"], "poison_a")
    )


def test_all_rows_poisoned_is_systemic(bulk_env, monkeypatch):
    from sat_tpu.data.images import ImageLoader
    from sat_tpu.resilience.quarantine import SystemicCorruption

    def load_raw(self, image_file):
        raise ValueError("poisoned")

    monkeypatch.setattr(ImageLoader, "load_raw", load_raw)
    cfg = bulk_env["config"].replace(
        bulk_output=os.path.join(bulk_env["root"], "poison_all"),
        quarantine_ledger=os.path.join(bulk_env["root"], "poison_all.jsonl"),
    )
    with pytest.raises(SystemicCorruption):
        bulk_env["run_bulk"](cfg)


def test_run_bulk_requires_output_dir(bulk_env):
    with pytest.raises(ValueError, match="bulk_output"):
        bulk_env["run_bulk"](bulk_env["config"].replace(bulk_output=""))


@pytest.mark.slow
def test_cli_phase_bulk_end_to_end(bulk_env, tmp_path):
    """The full CLI surface in a fresh process: --phase bulk on the
    fixture corpus from a blessed checkpoint, rc 0, verifiable output."""
    config = bulk_env["config"].replace(
        bulk_output=str(tmp_path / "out"), telemetry=True
    )
    cfg_path = str(tmp_path / "bulk.json")
    config.save(cfg_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "sat_tpu.cli", "--config", cfg_path],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert "bulk: complete" in proc.stderr
    m = load_manifest(manifest_path_for(config.bulk_output))
    assert m and len(m["completed"]) == m["num_shards"]
    # a fresh process decodes the same corpus to the same bytes as the
    # in-process reference run (geometry matches: same shard_rows)
    assert _outputs(config.bulk_output) == _outputs(
        bulk_env["config"].bulk_output
    )
