"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one chip: train -> beam-search eval -> serve
    python chip_smoke.py --chips 4   # four chips: meshes, then four replicas behind the router

Drives the main path once through the entry points a user would call, at
the flagship width (``Config()`` defaults: VGG16, 224 px, N=196, D=512,
512-unit LSTM, vocabulary_size=5000, T=20, bf16 compute) with random
weights made from ``--seed``, on a COCO-format dataset it generates
itself (no network, no git checkout needed).

**Processes.**  The default run is ONE process: it imports jax, checks
that the first device is a TPU before doing anything else, and calls
``sat_tpu.cli.main`` for ``--phase=train``, ``--phase=eval`` and
``--phase=serve`` one after another (the server runs on the main thread
exactly as the CLI runs it; a client thread sends the HTTP requests and
ends it with the SIGTERM an operator would send).  ``--chips 4`` is a
parent that never imports jax and runs two children one after another:
this script again as the mesh child, which holds all four chips and has
exited before ``python -m sat_tpu.cli --phase route`` starts, which
itself holds no chip and gives each of its four replicas one.

Every phase checks what came out by the repo's own means and raises if it
is wrong; the first failure ends the script non-zero with no result line.
Facts worth reading are printed one JSON object per line as they land.
The LAST stdout line is the result and holds nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--cpu-rehearsal`` runs the same control flow at a tiny size on the CPU
(Pallas in interpret mode, ``--chips 4`` on four virtual devices) to find
wrong paths and arguments at no chip time.  It never prints an ``ok``
line: its last line is ``{"rehearsal": "passed", "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# what --cpu-rehearsal shrinks (tests/test_runtime.py:SMALL_MODEL dims);
# the chip run overrides nothing of the model: Config() is the flagship
TINY_MODEL = dict(
    image_size=32, dim_embedding=16, num_lstm_units=16,
    dim_initialize_layer=16, dim_attend_layer=16, dim_decode_layer=32,
    vocabulary_size=128, num_data_workers=2,
    serve_slot_pages=2, serve_page_width=2, encode_cache_mb=4,
    # a 16-unit model learns too slowly at the default rate to say a word
    # in a run this short
    initial_learning_rate=3e-2, num_epochs=20,
)
# the repo's own tolerances: tests/test_parallel.py (mesh vs one device)
# and tests/test_pallas.py (bf16 kernel vs fused_attend_reference)
MESH_RTOL, MESH_ATOL = 2e-4, 2e-5
KERNEL_TOL = dict(alpha=dict(rtol=5e-2, atol=5e-3), ctx=dict(rtol=5e-2, atol=5e-2))


def emit(phase: str, **facts: Any) -> None:
    """One fact line on stdout (never the last line)."""
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# set-up shared by both modes (no jax)
# ---------------------------------------------------------------------------


def build_workdir(root: str, seed: int, rehearsal: bool) -> str:
    """Generate the dataset and write the run's Config; returns its path.
    The vocabulary is built by ``--phase=train`` from these captions."""
    from sat_tpu.config import Config
    from tests.fixtures import make_coco_fixture

    size = TINY_MODEL["image_size"] if rehearsal else Config().image_size
    batch = 4 if rehearsal else 32
    fx = make_coco_fixture(
        os.path.join(root, "coco"), num_images=2 * batch, image_size=size,
        seed=seed,
    )
    settings = dict(
        vocabulary_size=Config().vocabulary_size,
        seed=seed,
        batch_size=batch,           # 2 captions per image: 4 steps per epoch
        # 240 steps: at the default learning rate the best beam stops
        # being the bare terminator (an empty caption) after about 160
        num_epochs=60,
        log_every=1,                # every step's loss reaches metrics.jsonl
        save_period=80,
        max_eval_ann_num=None,      # eval decodes every image: 2 batches
        shard_cache_dir=os.path.join(root, "shards"),
    )
    if rehearsal:
        settings.update(TINY_MODEL)
    config = fx["config"].replace(**settings)
    path = os.path.join(root, "config.json")
    config.save(path)
    emit("setup", workdir=root, images=2 * batch, image_size=size,
         batch_size=batch, seed=seed)
    return path


def http(method: str, port: int, path: str, body: Optional[bytes] = None,
         timeout: float = 120.0):
    """(status, parsed JSON) of one request to a local server."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method=method
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def wait_ready(port: int, timeout_s: float, alive=lambda: True,
               ready=lambda payload: payload.get("ready")) -> float:
    """Seconds until ``/healthz`` answers 200 and ``ready(payload)``."""
    from sat_tpu.serve.replica import Endpoint, probe_health

    endpoint = Endpoint("smoke", "127.0.0.1", port)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout_s:
        check(alive(), f"server on port {port} died while booting")
        payload = probe_health(endpoint, timeout_s=5.0)
        if payload and payload["_status_code"] == 200 and ready(payload):
            return time.perf_counter() - t0
        time.sleep(0.5)
    raise TimeoutError(f"port {port} not ready after {timeout_s:.0f}s")


def free_port_run(n: int) -> int:
    """The first of ``n`` consecutive ports that are free right now (the
    router's local fleet binds base..base+n-1)."""
    import socket

    from sat_tpu.serve.replica import free_port

    for _ in range(50):
        base = free_port()
        try:
            for port in range(base + 1, base + n):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", port))
        except OSError:
            continue
        return base
    raise RuntimeError(f"no run of {n} free ports found")


def caption(port: int, jpeg: bytes) -> Dict[str, Any]:
    t0 = time.perf_counter()
    status, payload = http("POST", port, "/caption", jpeg)
    check(status == 200, f"/caption answered {status}: {payload}")
    text = payload["captions"][0]["caption"]
    check(bool(text.strip()), f"empty caption in {payload}")
    return {"caption": text, "ms": round(1e3 * (time.perf_counter() - t0), 1)}


def jpegs(config, n: int) -> List[bytes]:
    files = sorted(os.listdir(config.eval_image_dir))[:n]
    out = []
    for name in files:
        with open(os.path.join(config.eval_image_dir, name), "rb") as f:
            out.append(f.read())
    return out


# ---------------------------------------------------------------------------
# jax-side helpers (one-chip run and the mesh child)
# ---------------------------------------------------------------------------


class CompileMeter:
    """Compile seconds and persistent-cache hits/misses since the last
    ``take()``, fed by jax.monitoring (listeners cannot be removed, so one
    instance serves the whole process)."""

    def __init__(self) -> None:
        from jax import monitoring

        self._n = {"hits": 0, "misses": 0, "compile_s": 0.0}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._n["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._n["misses"] += 1

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._n["compile_s"] += seconds

    def take(self) -> Dict[str, Any]:
        n, self._n = self._n, {"hits": 0, "misses": 0, "compile_s": 0.0}
        return {
            "compile_s": round(n["compile_s"], 2),
            "cache_hits": n["hits"], "cache_misses": n["misses"],
            # cold: something new was compiled and written; warm: all of
            # it was read back (programs under the cache's 0.5 s floor
            # count as neither)
            "cache": ("cold" if n["misses"] else "warm" if n["hits"] else "unused"),
        }


def device_facts() -> Dict[str, Any]:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> Optional[int]:
    import jax

    stats = jax.devices()[0].memory_stats()   # None on the CPU
    return int(stats["peak_bytes_in_use"]) if stats else None


def require_tpu(rehearsal: bool, count: int) -> Dict[str, Any]:
    """Fail before doing anything unless jax's first device is a TPU (the
    rehearsal instead refuses to run on one)."""
    facts = device_facts()
    if rehearsal:
        check(facts["platform"] == "cpu",
              "--cpu-rehearsal is for the CPU; run without it on a chip")
    else:
        check(facts["platform"] == "tpu",
              f"no accelerator: jax's first device is {facts}")
    check(facts["count"] >= count,
          f"need {count} device(s), jax reports {facts['count']}")
    return facts


# ---------------------------------------------------------------------------
# one chip: train -> eval -> serve, all in this process
# ---------------------------------------------------------------------------


def phase_train(cfg_path: str, meter: CompileMeter) -> None:
    import numpy as np

    from sat_tpu import cli, telemetry
    from sat_tpu.config import Config
    from sat_tpu.resilience import lineage

    config = Config.load(cfg_path)
    t0 = time.perf_counter()
    rc = cli.main(["--phase=train", "--config", cfg_path, "--telemetry"])
    wall = time.perf_counter() - t0
    check(rc == 0, f"--phase=train exited {rc}")

    step_ms = telemetry.get().durations_ns("train/step") / 1e6
    with open(os.path.join(config.summary_dir, "metrics.jsonl")) as f:
        losses = [json.loads(line)["total_loss"] for line in f]
    check(len(losses) >= 3, f"trained {len(losses)} steps, need >= 3")
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    check(losses[-1] != losses[0], f"loss never moved: {losses}")
    ckpt = lineage.last_good_checkpoint(config.save_dir)
    check(ckpt is not None, "no verifiable LAST_GOOD checkpoint was written")
    emit(
        "train", steps=len(losses), batch_size=config.batch_size,
        wall_s=round(wall, 1),
        first_step_ms=round(float(step_ms[0]), 1),   # compile included
        steady_step_ms_median=round(float(np.median(step_ms[1:])), 2),
        steady_step_ms_max=round(float(step_ms[1:].max()), 1),  # a checkpoint step
        loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
        loss_falling=losses[-1] < losses[0],
        checkpoint=os.path.basename(ckpt), peak_bytes_in_use=peak_bytes(),
        **meter.take(),
    )


def phase_eval(cfg_path: str, meter: CompileMeter, rehearsal: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sat_tpu import cli, runtime, telemetry
    from sat_tpu.config import Config
    from sat_tpu.data.vocabulary import Vocabulary
    from sat_tpu.ops.beam_search import beam_search_jit
    from sat_tpu.ops.pallas_attention import (
        fused_attend,
        fused_attend_reference,
    )

    config = Config.load(cfg_path).replace(phase="eval", beam_size=3)
    t0 = time.perf_counter()
    rc = cli.main(
        ["--phase=eval", "--beam_size=3", "--config", cfg_path, "--telemetry"]
    )
    wall = time.perf_counter() - t0
    check(rc == 0, f"--phase=eval exited {rc}")
    with open(config.eval_result_file) as f:
        results = json.load(f)
    check(len(results) == 2 * config.batch_size,
          f"{len(results)} eval captions, expected {2 * config.batch_size}")
    check(all(r["caption"].strip() for r in results), "an eval caption is empty")
    batch_ms = telemetry.get().durations_ns("decode/batch") / 1e6
    emit(
        "eval", images=len(results), beam_size=3, wall_s=round(wall, 1),
        decode_batch_ms=[round(float(x), 1) for x in batch_ms],
        sample_caption=results[0]["caption"],
        peak_bytes_in_use=peak_bytes(), **meter.take(),
    )

    # the beam program eval just ran, lowered again from the same
    # arguments: a silent detour to the XLA attention branch shows here
    state = runtime.setup_state(config, load=True)
    vocabulary = Vocabulary(config.vocabulary_size, config.vocabulary_file)
    B, N, D = config.batch_size, config.num_ctx, config.dim_ctx
    program = beam_search_jit.lower(
        state.params["decoder"], config,
        jax.ShapeDtypeStruct((B, N, D), jnp.float32),
        vocabulary.word2idx["."], beam_size=3,
        valid_size=len(vocabulary.words), return_alphas=False,
    ).compile()
    has_kernel = "tpu_custom_call" in program.as_text()
    check(has_kernel or rehearsal,
          "no tpu_custom_call in the compiled beam search: the Pallas "
          "kernel was not taken")

    # kernel vs its plain-XLA twin on the same inputs, on this device, at
    # the beam program's shapes (B per-image grids, 3 beam rows an image)
    rng = np.random.default_rng(config.seed)
    rows, da = 3 * B, config.dim_attend_layer
    t1 = jnp.tanh(jnp.asarray(rng.normal(size=(B, N, da)), jnp.float32))
    t2 = jnp.tanh(jnp.asarray(rng.normal(size=(rows, da)), jnp.float32))
    w2 = jnp.asarray(0.08 * rng.normal(size=(da, 1)), jnp.float32)
    ctx = jnp.abs(jnp.asarray(rng.normal(size=(B, N, D)), jnp.float32))
    got = fused_attend(
        t1, t2, w2, ctx, compute_dtype=config.compute_dtype,
        interpret=rehearsal,
    )
    want = fused_attend_reference(
        t1, t2, w2, ctx, compute_dtype=config.compute_dtype
    )
    errs = {}
    for name, g, w in zip(("ctx", "alpha"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        check(bool(np.isfinite(g).all()), f"kernel {name} is not finite")
        errs[name] = float(np.abs(g - w).max())
        np.testing.assert_allclose(g, w, err_msg=name, **KERNEL_TOL[name])
    emit("kernel", tpu_custom_call_in_beam_search=has_kernel,
         rows=rows, max_abs_err_ctx=errs["ctx"],
         max_abs_err_alpha=errs["alpha"], interpret=rehearsal)


def drive_server(port: int, cfg_path: str) -> Dict[str, Any]:
    """The client's side of the serve phase."""
    from sat_tpu.config import Config

    boot_s = wait_ready(port, timeout_s=900.0)
    images = jpegs(Config.load(cfg_path), 4)
    answers = [caption(port, img) for img in images + images[:1]]
    check(answers[-1]["caption"] == answers[0]["caption"],
          f"repeated image, different caption: {answers[0]} vs {answers[-1]}")
    status, stats = http("GET", port, "/stats")
    check(status == 200, f"/stats answered {status}")
    check(stats["compiles_since_ready"] == 0,
          f"{stats['compiles_since_ready']} compiles after ready")
    check(stats["encode_cache"]["hits"] >= 1,
          f"repeated image did not hit the encode cache: {stats['encode_cache']}")
    return {
        "boot_s": round(boot_s, 1), "requests": len(answers),
        "request_ms": [a["ms"] for a in answers],
        "cache_hit_request_ms": answers[-1]["ms"],
        "sample_caption": answers[0]["caption"],
        "compiles_since_ready": stats["compiles_since_ready"],
        "encode_cache": {k: stats["encode_cache"][k]
                         for k in ("hits", "misses", "rows")},
        "device": stats["engine"]["device"],
    }


def phase_serve(cfg_path: str, meter: CompileMeter) -> None:
    from sat_tpu import cli
    from sat_tpu.serve.replica import free_port

    port = free_port()
    outcome: Dict[str, Any] = {}

    def client() -> None:
        try:
            outcome["facts"] = drive_server(port, cfg_path)
        except BaseException as e:  # re-raised on the main thread below
            outcome["error"] = e
        finally:
            # what an operator sends: the server drains and main() returns
            os.kill(os.getpid(), signal.SIGTERM)

    thread = threading.Thread(target=client, name="smoke-client", daemon=True)
    thread.start()
    rc = cli.main([
        "--phase=serve", "--config", cfg_path, "--serve_mode", "continuous",
        "--encode_cache", "on", "--port", str(port),
    ])
    thread.join(timeout=30.0)
    if "error" in outcome:
        raise outcome["error"]
    check(rc == 0 and "facts" in outcome, f"--phase=serve exited {rc}")
    emit("serve", serve_mode="continuous", **outcome["facts"],
         peak_bytes_in_use=peak_bytes(), **meter.take())


def run_one_chip(args) -> Dict[str, Any]:
    import jax  # noqa: F401 — this process owns the chip from here on

    device = require_tpu(args.cpu_rehearsal, count=1)
    from sat_tpu.utils.compile_cache import cache_dir

    emit("device", **device, compile_cache_dir=cache_dir())
    meter = CompileMeter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        cfg_path = build_workdir(root, args.seed, args.cpu_rehearsal)
        phase_train(cfg_path, meter)
        phase_eval(cfg_path, meter, args.cpu_rehearsal)
        phase_serve(cfg_path, meter)
    return device


# ---------------------------------------------------------------------------
# four chips: the mesh child, then the fleet behind the router
# ---------------------------------------------------------------------------


def mesh_child(args) -> int:
    """Holds all four chips: dp (4,1) and dp x tp (2,2) train steps and the
    context-parallel decode, each against one device in this process, then
    the whole of ``--phase=train`` under the (2,2) mesh (its checkpoint is
    what the fleet serves)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = require_tpu(args.cpu_rehearsal, count=4)
    from sat_tpu import cli
    from sat_tpu.config import Config
    from sat_tpu.models.captioner import encode
    from sat_tpu.ops.beam_search import beam_search_jit
    from sat_tpu.parallel import make_mesh, make_parallel_train_step
    from sat_tpu.parallel.collectives import make_global_batch
    from sat_tpu.parallel.context import make_context_parallel_beam_search
    from sat_tpu.parallel.sharding import named_shardings, shard_train_state
    from sat_tpu.resilience import lineage
    from sat_tpu.train.step import create_train_state, make_jit_train_step
    from sat_tpu.utils.compile_cache import cache_dir, enable

    enable(jax)
    emit("device", **device, compile_cache_dir=cache_dir())
    meter = CompileMeter()
    cfg_path = args.mesh_child
    config = Config.load(cfg_path)
    B, T, S = config.batch_size, config.max_caption_length, config.image_size
    rng = np.random.default_rng(config.seed)
    host_batch = {
        "images": rng.integers(0, 256, (B, S, S, 3), dtype=np.uint8),
        "word_idxs": rng.integers(0, config.vocabulary_size, (B, T)).astype(np.int32),
        "masks": (np.arange(T)[None, :] < rng.integers(8, T + 1, (B, 1))).astype(np.float32),
    }
    init_key = jax.random.PRNGKey(config.seed)
    # a threefry key, as tests/test_parallel.py uses: its dropout masks do
    # not depend on how the program is partitioned (rbg's do)
    drop_key = jax.random.PRNGKey(config.seed + 1)

    _, want = make_jit_train_step(config)(
        create_train_state(init_key, config), host_batch, drop_key
    )
    want = {k: float(v) for k, v in want.items()}
    emit("mesh", arm="one_device", total_loss=want["total_loss"], **meter.take())

    def devices_of(x) -> int:
        return len({s.device for s in x.addressable_shards})

    def parts(x, axis: int = 0) -> int:
        """How many distinct slices of ``axis`` the shards of x hold."""
        return len({(s.index[axis].start, s.index[axis].stop)
                    for s in x.addressable_shards})

    for shape in ((4, 1), (2, 2)):
        cfg = config.replace(mesh_shape=shape)
        mesh = make_mesh(cfg)
        state = shard_train_state(create_train_state(init_key, cfg), cfg, mesh)
        step = make_parallel_train_step(cfg, mesh)
        placed = make_global_batch(mesh, host_batch)
        images = placed["images"]
        check(devices_of(images) == 4, f"batch sits on {devices_of(images)} devices")
        rows = {s.data.shape[0] for s in images.addressable_shards}
        check(rows == {B // shape[0]}, f"{shape}: batch shards hold {rows} rows")
        emb = state.params["decoder"]["word_embedding"]["weights"]
        softmax = state.params["decoder"]["decode"]["fc_2"]["kernel"]
        check(parts(emb) == shape[1] and parts(softmax, 1) == shape[1] and
              {s.data.shape[0] for s in emb.addressable_shards}
              == {config.vocabulary_size // shape[1]},
              f"{shape}: embedding/softmax split {parts(emb)}/"
              f"{parts(softmax, 1)}-way along the vocabulary")
        text = step.lower(state, placed, drop_key).compile().as_text()
        check("all-reduce" in text, f"{shape}: no all-reduce in the compiled step")
        state, got = step(state, placed, drop_key)
        got = {k: float(v) for k, v in got.items()}
        # the numbers first, so a run that then fails still shows them
        emit("mesh", arm=f"mesh{shape}", total_loss=got["total_loss"],
             rel_diff={k: abs(got[k] - want[k]) / max(abs(want[k]), 1e-12)
                       for k in want},
             batch_devices=devices_of(images), batch_rows_per_shard=B // shape[0],
             vocab_shards=parts(emb), all_reduce=True, **meter.take())
        for k in (k for k in want if k.endswith("_loss")):
            np.testing.assert_allclose(
                got[k], want[k], rtol=MESH_RTOL, atol=MESH_ATOL,
                err_msg=f"mesh {shape} metric {k}",
            )
        check(int(state.step) == 1, f"{shape}: step counter {int(state.step)}")
        del state, placed

    # context-parallel decode (grid split over 'model') against the
    # one-device beam search.  The CP path has its own XLA attend, so its
    # reference is the XLA branch, not the Pallas kernel
    cp = config.replace(mesh_shape=(2, 2), context_parallel=2, beam_size=3)
    mesh = make_mesh(cp)
    variables = {"params": create_train_state(init_key, cp).params}
    eos, valid = 1, 64
    one = beam_search_jit(
        variables["params"]["decoder"],
        cp.replace(use_pallas_attention=False),
        encode(variables, cp, jnp.asarray(host_batch["images"]), train=False)[0],
        eos, beam_size=3, valid_size=valid,
    )
    placed_vars = jax.device_put(
        variables, named_shardings(variables, cp.replace(vocabulary_size=-1), mesh)
    )
    decode = make_context_parallel_beam_search(
        cp, mesh, eos, beam_size=3, valid_size=valid
    )
    got = decode(placed_vars, make_global_batch(mesh, {"images": host_batch["images"]})["images"])
    same = (np.asarray(got.words[:, 0]) == np.asarray(one.words[:, 0])).all(axis=1)
    score_diff = np.abs(
        np.asarray(got.log_scores[:, 0]) - np.asarray(one.log_scores[:, 0])
    ) / np.abs(np.asarray(one.log_scores[:, 0]))
    emit("mesh", arm="context_parallel=2", captions_identical=int(same.sum()),
         images=B, max_rel_score_diff_identical=float(score_diff[same].max(initial=0.0)),
         max_rel_score_diff_all=float(score_diff.max()), **meter.take())
    check(devices_of(got.words) == 4 and parts(got.words) == 2,
          "CP decode result is not split over the data axis of a (2,2) mesh")
    # beams on a near-tie may flip under a different reduction order; a
    # wrong shard would change every caption from the first word on
    check(int(same.sum()) >= int(0.9 * B),
          f"context-parallel captions match for {int(same.sum())}/{B} images")
    check(float(score_diff[same].max(initial=0.0)) <= 1e-3,
          f"context-parallel scores differ by {score_diff[same].max():.2e}")

    t0 = time.perf_counter()
    rc = cli.main(["--phase=train", "--config", cfg_path,
                   "--set", "mesh_shape=2,2"])
    check(rc == 0, f"--phase=train under mesh (2,2) exited {rc}")
    with open(os.path.join(config.summary_dir, "metrics.jsonl")) as f:
        losses = [json.loads(line)["total_loss"] for line in f]
    check(len(losses) >= 3 and bool(np.isfinite(losses).all())
          and losses[-1] < losses[0], f"mesh train losses {losses}")
    check(lineage.last_good_checkpoint(config.save_dir) is not None,
          "mesh train wrote no verifiable checkpoint")
    emit("mesh", arm="cli --phase=train mesh(2,2)", steps=len(losses),
         wall_s=round(time.perf_counter() - t0, 1),
         loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
         peak_bytes_in_use=peak_bytes(), **meter.take())
    print(json.dumps({"mesh_child": "passed", "device": device}), flush=True)
    return 0


def phase_fleet(cfg_path: str, env: Dict[str, str]) -> None:
    """Four one-chip replicas behind ``--phase route``; this process and
    the router hold no chip."""
    from sat_tpu.config import Config
    from sat_tpu.serve.replica import free_port, local_tpu_chips

    n = 4
    config = Config.load(cfg_path)
    route_port = free_port()
    base = free_port_run(n)
    log_path = os.path.join(os.path.dirname(cfg_path), "route.log")
    with open(log_path, "ab") as log:
        router = subprocess.Popen(
            [sys.executable, "-m", "sat_tpu.cli", "--phase", "route",
             "--config", cfg_path, "--num_replicas", str(n),
             "--port", str(route_port), "--serve_mode", "continuous",
             "--set", f"route_replica_base_port={base}"],
            stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
        )
    try:
        boot_s = wait_ready(
            route_port, 1200.0, alive=lambda: router.poll() is None,
            ready=lambda payload: payload.get("replicas_routable") == n,
        )
        images = jpegs(config, 4)
        # through the router, a burst so the load spreads...
        answers: List[Dict[str, Any]] = []
        errors: List[BaseException] = []

        def one(img: bytes) -> None:
            try:
                answers.append(caption(route_port, img))
            except BaseException as e:
                errors.append(e)

        threads = [threading.Thread(target=one, args=(images[i % 4],))
                   for i in range(4 * n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        if errors:
            raise errors[0]
        check(len(answers) == 4 * n, f"{len(answers)}/{4 * n} routed requests answered")
        # ...and a few to each replica directly, so each provably answers
        # on its own device
        devices = {}
        for i in range(n):
            port = base + i
            direct = [caption(port, img) for img in images[:2]]
            status, stats = http("GET", port, "/stats")
            check(status == 200 and stats["compiles_since_ready"] == 0,
                  f"replica r{i}: {stats.get('compiles_since_ready')} compiles after ready")
            served = int(stats["counters"].get("serve/http_requests", 0))
            check(served >= len(direct) and
                  not stats["counters"].get("serve/http_5xx", 0),
                  f"replica r{i} counters: {stats['counters']}")
            devices[f"r{i}"] = {**stats["engine"]["device"], "requests": served}
        status, rstats = http("GET", route_port, "/stats")
        counters = rstats["counters"]
        bad = {k: counters.get(k, 0) for k in
               ("route/http_5xx", "route/upstream_5xx", "route/upstream_errors")}
        check(status == 200 and not any(bad.values()), f"router saw failures: {bad}")
        if local_tpu_chips(env):     # the launcher's own test for a TPU host
            chips = [d["chip"] for d in devices.values()]
            check(len(set(chips)) == n and "" not in chips,
                  f"replicas do not each name their own chip: {devices}")
        emit("fleet", replicas=n, boot_s=round(boot_s, 1),
             routed_requests=len(answers),
             routed_ms_median=sorted(a["ms"] for a in answers)[len(answers) // 2],
             replica_devices=devices, router_failures=bad,
             routable=len(rstats["routable"]))
    except BaseException:
        with open(log_path, errors="replace") as f:
            sys.stderr.write("--- route.log tail ---\n" + "".join(f.readlines()[-40:]))
        raise
    finally:
        if router.poll() is None:
            router.send_signal(signal.SIGTERM)   # drains; stops its replicas
        try:
            rc = router.wait(timeout=120.0)
        except subprocess.TimeoutExpired:
            router.kill()
            rc = router.wait()
    check(rc == 0, f"--phase route exited {rc}")


def run_four_chips(args) -> Dict[str, Any]:
    """Parent of the four-chip run: never imports jax."""
    env = dict(os.environ)
    if args.cpu_rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
            ).strip()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        cfg_path = build_workdir(root, args.seed, args.cpu_rehearsal)
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-child", cfg_path,
             "--seed", str(args.seed)]
            + (["--cpu-rehearsal"] if args.cpu_rehearsal else []),
            env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
        )
        lines = child.stdout.strip().splitlines()
        ok = child.returncode == 0
        print("\n".join(lines[:-1] if ok else lines), flush=True)
        check(ok, f"mesh child exited {child.returncode}")
        verdict = json.loads(lines[-1])
        check(verdict.get("mesh_child") == "passed", f"mesh child said {verdict}")
        check("jax" not in sys.modules, "the fleet's parent imported jax")
        phase_fleet(cfg_path, env)
    check(verdict["device"]["count"] == 4, f"device count {verdict['device']}")
    return verdict["device"]


# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the mesh and fleet phases, on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="dataset, weights and kernel inputs are made from it")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU; never prints an ok line")
    ap.add_argument("--mesh-child", metavar="CONFIG", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        if args.mesh_child:
            return mesh_child(args)
        device = run_four_chips(args) if args.chips == 4 else run_one_chip(args)
    except Exception:
        # the boundary: say what failed, print no result line, exit non-zero
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr, flush=True)
        return 1
    if args.cpu_rehearsal:
        print(json.dumps({"rehearsal": "passed", "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
