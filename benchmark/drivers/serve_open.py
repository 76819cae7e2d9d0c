"""``serve_open``: ``--phase=serve --serve_mode continuous`` under an open
loop of distinct JPEGs over keep-alive HTTP.

Two processes.  This one holds the chip and runs the server on its main
thread exactly as the CLI does; ``benchmark/loadgen.py`` is a child with
its own interpreter that never imports jax.  A side thread here follows
the generator's progress lines, starts the profiler for the traced
sub-window, and ends the server with the SIGTERM an operator would send.

Mix parameters: end_to_end, rate_per_s (0.8 of the measured knee),
knee_per_s, schedule_seed (the Poisson sample path), warmup_requests,
threads, request_timeout_s, sample_requests, trace_seconds, limits,
program.  ``--rates a,b,c`` (builder's option)
runs one window per rate against one boot and prints a line per rate in
place of a result: the knee sweep.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

import datagen
import harness
from drivers import common
from reference import check as refcheck
from reference import model as refmodel
from reference.params import make_weights


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def window_stats(win: dict) -> dict:
    recs = win["records"]
    ok = [r for r in recs if r["status"] == 200]
    lat = np.array([(r["end"] - r["due"]) * 1e3 for r in ok])
    late = np.array([(r["sent"] - r["due"]) * 1e3 for r in recs])
    t0 = min(r["due"] for r in recs)
    half = len(ok) // 2
    by_due = sorted(ok, key=lambda r: r["due"])
    med = lambda rs: float(np.median([(r["end"] - r["due"]) * 1e3 for r in rs])) if rs else float("nan")  # noqa: E731
    statuses: dict = {}
    for r in recs:
        statuses[str(r["status"])] = statuses.get(str(r["status"]), 0) + 1
    return {
        "rate": win["rate"], "attempted": len(recs), "failed": len(recs) - len(ok),
        "statuses": statuses,
        "completed_per_s": len(ok) / max(1e-9, max(r["end"] for r in recs) - t0),
        "p50_ms": float(np.percentile(lat, 50)) if len(lat) else float("nan"),
        "p95_ms": float(np.percentile(lat, 95)) if len(lat) else float("nan"),
        "p99_ms": float(np.percentile(lat, 99)) if len(lat) else float("nan"),
        "late_p95_ms": float(np.percentile(late, 95)),
        "first_half_p50_ms": med(by_due[:half]), "second_half_p50_ms": med(by_due[half:]),
    }


def candidates(words, T: int, eos: int):
    """Token rows a returned caption of ``words`` may stand for: n == T
    words ran the full length; fewer end in the terminator, unless the
    program dropped one <start> (id 0) it had emitted among T tokens."""
    n = len(words)
    if n >= T:
        return [list(words[:T])]
    out = [list(words) + [eos]]
    if n == T - 1:
        out += [list(words[:p]) + [0] + list(words[p:]) for p in range(n + 1)]
    return out


def run(cell: harness.Cell, args, env) -> common.Outcome:
    from sat_tpu import cli, telemetry

    mix, seed = cell.mix, args.seed
    rates = ([float(r) for r in args.rates.split(",")] if getattr(args, "rates", None)
             else [float(mix["rate_per_s"])])
    warmup = int(mix["warmup_requests"])
    n_images = warmup + sum(int(round(r * args.seconds)) for r in rates) + 8
    kept, work, reused = cell.workdir(seed, n_images)
    if not reused:
        datagen.make_images(os.path.join(kept, "requests"), n_images, cell.model["image_size"], seed)
    port = free_port()
    config, cfg_path, vocab = common.seeded_setup(cell, kept, work, reused, seed, phase="serve",
                                                  serve_port=port)
    out_path = os.path.join(work, "loadgen.json")
    gen = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH_DIR, "loadgen.py"), "--port", str(port),
         "--images", os.path.join(kept, "requests"), "--seed", str(seed),
         "--schedule-seed", str(int(mix["schedule_seed"])),
         "--rates", ",".join(str(r) for r in rates), "--seconds", str(args.seconds),
         "--out", out_path, "--threads", str(mix["threads"]), "--warmup", str(warmup),
         "--timeout", str(mix["request_timeout_s"]), "--parent", str(os.getpid())],
        stdout=subprocess.PIPE, text=True)
    unix_to_ns = lambda u: int(time.perf_counter_ns() + (u - time.time()) * 1e9)  # noqa: E731
    windows, stats_box = [], {}
    tracer = (harness.TraceWindow(os.path.join(work, "trace"), float(mix["trace_seconds"]))
              if args.trace else None)

    def control() -> None:
        for line in gen.stdout:                  # ends when the generator exits
            parts = line.split()
            if parts and parts[0] == "WINDOW":
                windows.append(unix_to_ns(float(parts[1])))
                if tracer and len(windows) == 2:     # [0] is the warm-up burst
                    # the window's last stretch; stop_trace's cost falls after it
                    common.sleep_until(windows[1] + int((args.seconds - tracer.seconds) * 1e9))
                    tracer.run()
        gen.wait()
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/stats", timeout=10) as r:
                stats_box.update(json.loads(r.read()))
        except OSError as e:
            stats_box["error"] = repr(e)

    controller = harness.self_sigterm_after(control)
    try:
        rc = cli.main(["--phase=serve", "--config", cfg_path, "--serve_mode", "continuous",
                       "--port", str(port)])
    finally:
        if gen.poll() is None:
            gen.kill()
        gen.wait()
    controller.join(timeout=30.0)
    if rc != 0 or gen.returncode != 0 or not os.path.exists(out_path):
        raise harness.BenchError(f"serve exited {rc}, the load generator {gen.returncode}")
    result = harness.load_json(out_path)
    if getattr(args, "rates", None):
        for win in result["windows"]:
            print(json.dumps({"sweep": window_stats(win)}), flush=True)
        sys.stdout.flush()
        os._exit(0)

    win = result["windows"][0]
    recs = win["records"]
    w0 = windows[1]
    window_ns = (w0, w0 + int(args.seconds * 1e9))
    run_ = harness.RunData(cell, common.span_window(window_ns, tracer), env.peaks)
    run_.take_spans(telemetry.get())
    mem_peak = harness.memory_peak()["peak"]     # the counter alone: see PERF.md section 7
    st = window_stats(win)
    run_.measured.update(latency_p50_ms=st["p50_ms"], latency_p95_ms=st["p95_ms"],
                         completed_per_s=st["completed_per_s"],
                         setup_s=result["first_ok_unix"] - env.t_start_unix)
    run_.extras.update(
        compile_s=env.meter.seconds_before(unix_to_ns(result["first_ok_unix"])),
        trace_dir=os.path.join(work, "trace"),
        loadgen_late_ms=np.array([(r["sent"] - r["due"]) * 1e3 for r in recs
                                  if unix_to_ns(r["due"]) < run_.window_ns[1]]),
        completed_per_s=st["completed_per_s"],
        caption_latency_ms=np.array([(r["end"] - r["due"]) * 1e3 for r in recs
                                     if r["status"] == 200 and unix_to_ns(r["due"]) < run_.window_ns[1]]),
    )
    if tracer:
        common.take_trace(run_, tracer)
        ends = np.array([unix_to_ns(r["end"]) for r in recs if r["status"] == 200])
        run_.extras["replies_in_trace"] = int(((ends >= tracer.t0_ns) & (ends < tracer.t1_ns)).sum())

    # ---- correct: a seeded sample of the replies served under load
    T, beam = config.max_caption_length, config.beam_size
    index = {w: i for i, w in enumerate(vocab)}
    eos = index["."]
    checks = [{"name": "compiles_since_ready", "limit": 0,
               "value": stats_box.get("compiles_since_ready", 1 if "error" in stats_box else 0)},
              {"name": "compiles_in_window", "limit": 0,
               "value": env.meter.count_between(*window_ns)}]
    ok = [r for r in recs if r["status"] == 200]
    parsed, well_formed = {}, True
    for r in ok:
        try:
            parsed[r["i"]] = datagen.tokenize_caption(r["caption"], index)
            well_formed &= len(parsed[r["i"]]) <= T and bool(np.isfinite(r["log_prob"]))
        except KeyError:
            well_formed = False
    checks.append({"name": "replies_well_formed", "value": bool(well_formed and ok), "limit": None})
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    usable = [r for r in ok if r["i"] in parsed]
    longest = int(np.argmax([len(parsed[r["i"]]) for r in usable])) if usable else None
    sample = [usable[i] for i in common.sample_indices(rng, len(usable), int(mix["sample_requests"]),
                                                       must=longest)] if usable else []
    rows, owner = [], []
    for k, r in enumerate(sample):
        for cand in candidates(parsed[r["i"]], T, eos):
            rows.append(cand + [0] * (T - len(cand)))
            owner.append((k, len(cand)))
    images = {k: datagen.read_rgb(os.path.join(kept, "requests", result["files"][r["image"]]))
              for k, r in enumerate(sample)}
    tokens = np.array(rows, np.int32)
    weights = make_weights(cell.model, seed)
    logits = refmodel.served_logits(weights, cell.model,
                                    np.stack([images[k] for k, _ in owner]), tokens)
    lengths = [n for _, n in owner]
    reported = [sample[k]["log_prob"] for k, _ in owner]
    per_row = refcheck.served_numbers(logits, tokens, lengths, reported, beam)["ref_score"]
    best = {}
    for j, (k, _n) in enumerate(owner):          # the reading of a caption is the one that fits
        gap = abs(per_row[j] - reported[j])
        if k not in best or gap < best[k][0]:
            best[k] = (gap, j)
    keep = sorted(j for _g, j in best.values())
    got = refcheck.served_numbers(logits[keep], tokens[keep], [lengths[j] for j in keep],
                                  [reported[j] for j in keep], beam)
    checks += common.limit_checks({k: got[k] for k in mix["limits"]}, mix["limits"])
    control = None
    if getattr(args, "control", 0):
        control = common.control_served(cell, seed, np.stack([images[owner[j][0]] for j in keep]),
                                        tokens[keep], [lengths[j] for j in keep], logits[keep], beam)
    return common.Outcome(run_, checks, attempted=st["attempted"], failed=st["failed"],
                          memory_peak_bytes=mem_peak,
                          notes={"control": control, "reused": reused, "score_gap_mean": got["score_gap_mean"], "trace_timing": run_.extras.get("trace_timing"), "served_tokens": int(sum(lengths[j] for j in keep)),
                                 "captions": len(keep), "stats": st,
                                 "slow_due_s": [round(r["due"] - recs[0]["due"], 2) for r in recs
                                                if r["status"] == 200 and (r["end"] - r["due"]) > 2.5e-3 * st["p50_ms"]][:40]})
