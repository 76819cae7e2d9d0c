"""Command-line driver: ``python -m sat_tpu.cli --phase=train|eval|test``.

Flag-for-flag parity with the reference CLI (/root/reference/main.py:15-36):
``--phase --load --model_file --load_cnn --cnn_model_file --train_cnn
--beam_size``, dispatching to the runtime layer (main.py:45-72).  Any other
Config field can be overridden with ``--set key=value`` pairs (the
reference requires editing config.py for those).

One extra input-pipeline flag beyond the reference surface:
``--shard_cache auto|on|off`` selects the mmap'd preprocessed-shard
cache (docs/DATA_PIPELINE.md); ``--set`` spellings of the same field
still win, flag defaults never clobber them.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import List, Optional

from .config import Config


# The reference's config attributes are literally misspelled
# (/root/reference/config.py:12-13: "num_initalize_layers",
# "dim_initalize_layer"); accept those spellings so its users' override
# lists port verbatim.
_REFERENCE_KEY_ALIASES = {
    "num_initalize_layers": "num_initialize_layers",
    "dim_initalize_layer": "dim_initialize_layer",
}


def _parse_override(config: Config, key: str, raw: str):
    fields = {f.name: f for f in dataclasses.fields(Config)}
    if key not in fields:
        raise SystemExit(f"--set {key}: unknown Config field")
    current = getattr(config, key)
    if raw.lower() == "none":  # Optional[int] caps: 'none' clears the cap
        return None
    if isinstance(current, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(current, int):
        return int(raw)
    if isinstance(current, float):
        return float(raw)
    if isinstance(current, tuple):
        cast = str if current and isinstance(current[0], str) else int
        return tuple(cast(x) for x in raw.split(","))
    if current is None:  # field currently None: best-effort int, else str
        try:
            return int(raw)
        except ValueError:
            return raw
    return raw


def build_config(argv: Optional[List[str]] = None):
    """Returns (Config, cli_options_dict)."""
    p = argparse.ArgumentParser(
        prog="sat_tpu",
        description="TPU-native Show, Attend and Tell",
    )
    p.add_argument(
        "--phase", default=None,
        choices=["train", "eval", "test", "serve", "route", "bulk"],
        help="default: train, or the --config file's phase when one is given",
    )
    p.add_argument(
        "--load", action="store_true",
        help="resume from the latest checkpoint in save_dir",
    )
    p.add_argument("--model_file", default=None, help="explicit checkpoint file")
    p.add_argument(
        "--load_cnn", action="store_true",
        help="import a pretrained CNN before training",
    )
    p.add_argument(
        "--cnn_model_file", default="./vgg16_no_fc.npy",
        help="pretrained CNN npy (reference nested format)",
    )
    p.add_argument(
        "--train_cnn", action="store_true",
        help="jointly train CNN + RNN (default: RNN only)",
    )
    p.add_argument("--beam_size", type=int, default=None)
    p.add_argument(
        "--shard_cache", default=None, choices=["auto", "on", "off"],
        help="preprocessed-image shard cache (data.shards): 'auto' "
             "(default) uses a valid existing cache and falls back to "
             "live JPEG decode otherwise, 'on' builds/extends the cache "
             "before the run, 'off' forces live decode",
    )
    p.add_argument(
        "--verify_shards", default=None,
        choices=["off", "sample", "open", "full"],
        help="verify gathered shard rows against their per-row crc32c "
             "sidecars (data/integrity.py): 'sample' scrubs one rotating "
             "row every few gathers (≪1%% of a step), 'open' fully "
             "verifies each shard on first touch, 'full' verifies every "
             "row every batch; corrupt rows fall back to live decode and, "
             "failing that, are quarantined (docs/DATA_PIPELINE.md)",
    )
    p.add_argument(
        "--repair_shards", action="store_true",
        help="rebuild only the shard files holding crc-mismatching or "
             "quarantined rows by re-decoding their source images "
             "(bitwise-identical to a clean rebuild), print a JSON "
             "report, and exit — no accelerator needed",
    )
    p.add_argument(
        "--anomaly_policy", default=None,
        choices=["off", "warn", "skip", "rollback"],
        help="anomaly-sentinel response to NaN/Inf or spiking metrics at "
             "each log_every check (docs/RESILIENCE.md): 'warn' (default) "
             "reports and stops blessing LAST_GOOD, 'skip' also suppresses "
             "checkpoint writes while unhealthy, 'rollback' restores "
             "LAST_GOOD and fast-forwards past the poison step, 'off' "
             "disarms the sentinel",
    )
    p.add_argument(
        "--keep_checkpoints", type=int, default=None, metavar="N",
        help="checkpoint retention: keep the newest N plus the LAST_GOOD "
             "target, delete the rest (default 0 = keep everything)",
    )
    p.add_argument(
        "--io_retries", type=int, default=None, metavar="N",
        help="retry budget for transient IO errors (EIO/EAGAIN/ESTALE...) "
             "on checkpoint/shard/manifest/caption reads and writes, with "
             "jittered exponential backoff (default 3; 0 disables)",
    )
    p.add_argument(
        "--telemetry", action="store_true",
        help="enable host-side span tracing: per-phase step-time "
             "breakdown at end of run, heartbeat.json run-health file, "
             "telemetry.jsonl snapshots, Chrome trace JSON "
             "(docs/OBSERVABILITY.md; adds no device syncs)",
    )
    p.add_argument(
        "--heartbeat_interval", type=float, default=None, metavar="SEC",
        help="seconds between heartbeat.json rewrites when --telemetry is "
             "on (default 10; 0 disables the heartbeat thread)",
    )
    p.add_argument(
        "--diag_level", default=None, choices=("off", "basic", "full"),
        help="in-graph model-health taps (grad/update/param norms, "
             "attention entropy, alpha-coverage deviation, logit max) "
             "merged into the train metrics at the existing log sync — "
             "zero extra device syncs; 'full' adds per-layer-group norms "
             "(docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--metrics_port", type=int, default=None, metavar="PORT",
        help="train phase: read-only Prometheus /metrics + /healthz "
             "scrape endpoint riding the heartbeat payload (default 0 = "
             "off; the serve phase exposes /metrics on its own port)",
    )
    p.add_argument(
        "--trace_export", default=None, metavar="PATH",
        help="Chrome trace-event JSON output path (default "
             "<summary_dir>/telemetry/trace.json when --telemetry is on); "
             "load in Perfetto or chrome://tracing",
    )
    p.add_argument(
        "--fleet_telemetry", action="store_true",
        help="cross-host fleet plane (docs/OBSERVABILITY.md): each "
             "process writes a heartbeat_p<i>.json sidecar at the log "
             "boundary and process 0 merges them into fleet.json with "
             "per-host rows, skew ratios, and a straggler verdict; "
             "implies --telemetry (shared dir via --set fleet_dir=...)",
    )
    p.add_argument(
        "--blackbox", action="store_true",
        help="black-box flight recorder (docs/OBSERVABILITY.md): journal "
             "recent counters/gauges/events to a bounded on-disk ring and "
             "dump a postmortem_<run_id>/ bundle on abnormal exits "
             "(watchdog 86, data corruption 87, sentinel trips, uncaught "
             "exceptions); implies --telemetry",
    )
    p.add_argument(
        "--straggler_factor", type=float, default=None, metavar="X",
        help="fleet straggler threshold: name the worst host when its "
             "step-time p95 exceeds the fleet median by this factor "
             "(default 2.0)",
    )
    p.add_argument(
        "--port", type=int, default=None, metavar="PORT",
        help="serve phase: HTTP listen port (default Config.serve_port; "
             "0 picks an ephemeral port)",
    )
    p.add_argument(
        "--max_batch", type=int, default=None, metavar="N",
        help="serve phase: most requests per dispatched micro-batch "
             "(padded up to the bucket ladder, --set serve_buckets=...)",
    )
    p.add_argument(
        "--max_wait_ms", type=float, default=None, metavar="MS",
        help="serve phase: how long the batcher holds an underfull batch "
             "open waiting for more arrivals (latency/throughput knob, "
             "docs/SERVING.md)",
    )
    p.add_argument(
        "--replicas", default=None, metavar="HOST:PORT,...",
        help="route phase: front these pre-started serve replicas instead "
             "of spawning a local fleet (sat_tpu/serve/router.py)",
    )
    p.add_argument(
        "--num_replicas", type=int, default=None, metavar="N",
        help="route phase: size of the locally spawned replica fleet "
             "(ignored when --replicas is given; default "
             "Config.route_num_replicas)",
    )
    p.add_argument(
        "--serve_mode", choices=("batch", "continuous"), default=None,
        help="serve phase: 'batch' dispatches whole padded micro-batches "
             "(the correctness oracle); 'continuous' admits requests into "
             "a paged slot pool between decode steps and retires finished "
             "beams early (docs/SERVING.md)",
    )
    p.add_argument(
        "--serve_decode_depth", default=None, metavar="K1,K2,...",
        help="serve phase (continuous): the fused decode window ladder — "
             "comma-separated K values the adaptive policy may pick "
             "(the depth is a runtime operand of one AOT-warmed "
             "multi-step executable); the batcher runs the deepest K "
             "when the admission queue is idle and K=1 under burst "
             "(must start at 1; default "
             "Config.serve_decode_depth=1,2,4,8; docs/SERVING.md 'Fused "
             "decode window')",
    )
    p.add_argument(
        "--tenants", default=None, metavar="SPEC",
        help="serve/route phase: multi-tenant registry — a JSON file path "
             "or an inline 'name[:weight[:rps[:burst]]],...' list (first "
             "entry = the default tenant for requests without X-Tenant). "
             "Tenants get weighted deficit-round-robin scheduling, "
             "token-bucket admission quotas, per-tenant SLO burn lanes, "
             "and optional per-tenant resident models (docs/SERVING.md "
             "'Multi-tenant serving'; default Config.tenants='' = "
             "single-tenant)",
    )
    p.add_argument(
        "--serve_metering", choices=("on", "off"), default=None,
        help="serve phase: per-request cost attribution + per-tenant "
             "metering ledger + online capacity model (telemetry/"
             "metering.py, telemetry/capacity.py; docs/OBSERVABILITY.md "
             "'Cost attribution'). Only active when telemetry is on; "
             "default Config.serve_metering=True",
    )
    p.add_argument(
        "--encode_cache", choices=("on", "off"), default=None,
        help="serve phase: device-resident content-addressed LRU of "
             "encoder feature grids keyed by (image crc32c, param "
             "fingerprint, quant mode) — a hit skips the encode lane, a "
             "miss encodes once with single-flight coalescing "
             "(docs/SERVING.md 'Encode cache & tiered fleets'; default "
             "Config.encode_cache='off', bit-identical to pre-cache "
             "serving)",
    )
    p.add_argument(
        "--encode_cache_mb", type=int, default=None,
        help="serve phase: HBM budget for the encode-cache feature-grid "
             "ring (fixed geometry, sized at warmup; default "
             "Config.encode_cache_mb=64)",
    )
    p.add_argument(
        "--serve_tier", choices=("both", "encode", "decode"), default=None,
        help="serve phase: fleet tier this replica advertises — 'encode' "
             "(stateless POST /encode feature-grid tier), 'decode' "
             "(latency tier fed grids), or 'both' (default; untiered). "
             "Routing metadata only: every replica still answers direct "
             "image captions (docs/SERVING.md 'Encode cache & tiered "
             "fleets')",
    )
    p.add_argument(
        "--serve_quality", choices=("on", "off"), default=None,
        help="serve phase: caption-quality observability plane — "
             "per-request quality signals at the detok boundary, "
             "streaming PSI drift vs a frozen reference, exemplar "
             "flight recorder + bitwise replay (telemetry/quality.py, "
             "telemetry/exemplar.py; docs/OBSERVABILITY.md 'Caption "
             "quality'). Default Config.serve_quality='off' — off is "
             "bit-identical to the pre-quality serve path",
    )
    p.add_argument(
        "--quality_reference", default=None, metavar="JSON",
        help="serve phase: quality_reference.json to load as the frozen "
             "drift reference (exported by GET /quality_reference); "
             "default '' freezes the reference from the first "
             "serve_quality_window live requests",
    )
    p.add_argument(
        "--slo_quality_psi", type=float, default=None, metavar="PSI",
        help="serve phase: quality_drift SLO lane — gauge_ceiling over "
             "quality/psi_max (population-stability drift score); "
             "diagnostic like tenant lanes (/healthz stays ok while it "
             "burns); 0 disables; default Config.slo_quality_psi=0",
    )
    p.add_argument(
        "--slo_quality_unk", type=float, default=None, metavar="RATE",
        help="serve phase: quality_unk SLO lane — gauge_ceiling over the "
             "windowed quality/unk_rate; 0 disables; default "
             "Config.slo_quality_unk=0",
    )
    p.add_argument(
        "--slo_capacity_headroom_pct", type=float, default=None,
        metavar="PCT",
        help="serve phase: capacity_headroom SLO objective — alert when "
             "the capacity model's headroom gauge falls below PCT "
             "(gauge_floor kind; 0 disables; default "
             "Config.slo_capacity_headroom_pct=0)",
    )
    p.add_argument(
        "--encoder_quant", choices=("off", "bf16", "int8"), default=None,
        help="serve phase: post-training quantization of the frozen CNN "
             "encoder at param load, before AOT warmup (docs/SERVING.md "
             "'Precision & parity').  'int8' = per-output-channel symmetric "
             "int8 kernels + calibrated activation scales, convs run "
             "int8xint8->int32 on the MXU with fused dequant; 'bf16' = "
             "bfloat16 kernel storage; 'off' (default) is bitwise the "
             "unquantized path",
    )
    p.add_argument(
        "--model_reload", type=float, default=None, metavar="SEC",
        help="serve phase: poll the lineage LAST_GOOD pointer every SEC "
             "seconds (jittered) and hot-swap new checkpoints through a "
             "canary stage without restarting the server (0 = off, the "
             "load-once default; docs/SERVING.md 'Model lifecycle')",
    )
    p.add_argument(
        "--canary_fraction", type=float, default=None, metavar="F",
        help="serve phase: fraction of requests routed to the candidate "
             "params during the canary window, sticky per X-Request-Id "
             "(default Config.canary_fraction)",
    )
    p.add_argument(
        "--canary_window_s", type=float, default=None, metavar="SEC",
        help="serve phase: canary qualification window length before "
             "promote/rollback is decided (default Config.canary_window_s)",
    )
    p.add_argument(
        "--promote_policy", choices=("auto", "manual"), default=None,
        help="serve phase: 'auto' promotes a candidate whose canary window "
             "elapsed without the canary SLO burning; 'manual' holds in "
             "CANARY until POST /promote or /rollback",
    )
    p.add_argument(
        "--bulk_input", default=None, metavar="PATH",
        help="bulk phase: image corpus — a directory tree (recursively "
             "walked for images; non-image files are skipped and counted) "
             "or a text file listing one image path per line "
             "(docs/BULK.md)",
    )
    p.add_argument(
        "--bulk_output", default=None, metavar="DIR",
        help="bulk phase: output directory for captions_<shard>.jsonl + "
             "crc sidecars and the bulk_manifest.json resume frontier",
    )
    p.add_argument(
        "--bulk_shard_rows", type=int, default=None, metavar="N",
        help="bulk phase: images per output shard — the resume grain; a "
             "killed run re-decodes at most one shard (default "
             "Config.bulk_shard_rows)",
    )
    p.add_argument(
        "--supervise", action="store_true",
        help="crash-only restart loop (docs/RESILIENCE.md): keep this "
             "process jax-free and run the real work in a child; a child "
             "that crashes, is killed, or is aborted by the hang watchdog "
             "(exit code 86) is relaunched with --load so it resumes from "
             "the LAST_GOOD checkpoint, with jittered exponential backoff "
             "and a bounded restart budget",
    )
    p.add_argument(
        "--max_restarts", type=int, default=None, metavar="N",
        help="--supervise restart budget (default "
             "Config.supervise_max_restarts)",
    )
    p.add_argument(
        "--watchdog", type=float, default=None, metavar="SEC",
        help="arm the hang/wedge watchdog with this observer poll interval "
             "(sets watchdog_interval; per-phase deadlines via --set "
             "watchdog_step_s=... etc.; 0 disables — the default)",
    )
    p.add_argument(
        "--config", default=None, metavar="JSON",
        help="load a Config JSON (e.g. the save_dir sidecar a checkpoint "
             "rode with) as the base instead of built-in defaults; "
             "--set/--phase still override it",
    )
    p.add_argument(
        "--sweep", action="store_true",
        help="eval phase: score EVERY checkpoint under save_dir "
             "(the reference's eval.sh loop), writing <step>.txt dumps",
    )
    p.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="override any Config field, repeatable",
    )
    p.add_argument(
        "--print_config", action="store_true",
        help="print the fully resolved Config as JSON and exit (audits "
             "--set stacks and env path re-rooting without running)",
    )
    args = p.parse_args(argv)
    if args.sweep and (args.model_file or args.load):
        raise SystemExit(
            "--sweep scores every checkpoint under save_dir; it conflicts "
            "with --model_file/--load"
        )

    if args.config:
        # file values are the base; only EXPLICIT flags override them
        # (each flag's absent-default is a sentinel; train_cnn is a
        # store_true — absent means "keep the file's value")
        config = Config.load(args.config)
        if args.phase is not None:
            config = config.replace(phase=args.phase)
        if args.train_cnn:
            config = config.replace(train_cnn=True)
        if args.beam_size is not None:
            config = config.replace(beam_size=args.beam_size)
    else:
        config = Config(
            phase=args.phase if args.phase is not None else "train",
            train_cnn=args.train_cnn,
            beam_size=args.beam_size if args.beam_size is not None else 3,
        )
    if args.shard_cache is not None:
        config = config.replace(shard_cache=args.shard_cache)
    if args.verify_shards is not None:
        config = config.replace(verify_shards=args.verify_shards)
    if args.anomaly_policy is not None:
        config = config.replace(anomaly_policy=args.anomaly_policy)
    if args.keep_checkpoints is not None:
        config = config.replace(keep_checkpoints=args.keep_checkpoints)
    if args.io_retries is not None:
        config = config.replace(io_retries=args.io_retries)
    if args.telemetry:
        config = config.replace(telemetry=True)
    if args.fleet_telemetry:
        # both ride the span recorder, so they imply the base layer
        config = config.replace(fleet_telemetry=True, telemetry=True)
    if args.blackbox:
        config = config.replace(blackbox=True, telemetry=True)
    if args.straggler_factor is not None:
        config = config.replace(straggler_factor=args.straggler_factor)
    if args.heartbeat_interval is not None:
        config = config.replace(heartbeat_interval=args.heartbeat_interval)
    if args.metrics_port is not None:
        config = config.replace(metrics_port=args.metrics_port)
    if args.trace_export is not None:
        config = config.replace(trace_export=args.trace_export)
    if args.diag_level is not None:
        config = config.replace(diag_level=args.diag_level)
    if args.replicas is not None:
        # naming endpoints implies the route phase (before --port below,
        # which binds to the router in route phase)
        config = config.replace(phase="route", route_replicas=args.replicas)
    if args.num_replicas is not None:
        config = config.replace(route_num_replicas=args.num_replicas)
    if args.port is not None:
        # one --port flag, two listeners: in route phase it is the
        # router's own port, otherwise the replica's
        if config.phase == "route":
            config = config.replace(route_port=args.port)
        else:
            config = config.replace(serve_port=args.port)
    if args.max_batch is not None:
        config = config.replace(serve_max_batch=args.max_batch)
    if args.max_wait_ms is not None:
        config = config.replace(serve_max_wait_ms=args.max_wait_ms)
    if args.serve_mode is not None:
        config = config.replace(serve_mode=args.serve_mode)
    if args.serve_decode_depth is not None:
        config = config.replace(serve_decode_depth=tuple(
            int(k) for k in args.serve_decode_depth.split(",") if k
        ))
    if args.tenants is not None:
        config = config.replace(tenants=args.tenants)
    if args.serve_metering is not None:
        config = config.replace(serve_metering=args.serve_metering == "on")
    if args.encode_cache is not None:
        config = config.replace(encode_cache=args.encode_cache)
    if args.encode_cache_mb is not None:
        config = config.replace(encode_cache_mb=args.encode_cache_mb)
    if args.serve_tier is not None:
        config = config.replace(serve_tier=args.serve_tier)
    if args.serve_quality is not None:
        config = config.replace(serve_quality=args.serve_quality)
    if args.quality_reference is not None:
        config = config.replace(serve_quality_reference=args.quality_reference)
    if args.slo_quality_psi is not None:
        config = config.replace(slo_quality_psi=args.slo_quality_psi)
    if args.slo_quality_unk is not None:
        config = config.replace(slo_quality_unk=args.slo_quality_unk)
    if args.slo_capacity_headroom_pct is not None:
        config = config.replace(
            slo_capacity_headroom_pct=args.slo_capacity_headroom_pct
        )
    if args.encoder_quant is not None:
        config = config.replace(encoder_quant=args.encoder_quant)
    if args.model_reload is not None:
        config = config.replace(model_reload=args.model_reload)
    if args.canary_fraction is not None:
        config = config.replace(canary_fraction=args.canary_fraction)
    if args.canary_window_s is not None:
        config = config.replace(canary_window_s=args.canary_window_s)
    if args.promote_policy is not None:
        config = config.replace(promote_policy=args.promote_policy)
    if args.bulk_input is not None:
        config = config.replace(bulk_input=args.bulk_input)
    if args.bulk_output is not None:
        config = config.replace(bulk_output=args.bulk_output)
    if args.bulk_shard_rows is not None:
        config = config.replace(bulk_shard_rows=args.bulk_shard_rows)
    if args.watchdog is not None:
        config = config.replace(watchdog_interval=args.watchdog)
    overrides = {}
    for item in args.set:
        if "=" not in item:
            raise SystemExit(f"--set expects KEY=VALUE, got {item!r}")
        key, raw = item.split("=", 1)
        key = _REFERENCE_KEY_ALIASES.get(key, key)
        overrides[key] = _parse_override(config, key, raw)
    if overrides:
        config = config.replace(**overrides)
    # env-driven path re-rooting (SAT_DATA_ROOT / SAT_LOG_ROOT); explicit
    # --set overrides win because re-rooting only touches default values
    config = config.apply_env_paths()
    # checked against the RESOLVED phase so `--sweep --config <eval cfg>`
    # works without restating --phase
    if args.sweep and config.phase != "eval":
        raise SystemExit("--sweep only applies to --phase=eval")

    cli = {
        "load": args.load,
        "model_file": args.model_file,
        "load_cnn": args.load_cnn,
        "cnn_model_file": args.cnn_model_file,
        "sweep": args.sweep,
        "print_config": args.print_config,
        "supervise": args.supervise,
        "max_restarts": args.max_restarts,
        "repair_shards": args.repair_shards,
    }
    return config, cli


def _postmortem(reason: str, exit_code: "Optional[int]" = None, **fields) -> None:
    """Best-effort black-box bundle on an abnormal CLI exit path — a
    no-op unless the run installed a recorder (``--blackbox``)."""
    try:
        from .telemetry import blackbox as _blackbox

        _blackbox.dump(reason, exit_code=exit_code, **fields)
    except Exception:
        pass  # the process is already dying; forensics must not mask why


def main(argv: Optional[List[str]] = None) -> int:
    config, cli = build_config(argv)

    if cli["print_config"]:
        import json

        print(json.dumps(config.to_dict(), indent=2, sort_keys=True))
        return 0

    if cli["repair_shards"]:
        # jax-free maintenance mode: rot repair touches only the shard
        # files and manifest (data/integrity.py)
        import json

        from .data.integrity import repair_shards

        try:
            report = repair_shards(config)
        except FileNotFoundError:
            print(
                "sat_tpu: --repair_shards: no shard cache exists for this "
                f"config (looked under {config.shard_cache_dir!r})",
                file=sys.stderr,
                flush=True,
            )
            return 2
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0

    if cli["supervise"]:
        # the supervisor parent must NEVER import jax: a chip belongs to
        # one process at a time, so a parent that touched the device
        # stack would hold the chip its child needs — dispatch to the
        # restart loop before the jax bootstrap below.  The child
        # re-enters this CLI without --supervise/--max_restarts.
        from .resilience.supervisor import supervise

        return supervise(
            list(argv) if argv is not None else list(sys.argv[1:]),
            max_restarts=(
                cli["max_restarts"]
                if cli["max_restarts"] is not None
                else config.supervise_max_restarts
            ),
            backoff_base_s=config.supervise_backoff_s,
        )

    if config.phase == "route":
        # the fleet router is jax-free by the same contract as the
        # supervisor parent: it holds no chip and outlives a replica
        # whose device runtime dies, so dispatch before the jax bootstrap
        # below — the replicas it spawns re-enter this CLI in --phase
        # serve and own one chip each (serve/replica.py).
        from .serve.router import route

        return route(config)

    # multi-host bootstrap first, before any other jax use (no-op unless a
    # launcher/env signals a cluster — see parallel.mesh)
    from .parallel import initialize_distributed

    initialize_distributed()

    import jax

    # one persistent compile cache for every jax-using phase: where
    # JAX_COMPILATION_CACHE_DIR says, else <repo>/.jax_cache
    from .utils.compile_cache import enable as _enable_compile_cache

    _enable_compile_cache(jax)

    from . import runtime
    from .resilience import CheckpointWriteError, SimulatedPreemption
    from .resilience import retry as _retry
    from .resilience.quarantine import (
        DATA_CORRUPTION_EXIT_CODE,
        SystemicCorruption,
    )

    # process-wide IO-retry knobs for every phase (train re-applies them,
    # but eval/test read shards and caption files through retry_io too)
    _retry.configure(config.io_retries, config.io_retry_base_s)

    # the run's telemetry begins here, once, so that set-up (data, the
    # restore, the first dispatch) is inside it; the loops take it over
    tel = (
        runtime._telemetry_begin(config)
        if config.phase in ("train", "eval", "test")
        else None
    )

    if config.phase == "train":
        state = runtime.setup_state(
            config,
            load=cli["load"],
            model_file=cli["model_file"],
            load_cnn=cli["load_cnn"],
            cnn_model_file=cli["cnn_model_file"],
        )
        try:
            runtime.train(config, state=state, tel=tel)
        except CheckpointWriteError as e:
            # the run trained but a checkpoint it depends on did not land
            # — warn + non-zero exit instead of a swallowed queue failure
            # or a bare traceback (docs/RESILIENCE.md)
            print(f"sat_tpu: WARNING: {e}", file=sys.stderr, flush=True)
            _postmortem("checkpoint_write_failed", 1, error=str(e))
            return 1
        except SimulatedPreemption as e:
            # injected die-at-step-k: behave like the preempted process
            # the injection simulates (non-zero exit; supervisor relaunches
            # with --load)
            print(f"sat_tpu: {e}", file=sys.stderr, flush=True)
            _postmortem("simulated_preemption", 1, error=str(e))
            return 1
        except SystemicCorruption as e:
            # the quarantine ceiling tripped: the input data is rotten,
            # not the process — a distinct exit code the supervisor
            # refuses to restart (a rerun re-reads the same rot)
            print(f"sat_tpu: FATAL: {e}", file=sys.stderr, flush=True)
            _postmortem(
                "systemic_corruption", DATA_CORRUPTION_EXIT_CODE, error=str(e)
            )
            return DATA_CORRUPTION_EXIT_CODE
        except Exception as e:
            # any other crash: leave forensics behind, then fail loudly
            # with the original traceback
            _postmortem("uncaught_exception", None, error=repr(e))
            raise
        # graceful SIGTERM/SIGINT: train() drained and returned normally —
        # fall through to exit 0 so the supervisor relaunches into --load
    elif config.phase == "serve":
        from .serve.server import serve as _serve

        return _serve(config, model_file=cli["model_file"])
    elif config.phase == "bulk":
        from .bulk.runner import run_bulk

        try:
            return run_bulk(config, model_file=cli["model_file"])
        except SimulatedPreemption as e:
            # injected die-at-step-k: behave like a real preemption — the
            # supervisor relaunches and the manifest frontier resumes
            print(f"sat_tpu: {e}", file=sys.stderr, flush=True)
            _postmortem("simulated_preemption", 1, error=str(e))
            return 1
        except SystemicCorruption as e:
            # quarantine ceiling: the corpus is rotten, not the process —
            # exit 87, which the supervisor refuses to restart
            print(f"sat_tpu: FATAL: {e}", file=sys.stderr, flush=True)
            _postmortem(
                "systemic_corruption", DATA_CORRUPTION_EXIT_CODE, error=str(e)
            )
            return DATA_CORRUPTION_EXIT_CODE
        except Exception as e:
            _postmortem("uncaught_exception", None, error=repr(e))
            raise
    elif config.phase == "eval":
        if cli["sweep"]:
            sweep = runtime.evaluate_sweep(config)
            for step in sorted(sweep):
                line = "  ".join(f"{k}={v:.4f}" for k, v in sweep[step].items())
                print(f"step {step}: {line}")
            return 0
        state = runtime.setup_state(
            config, load=True, model_file=cli["model_file"]
        )
        scores = runtime.evaluate(config, state=state, tel=tel)
        for k, v in scores.items():
            print(f"{k}: {v:.4f}")
    else:
        state = runtime.setup_state(
            config, load=True, model_file=cli["model_file"]
        )
        runtime.test(config, state=state, tel=tel)
    return 0


if __name__ == "__main__":
    sys.exit(main())
