"""Open-loop HTTP load generator.  A process of its own: it never imports
jax or the program, so it shares neither the chip nor the server's
interpreter.

    python benchmark/loadgen.py --port P --images DIR --seed S \
        --schedule-seed Q --rates 50 --seconds 20 --out results.json

Arrivals are on an ABSOLUTE schedule: request i is due at t0 + due[i]
whatever happened to the requests before it.  They are a Poisson process:
i.i.d. exponential gaps (see ``schedule``), so a second of the window
carries rate +- sqrt(rate) requests.  The sample path is drawn from the
MIX's ``schedule_seed`` and not from ``--seed``: on paths of their own,
the median latency of 1000 requests at 0.8 of the knee moved 8% from seed
to seed (PERF.md section 2), more than any bound under the cap admits.  So
every seed replays the same path, entered at a point of its cycle drawn
from the seed, with other images.
Latency runs from the DUE instant to the last byte of the reply; how late
a request was sent (send - due) is reported beside it.  429, 5xx, a bad
body and a time-out are failures.  Several ``--rates`` give one window
after another against the same server (the knee sweep).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import queue
import sys
import threading
import time
from typing import List

import numpy as np


def schedule(rate: float, seconds: float, schedule_seed: int, turn: float = 0.0) -> np.ndarray:
    """Due offsets (s) of the window's n = round(rate * seconds) requests:
    a Poisson process of ``rate`` given that n arrivals fall in the window.
    n + 1 i.i.d. exponential gaps are drawn from ``schedule_seed`` (and n),
    scaled to sum to ``seconds`` (gap 0 is the silence before the first
    request, gap n the one after the last), and the cycle of gaps is
    entered ``turn`` (a share, 0..1) of the way round: the same gaps in
    the same cyclic order, bursts and lulls and all, at other instants."""
    n = max(2, int(round(rate * seconds)))
    rng = np.random.default_rng([int(schedule_seed), n])
    gaps = rng.exponential(1.0 / rate, n + 1)
    gaps *= seconds / gaps.sum()
    gaps = np.roll(gaps, -int(turn * (n + 1)) % (n + 1))
    return np.cumsum(gaps)[:n]


def wait_ready(port: int, timeout_s: float, parent: int) -> None:
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        if parent and not _alive(parent):
            raise SystemExit("loadgen: the server process is gone")
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = json.loads(resp.read() or b"{}")
            conn.close()
            if resp.status == 200 and body.get("ready"):
                return
        except (OSError, ValueError, http.client.HTTPException):
            pass
        time.sleep(0.2)
    raise SystemExit(f"loadgen: port {port} not ready after {timeout_s:.0f}s")


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    return True


class Client:
    """One keep-alive connection; reconnects once on a dropped socket."""

    def __init__(self, port: int, timeout: float) -> None:
        self.port, self.timeout, self.conn = port, timeout, None

    def post(self, body: bytes):
        for attempt in (0, 1):
            try:
                if self.conn is None:
                    self.conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=self.timeout)
                self.conn.request("POST", "/caption", body=body,
                                  headers={"Content-Type": "image/jpeg"})
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (OSError, http.client.HTTPException) as e:
                if self.conn is not None:
                    self.conn.close()
                self.conn = None
                if attempt or isinstance(e, TimeoutError):
                    return 0, repr(e).encode()
        return 0, b""

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()


def one(client: Client, body: bytes) -> dict:
    status, raw = client.post(body)
    out = {"status": status, "end": time.time()}
    if status == 200:
        try:
            top = json.loads(raw)["captions"][0]
            out["caption"], out["log_prob"] = top["caption"], top["log_prob"]
        except (ValueError, KeyError, IndexError, TypeError):
            out["status"] = -1           # 200 with a malformed body is a failure
    return out


def run_window(port, bodies, order, due, threads, timeout) -> List[dict]:
    """Send request i (image order[i]) at t0 + due[i]; returns one record
    per request, in schedule order."""
    jobs: "queue.Queue" = queue.Queue()
    records: List[dict] = [None] * len(due)  # type: ignore[list-item]

    def worker() -> None:
        client = Client(port, timeout)
        while True:
            job = jobs.get()
            if job is None:
                break
            i, due_at = job
            sent = time.time()
            rec = one(client, bodies[order[i]])
            rec.update(i=i, image=int(order[i]), due=due_at, sent=sent)
            records[i] = rec
        client.close()

    pool = [threading.Thread(target=worker, daemon=True) for _ in range(threads)]
    for t in pool:
        t.start()
    t0 = time.time() + 0.05
    print(f"WINDOW {t0:.6f} {len(due)}", flush=True)
    for i, offset in enumerate(due):
        target = t0 + float(offset)
        while True:
            now = time.time()
            if now >= target:
                break
            time.sleep(min(target - now, 0.002) if target - now > 0.0005 else 0)
        jobs.put((i, target))
    for _ in pool:
        jobs.put(None)
    for t in pool:
        t.join()
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--images", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--schedule-seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--threads", type=int, default=24)
    ap.add_argument("--timeout", type=float, default=30.0)
    ap.add_argument("--warmup", type=int, default=24)
    ap.add_argument("--ready-timeout", type=float, default=1500.0)
    ap.add_argument("--parent", type=int, default=0)
    args = ap.parse_args(argv)

    files = sorted(f for f in os.listdir(args.images) if f.endswith(".jpg"))
    bodies = []
    for name in files:
        with open(os.path.join(args.images, name), "rb") as f:
            bodies.append(f.read())
    rates = [float(r) for r in args.rates.split(",") if r]
    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, args.seed >> 32, 7])
    turn = float(rng.random())
    plans = [schedule(r, args.seconds, args.schedule_seed, turn) for r in rates]
    need = args.warmup + sum(len(p) for p in plans)
    if need > len(bodies):
        raise SystemExit(f"loadgen: {need} distinct images needed, {len(bodies)} generated")
    order = rng.permutation(len(bodies))

    wait_ready(args.port, args.ready_timeout, args.parent)
    # warm-up: a few one at a time, then the rest at once (every admission
    # lane and the deep decode windows); these images are never reused
    client = Client(args.port, args.timeout)
    first_ok = None
    solo = min(8, args.warmup)
    for i in range(solo):
        rec = one(client, bodies[order[i]])
        if rec["status"] == 200 and first_ok is None:
            first_ok = rec["end"]
    client.close()
    burst = run_window(args.port, bodies, order[solo:args.warmup],
                       np.zeros(args.warmup - solo), args.threads, args.timeout)
    warm_failed = sum(1 for r in burst if r["status"] != 200)
    if first_ok is None or warm_failed:
        raise SystemExit(f"loadgen: warm-up failed (first_ok={first_ok}, burst failures={warm_failed})")

    used = args.warmup
    windows = []
    for rate, due in zip(rates, plans):
        recs = run_window(args.port, bodies, order[used:used + len(due)], due,
                          args.threads, args.timeout)
        used += len(due)
        windows.append({"rate": rate, "seconds": args.seconds, "records": recs})
    with open(args.out + ".tmp", "w") as f:
        json.dump({"first_ok_unix": first_ok, "files": files, "windows": windows}, f)
    os.replace(args.out + ".tmp", args.out)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
