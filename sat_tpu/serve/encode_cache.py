"""Device-resident, content-addressed LRU of encoder feature grids.

The conv encoder is the single biggest serve-path cost and a pure
function of the preprocessed image — yet every duplicate image pays it
again.  PR 18's live ``EncodeCacheSketch`` probe measured a 0.77
would-be hit ratio under Zipf traffic, so this module closes the loop:
a fixed-geometry HBM ring of ``[rows, N, D]`` context grids, keyed by
``(image crc32c, param fingerprint, quant mode)``, with host-side LRU
bookkeeping and two AOT-warmed device programs per dispatch width —

* **gather** ``store[idx] -> [w, N, D]`` feeds the existing seed/beam
  executables the exact bits a fresh encode would have produced (rows
  are written once and read verbatim, so hit-path captions are bitwise
  identical to the encode path);
* **insert** ``store.at[idx].set(ctx)`` scatters a miss lane's freshly
  encoded rows into their assigned ring rows (pad rows land in a
  scratch row nobody reads).

Both are compiled at warmup for every dispatch width the server can
see (the bucket ladder in batch mode, the admission lanes in
continuous mode), so steady state never recompiles — the same
zero-recompile contract as the rest of the serve path.

Single-flight coalescing falls out of the planning discipline: one
batcher/pool thread owns all plans, a plan dedupes repeated keys within
its chunk (one encode, N seeds), and the host map is updated at plan
time, so N concurrent requests for one image trigger exactly one
encode however they land across chunks.

Capacity comes from ``--encode_cache_mb``; ``--encode_cache off``
never constructs this class, keeping serving bit-identical to the
pre-cache path with zero compile delta (pinned by
tests/test_encode_cache.py).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import numpy as np


def gather_rows(store, idx):
    """The hit path: rows ``idx`` of the ring."""
    return store[idx]


def insert_rows(store, ctx, idx):
    """The miss path: write ``ctx`` into rows ``idx`` of the ring.
    Duplicate scratch indices are fine: scratch is write-only."""
    return store.at[idx].set(ctx)


class CachePlan(object):
    """One chunk's resolved lookup: a ring row per request, plus the
    unique misses that must be encoded (first occurrence wins; repeats
    within the chunk are coalesced onto the same row)."""

    __slots__ = ("rows", "miss_keys", "miss_rows", "miss_pos", "hits",
                 "coalesced")

    def __init__(self) -> None:
        self.rows: List[int] = []       # ring row per chunk item
        self.miss_keys: List[Hashable] = []  # unique keys to encode
        self.miss_rows: List[int] = []  # ring row per unique miss
        self.miss_pos: List[int] = []   # chunk position of each first miss
        self.hits = 0
        self.coalesced = 0

    @property
    def n_miss(self) -> int:
        return len(self.miss_keys)


class EncodeCache(object):
    """Fixed-geometry HBM ring + host LRU map + AOT gather/insert.

    Device geometry is decided once at warmup (``ensure_store``) from
    the context-row aval and the MB budget, and never changes; the host
    map is guarded by a small lock because ``/stats`` scrapes read it
    from HTTP threads while the single batcher thread plans against it.
    """

    def __init__(self, capacity_mb: int, tel=None) -> None:
        self.capacity_mb = int(capacity_mb)
        self._tel = tel
        self._lock = threading.Lock()
        self._store = None          # device [rows+1, N, D]; row `rows` = scratch
        self.rows = 0               # usable ring rows (excludes scratch)
        self.row_shape: Optional[Tuple[int, ...]] = None
        self.row_dtype = None
        self.row_bytes = 0
        self._map: "OrderedDict[Hashable, int]" = OrderedDict()
        self._free: List[int] = []
        self._gather_execs: Dict[int, Any] = {}
        self._insert_execs: Dict[int, Any] = {}
        # lifetime counters (the /stats cache block; tel counters mirror
        # them so /metrics exports ride promtext for free)
        self.hits = 0
        self.misses = 0
        self.coalesced = 0
        self.evictions = 0
        self.inserts = 0
        self.flushes = 0

    # -- warmup (called from engine/pool warmup, before ready) -------------

    def ensure_store(
        self, row_shape: Sequence[int], row_dtype, min_rows: int
    ) -> None:
        """Allocate the ring once: ``capacity_mb`` worth of rows, floored
        at ``min_rows + 1`` so one dispatch-width chunk of distinct
        misses can always be planned without evicting a row the same
        plan just pinned.  Idempotent for a matching row aval (the
        re-warm path); a different aval means different params geometry
        and raises — the cache must be rebuilt, not silently reshaped."""
        import jax.numpy as jnp

        row_shape = tuple(int(d) for d in row_shape)
        if self._store is not None:
            if row_shape != self.row_shape or np.dtype(row_dtype) != np.dtype(
                self.row_dtype
            ):
                raise ValueError(
                    f"encode cache store is {self.row_shape}/{self.row_dtype} "
                    f"but warmup now wants {row_shape}/{row_dtype}"
                )
            return
        self.row_shape = row_shape
        self.row_dtype = np.dtype(row_dtype)
        self.row_bytes = int(
            np.prod(row_shape, dtype=np.int64) * self.row_dtype.itemsize
        )
        budget_rows = int(self.capacity_mb * 1e6) // max(1, self.row_bytes)
        self.rows = max(int(min_rows) + 1, budget_rows)
        self._store = jnp.zeros(
            (self.rows + 1,) + row_shape, self.row_dtype
        )
        self._free = list(range(self.rows))
        if self._tel is not None:
            self._tel.gauge("serve/cache_rows", self.rows)
            self._tel.gauge(
                "serve/cache_capacity_bytes", self.rows * self.row_bytes
            )

    def warm(self, widths: Sequence[int]) -> None:
        """AOT-compile gather + insert for every dispatch width; called
        after ``ensure_store``.  ``jit.lower(...).compile()`` like every
        other serve program, so the executables only ever run at their
        compiled shapes and steady state cannot recompile."""
        import jax

        if self._store is None:
            raise RuntimeError("EncodeCache.warm before ensure_store")
        store_sd = jax.ShapeDtypeStruct(
            (self.rows + 1,) + self.row_shape, self.row_dtype
        )

        gather_jit = jax.jit(gather_rows)
        # the store is donated so an insert rewrites the ring in place
        # instead of copying capacity_mb per miss chunk (a no-op warning
        # on backends without donation, e.g. the CPU test container)
        insert_jit = jax.jit(insert_rows, donate_argnums=0)
        for w in widths:
            w = int(w)
            if w in self._gather_execs:
                continue
            idx_sd = jax.ShapeDtypeStruct((w,), np.int32)
            ctx_sd = jax.ShapeDtypeStruct(
                (w,) + self.row_shape, self.row_dtype
            )
            self._gather_execs[w] = gather_jit.lower(
                store_sd, idx_sd
            ).compile()
            self._insert_execs[w] = insert_jit.lower(
                store_sd, ctx_sd, idx_sd
            ).compile()

    @property
    def warm_widths(self) -> Tuple[int, ...]:
        return tuple(sorted(self._gather_execs))

    # -- planning (single batcher/pool thread) -----------------------------

    def plan(self, keys: Sequence[Hashable]) -> CachePlan:
        """Resolve one chunk of content keys to ring rows, assigning LRU
        rows to the unique misses (the single-flight dedup: a key
        repeated within the chunk coalesces onto its first row).  The
        map is updated NOW — before the encode lands — because one
        thread owns all plans, so a later chunk referencing the same
        key must hit, not re-encode.  Callers that fail the dispatch
        must ``drop`` the planned miss keys."""
        plan = CachePlan()
        with self._lock:
            pinned = set()
            seen_miss: Dict[Hashable, int] = {}
            for i, key in enumerate(keys):
                row = self._map.get(key)
                if row is not None and key not in seen_miss:
                    self._map.move_to_end(key)
                    plan.hits += 1
                    plan.rows.append(row)
                    pinned.add(row)
                    continue
                if key in seen_miss:
                    plan.coalesced += 1
                    plan.rows.append(plan.miss_rows[seen_miss[key]])
                    continue
                row = self._alloc_row(pinned)
                seen_miss[key] = len(plan.miss_keys)
                plan.miss_keys.append(key)
                plan.miss_rows.append(row)
                plan.miss_pos.append(i)
                self._map[key] = row
                self._map.move_to_end(key)
                pinned.add(row)
                plan.rows.append(row)
            self.hits += plan.hits
            self.misses += plan.n_miss
            self.coalesced += plan.coalesced
        if self._tel is not None:
            if plan.hits:
                self._tel.count("serve/cache_hits", plan.hits)
            if plan.n_miss:
                self._tel.count("serve/cache_misses", plan.n_miss)
            if plan.coalesced:
                self._tel.count("serve/cache_coalesced", plan.coalesced)
        return plan

    def _alloc_row(self, pinned) -> int:
        """A free row, else evict the least-recently-used entry whose row
        is not pinned by the current plan (``ensure_store`` floors the
        ring at one row past the widest chunk, so one always exists)."""
        if self._free:
            return self._free.pop()
        for key, row in self._map.items():  # oldest first
            if row not in pinned:
                del self._map[key]
                self.evictions += 1
                if self._tel is not None:
                    self._tel.count("serve/cache_evictions")
                return row
        raise RuntimeError(
            "encode cache has no evictable row (ring smaller than one "
            "dispatch chunk — ensure_store floor violated)"
        )

    def drop(self, keys: Sequence[Hashable]) -> None:
        """Un-plan miss keys whose encode/insert failed: their rows hold
        garbage, so the entries must not serve hits."""
        with self._lock:
            for key in keys:
                row = self._map.pop(key, None)
                if row is not None:
                    self._free.append(row)

    # -- device programs ---------------------------------------------------

    def insert(self, width: int, lane_ctx, rows: Sequence[int]):
        """Scatter a freshly encoded ``[width, N, D]`` lane into the ring
        at ``rows`` (pad lane rows land in the scratch row).  Rebinding
        the donated store keeps device-stream ordering: any gather
        dispatched after this insert sees the new rows."""
        import jax

        idx = np.full((int(width),), self.rows, np.int32)
        idx[: len(rows)] = rows
        self._store = self._insert_execs[int(width)](
            self._store, lane_ctx, jax.device_put(idx)
        )
        self.inserts += len(rows)

    def gather(self, width: int, rows: Sequence[int]):
        """``[width, N, D]`` of ring rows (pad positions read the scratch
        row — beam search is row-independent, so scratch garbage never
        perturbs real rows, exactly like zero-padded encode lanes)."""
        import jax

        idx = np.full((int(width),), self.rows, np.int32)
        idx[: len(rows)] = rows
        return self._gather_execs[int(width)](
            self._store, jax.device_put(idx)
        )

    # -- invalidation (lifecycle/quant coherence) --------------------------

    def flush(self) -> None:
        """Forget every entry (model promote/rollback): keys carry the
        param fingerprint so stale entries could never hit anyway, but
        flushing returns their rows to the free list immediately instead
        of waiting out LRU churn.  Device rows become unreferenced
        garbage — no device work."""
        with self._lock:
            self._map.clear()
            self._free = list(range(self.rows))
            self.flushes += 1
        if self._tel is not None:
            self._tel.count("serve/cache_flushes")

    # -- observability -----------------------------------------------------

    @property
    def lookups(self) -> int:
        return self.hits + self.misses + self.coalesced

    def hit_ratio(self) -> float:
        """Fraction of lookups that skipped the encode lane — coalesced
        requests rode another request's single-flight encode, so they
        count as hits (matching what the would-hit sketch observes)."""
        n = self.lookups
        return (self.hits + self.coalesced) / n if n else 0.0

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            entries = len(self._map)
        return {
            "entries": entries,
            "rows": self.rows,
            "bytes": entries * self.row_bytes,
            "capacity_bytes": self.rows * self.row_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "coalesced": self.coalesced,
            "evictions": self.evictions,
            "inserts": self.inserts,
            "flushes": self.flushes,
            "hit_ratio": round(self.hit_ratio(), 4),
            "warm_widths": list(self.warm_widths),
        }
