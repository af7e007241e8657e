"""Device-resident array cache: the HBM half of the bucketed columnar
container (SURVEY.md §2.3).

Decoded index tables are host-cached and FROZEN (execution/io.py); their
arrays are therefore identity-stable for as long as they live. This
module keys derived artifacts on that identity — `(id(base), variant)` —
while holding a reference to the base array so the id can never be
recycled underneath an entry. Refresh/rebuild produces new host arrays
with new ids, so invalidation is automatic; eviction is LRU under a byte
budget.

Two instances cover the read hot path:
- DEVICE_CACHE: uploaded (padded, optionally sharded) `jax.Array`s —
  repeat queries over the same index version serve straight from HBM
  instead of re-staging over the host link;
- HOST_DERIVED: host-side derived arrays (order-preserving 64-bit key
  words, join key codes, bucket-major pads) that would otherwise be
  recomputed per query. Entries are frozen on insert so they are
  themselves valid cache bases.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from hyperspace_tpu.obs import metrics as obs_metrics


class RefCache:
    """Identity-keyed LRU memo with a byte budget. Entries hold strong
    references to their base arrays, so id()-based keys stay valid for
    the lifetime of the entry. `name` keys the hit/miss/eviction
    counters and byte gauge in the exportable metrics registry."""

    def __init__(self, budget_bytes: int, name: str = "ref_cache"):
        self.budget = int(budget_bytes)
        self._lock = threading.Lock()
        self._entries: dict[tuple, tuple[int, tuple, object]] = {}
        # Single-flight: key -> Event set when that key's in-progress
        # build finishes (docs/serving.md — N concurrent clients missing
        # on the same cold key must not stage the same upload N times).
        self._building: dict[tuple, threading.Event] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self._met_hits = obs_metrics.counter(f"{name}.hits")
        self._met_misses = obs_metrics.counter(f"{name}.misses")
        self._met_evictions = obs_metrics.counter(f"{name}.evictions")
        self._met_bytes = obs_metrics.gauge(f"{name}.bytes", "resident cached bytes")

    def get_or_build(self, key: tuple, base_refs: tuple, build, wait_timeout: float | None = None):
        """`build() -> (value, nbytes)`; value cached under `key` while
        `base_refs` are pinned. Concurrent misses on the same key are
        single-flighted: one caller builds, the rest wait on its event
        and then hit (a waiter re-builds only if the value turned out
        too large to cache — same cost as before the dedup).

        `wait_timeout` bounds each single-flight wait: a waiter whose
        wait expires builds LOCALLY without claiming the building slot
        (the slot still belongs to the stuck builder), so an abandoned
        in-process build — a builder thread wedged in device staging, or
        killed in a way that never sets its event — cannot block waiters
        forever. None preserves the original unbounded wait."""
        while True:
            with self._lock:
                hit = self._entries.get(key)
                if hit is not None:
                    self._entries[key] = self._entries.pop(key)  # LRU touch
                    self.hits += 1
                    self._met_hits.inc()
                    return hit[2]
                ev = self._building.get(key)
                if ev is None:
                    self._building[key] = threading.Event()
                    self.misses += 1
                    break  # this caller builds
            if not ev.wait(wait_timeout):
                # Timed out on another caller's build: fall through to a
                # local build. No slot ownership — the original builder
                # (if it ever finishes) still sets and clears its event.
                with self._lock:
                    self.misses += 1
                self._met_misses.inc()
                value, nbytes = build()
                evicted = self._insert(key, base_refs, value, nbytes)
                if evicted:
                    self._met_evictions.inc(evicted)
                return value
            # Re-check: usually a hit now. If the builder failed or the
            # value was uncacheable, the building slot is free again and
            # this caller becomes the builder on the next lap.
        self._met_misses.inc()
        try:
            value, nbytes = build()
        except BaseException:
            with self._lock:
                self._building.pop(key).set()
            raise
        with self._lock:
            evicted = self._insert_locked(key, base_refs, value, nbytes)
            self._building.pop(key).set()
        if evicted:
            self._met_evictions.inc(evicted)
        return value

    def _insert(self, key: tuple, base_refs: tuple, value, nbytes: int) -> int:
        with self._lock:
            return self._insert_locked(key, base_refs, value, nbytes)

    def _insert_locked(self, key: tuple, base_refs: tuple, value, nbytes: int) -> int:
        """Admit a built value under the byte budget; returns evictions.
        Caller holds `self._lock`."""
        evicted = 0
        if nbytes <= self.budget // 4 and key not in self._entries:
            self._entries[key] = (nbytes, base_refs, value)
            self._bytes += nbytes
            while self._bytes > self.budget and self._entries:
                k = next(iter(self._entries))
                nb, _, _ = self._entries.pop(k)
                self._bytes -= nb
                evicted += 1
        self._met_bytes.set(self._bytes)
        return evicted

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            self.hits = 0
            self.misses = 0
            self._met_bytes.set(0)

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "hits": self.hits,
                "misses": self.misses,
            }


DEVICE_CACHE = RefCache(
    int(os.environ.get("HYPERSPACE_DEVICE_CACHE_BYTES", 2 << 30)), name="device_cache"
)
HOST_DERIVED = RefCache(
    int(os.environ.get("HYPERSPACE_DERIVED_CACHE_BYTES", 1 << 30)), name="host_derived"
)


def table_footprint_bytes(table) -> int:
    """Canonical ColumnTable byte accounting for every byte-budgeted
    cache (io decoded-table cache, HOST_DERIVED side entries, the serve
    result cache). Dictionary-coded string columns count at their
    (codes + dictionary payload) footprint: the int32 code array plus
    the summed character payload (+ pointer word) of the SMALL
    dictionary — never the inflated per-row string size, and never a
    ``<U``-dtype dictionary's UTF-32-padded ``.nbytes`` (which scales
    with the LONGEST entry times the entry count). Over-counting here
    evicted dict-coded columns far too eagerly: a 4M-row dict column is
    ~16 MB of codes, not the hundreds of MB its decoded strings would
    occupy."""
    total = sum(int(v.nbytes) for v in table.columns.values())
    total += sum(int(v.nbytes) for v in table.validity.values())
    for d in table.dictionaries.values():
        total += sum(len(str(s)) for s in d.tolist()) + 8 * len(d)
    return int(total)


def is_stable(arr: np.ndarray) -> bool:
    """True when the array's identity is a valid cache key: frozen arrays
    (decoded-table cache entries and HOST_DERIVED values) never mutate
    and are pinned by the entry that caches against them."""
    return isinstance(arr, np.ndarray) and not arr.flags.writeable


def freeze(arr: np.ndarray) -> np.ndarray:
    arr.flags.writeable = False
    return arr


def _x64_now() -> bool:
    """jnp.asarray dtype resolution depends on the ACTIVE x64 scope
    (float64 downcasts to float32 outside it) — the upload key must
    distinguish the two or a cross-scope hit would return the wrong
    device dtype."""
    import jax

    return bool(jax.config.jax_enable_x64)


def device_put_padded(arr: np.ndarray, n_pad: int, sharding=None):
    """Upload `arr` padded with zeros to length n_pad (row dim), through
    DEVICE_CACHE when the base is stable. `sharding` is a
    jax.sharding.Sharding or None."""
    import jax
    import jax.numpy as jnp

    def build():
        a = arr
        if len(a) != n_pad:
            a = np.concatenate([a, np.zeros(n_pad - len(a), dtype=a.dtype)])
        dev = jnp.asarray(a) if sharding is None else jax.device_put(a, sharding)
        return dev, int(dev.nbytes)

    if not is_stable(arr):
        return build()[0]
    skey = None
    if sharding is not None:
        try:
            skey = (str(sharding.mesh.shape), str(sharding.spec))
        except Exception:
            skey = repr(sharding)
    return DEVICE_CACHE.get_or_build(
        ("pad", id(arr), n_pad, skey, _x64_now()), (arr,), build
    )


def device_put_cached(arr: np.ndarray):
    """Upload `arr` as-is, through DEVICE_CACHE when stable."""
    import jax.numpy as jnp

    def build():
        dev = jnp.asarray(arr)
        return dev, int(dev.nbytes)

    if not is_stable(arr):
        return build()[0]
    return DEVICE_CACHE.get_or_build(
        ("raw", id(arr), arr.shape, _x64_now()), (arr,), build
    )


def derived(key: tuple, base_refs: tuple, build_host):
    """Memoize a host-derived array of stable bases; the value is frozen
    so it can serve as a cache base itself. `build_host() -> np.ndarray`."""

    def build():
        out = build_host()
        return freeze(out), int(out.nbytes)

    return HOST_DERIVED.get_or_build(key, base_refs, build)


def clear_all() -> None:
    DEVICE_CACHE.clear()
    HOST_DERIVED.clear()
