"""Process-wide fault-tolerance counters — compat shim over the metrics
registry.

Historically this module held its own ad-hoc ``dict[str, int]``; it is
now a thin facade over the declared registry in
`hyperspace_tpu/obs/metrics.py`, keeping the call-site API
(``increment``/``get``/``snapshot``/``reset``) stable for the fault
plane while everything lands in one exportable place
(docs/observability.md).

Counter names are **declared** in :data:`KNOWN_COUNTERS`; incrementing
an undeclared name raises immediately instead of silently creating a new
counter (the ``increment("retyr.attempts")`` typo class). Lint rule
HSL007 flags undeclared constant names at call sites too, so the typo
never survives to runtime. New counters are added by extending the
tuple below (and its docstring row).

Counter names in use:

- ``retry.attempts``       extra attempts made after a transient failure
- ``retry.exhausted``      retry loops that gave up and re-raised
- ``faults.injected``      faults the injection harness actually fired
- ``index.corruption``     typed corruption detections (bucket/manifest)
- ``fallback.queries``     queries re-planned against source data
- ``action.rolled_back``   op() failures rolled back to the last stable state
- ``recover.rolled``       recover() roll-forwards of a transient log
- ``recover.quarantined_entries``  torn log entries quarantined by recover()
- ``recover.orphans_removed``      unreferenced version dirs GC'd by recover()
- ``metadata.cache.hits``    TTL index-entry cache hits (metadata/cache.py)
- ``metadata.cache.misses``  TTL index-entry cache misses (empty or expired)
- ``action.rollback_failed``  in-process rollback attempts that themselves
  failed (recover() finishes the repair from the next process)
- ``action.cleanup_failed``   partial-data quarantines that failed (the
  orphan GC in recover() sweeps what they left)
- ``recover.on_access_failed``  lazy recover-on-access attempts that
  failed during listing (the entry stays unlisted; explicit recover()
  still applies)
- ``io.footer_cache.hits``    parquet footer parses skipped by the
  mtime-validated footer cache (execution/io.py)
- ``io.footer_cache.misses``  footer parses that actually opened the file
- ``jit_memory.cache_drops``  jax cache drops by the map-count guard
  (utils/jit_memory.py) — each one is a narrowly avoided XLA:CPU
  map-exhaustion segfault, paired with a WARN ``jit.cache_drop`` event
- ``fleet.shared_cache.hits``    disk-backed shared plan/result cache hits
  (serve/fleet/shared_cache.py)
- ``fleet.shared_cache.misses``  shared-cache lookups that found no entry
- ``fleet.shared_cache.evictions``  entries removed by the lease-held
  byte-budget eviction
- ``fleet.shared_cache.errors``  advisory shared-cache IO failures
  (unreadable/unwritable entries — the caller recomputes locally)
- ``fleet.singleflight.leader``  cross-process single-flight claims won
  (this process did the build)
- ``fleet.singleflight.follower_hits``  waits that ended by observing the
  leader's published artifact
- ``fleet.singleflight.takeovers``  stale leases reaped from a crashed
  holder (the fleet un-wedged itself)
- ``fleet.singleflight.local_fallbacks``  waits that expired and fell
  back to a local build (no dedup, full correctness)
- ``fleet.supervisor.restarts``  crashed fleet workers respawned by the
  supervisor (serve/fleet/supervisor.py)
- ``build.exchange.bytes``  decoded bytes exchanged through spill files
  between the pooled build's p1 shards and p2 owners (the cross-process
  ledger, execution/build_exchange.py)
- ``build.worker.crashes``  pooled-build workers found dead without a
  posted result — each one became a typed WorkerCrashed abort instead
  of a hung coordinator (parallel/procpool.py)
- ``device.stage.bytes_zero_copy``  column bytes that crossed the
  Arrow→device staging boundary as read-only buffer VIEWS — no host
  materialization (execution/staging.py, docs/architecture.md "device
  data path")
- ``device.stage.bytes_copied``  column bytes host-materialized during
  staging (nulls, casts, multi-chunk concat, unaligned offset views,
  staging disabled, or the un-cached downgrade path)
- ``device.kernel.fused``  fused Pallas kernel launches on the device
  venue (join-agg run bounds, top-k tiles) — each one replaced a
  multi-dispatch lax composition
- ``device.kernel.fallbacks``  device-venue calls that took the
  always-available jitted lax path while fused kernels were enabled
  (an ineligible shape)
- ``device.kernel.dense_reduce``  device join-aggregates whose padded
  group count let every channel reduce in one dense masked reduction
  over all rows (ops/join_agg.py)
- ``device.kernel.scatter_reduce``  device join-aggregates with too many
  groups for that, which reduce each channel by a segment scatter
- ``device.kernel.bucket_reduce``  device join-aggregates with too many
  groups for the dense reduction whose group keys hold the join key, so
  each bucket's channels reduce into that bucket's own groups
- ``device.kernel.segment_reduce_lax`` / ``_sharded``  device grouped
  aggregates (ops/aggregate.py) by the reduction they took: the jitted
  float64 lax reduce on one device, or the mesh-sharded reduce with one
  collective per channel
- ``device.kernel.agg_channels_device`` / ``_host``  AggSpecs of device
  grouped aggregates whose channels the reduction program builds from
  the staged base columns, or that the host builds first (a CASE, a
  date part, a string extremum)
- ``controller.ticks``  reconciliation steps the self-driving operations
  controller ran while armed (serve/controller.py,
  docs/fault_tolerance.md "self-driving operations")
- ``controller.actuations``  mutations the controller executed through
  the crash-safe protocols (shed engage, quota tighten, heal, sweep)
- ``controller.actuation_failures``  actuations that raised an ordinary
  Exception — recorded (ERROR ``controller.actuation_failed`` event) and
  the reconciliation continued; the failed subsystem's own Action
  rollback already ran
- ``controller.deferred``  actuations the controller decided on but
  held back — per-actuation cooldown still running, background work
  backed off while serve SLOs burn, or observe-only after budget
  exhaustion
- ``controller.heals``  quarantined indexes the controller healed
  (recover() + gated rebuild) without a human in the loop
- ``controller.scale``  fleet scale actuations the controller executed
  (set_target_workers up on sustained saturation, back down on
  recovery)
- ``controller.health_probe_errors``  saturation probes (fleet-health
  aggregate or local server) that raised — the member counts as zero
  load for that tick, but the operator still gets the signal
- ``fleet.worker.scaled``  fleet members added or drained by
  ``FleetSupervisor.set_target_workers`` (counted per member moved,
  paired with an INFO ``fleet.worker.scaled`` event)
- ``faults.delays_injected``  brownout delays the injection harness
  applied (a `delay_s` fault rule firing — the slow-path counterpart
  of ``faults.injected``)
- ``obs.journal.records``  telemetry records appended to this process's
  durable journal (obs/journal.py — events, root spans, metrics
  snapshots, SLO transitions, process markers)
- ``obs.journal.errors``  advisory journal IO failures swallowed by the
  never-raise contract (full disk, unwritable root — the query or
  actuation being observed proceeds untouched)
- ``obs.journal.segments_sealed``  active journal segments atomically
  published as ``segment-<n>.jsonl`` (mkstemp + os.replace)
- ``obs.journal.evictions``  sealed journal segments dropped oldest-first
  by the per-process byte budget (``hyperspace.obs.journal.maxBytes``)
- ``controller.incidents``  incident bundles the controller opened on an
  SLO page, quarantine, or observe-only entry
  (docs/fault_tolerance.md "incident bundles")
- ``controller.incident_errors``  advisory incident-bundle capture
  failures (forensics must never compound the incident)
- ``ingest.ticks``  poll passes the continuous-ingestion daemon ran
  (hyperspace_tpu/ingest/, docs/ingestion.md)
- ``ingest.commits``  micro-batches committed through the incremental
  refresh action (each one is a new crash-safe index version)
- ``ingest.commit_failures``  micro-batch commits that raised an ordinary
  Exception — the Action's own rollback ran; the daemon keeps polling
- ``ingest.rows``  source rows the tailer materialized from CDC
  changelogs into batch files
- ``ingest.bytes``  source bytes the daemon observed arriving (new files
  + materialized CDC batches) — the ingest-throughput ledger
- ``ingest.compactions``  delta-bucket compactions the daemon triggered
  through the gated optimize action
- ``ingest.compact_failures``  compactions that raised an ordinary
  Exception (rolled back by the optimize action itself)
- ``ingest.deferred``  daemon work held back — paused by the controller,
  or compaction deferred behind its gates
- ``ingest.snapshots``  MVCC pinned snapshots taken (ingest/snapshot.py)
- ``ingest.pinned_reads``  queries executed against a pinned snapshot's
  stamp instead of the live latest-stable versions
"""

from __future__ import annotations

from hyperspace_tpu.obs import metrics as _metrics

# The declared counter set. analysis/lint.py parses this tuple (by AST,
# not import — the lint CI job runs dependency-free) to validate
# stats.increment call sites; keep it a plain literal of string
# constants.
KNOWN_COUNTERS = (
    "retry.attempts",
    "retry.exhausted",
    "faults.injected",
    "index.corruption",
    "fallback.queries",
    "action.rolled_back",
    "recover.rolled",
    "recover.quarantined_entries",
    "recover.orphans_removed",
    "metadata.cache.hits",
    "metadata.cache.misses",
    "action.rollback_failed",
    "action.cleanup_failed",
    "recover.on_access_failed",
    "io.footer_cache.hits",
    "io.footer_cache.misses",
    "jit_memory.cache_drops",
    "fleet.shared_cache.hits",
    "fleet.shared_cache.misses",
    "fleet.shared_cache.evictions",
    "fleet.shared_cache.errors",
    "fleet.singleflight.leader",
    "fleet.singleflight.follower_hits",
    "fleet.singleflight.takeovers",
    "fleet.singleflight.local_fallbacks",
    "fleet.supervisor.restarts",
    "build.exchange.bytes",
    "build.worker.crashes",
    "device.stage.bytes_zero_copy",
    "device.stage.bytes_copied",
    "device.kernel.fused",
    "device.kernel.fallbacks",
    "device.kernel.dense_reduce",
    "device.kernel.scatter_reduce",
    "device.kernel.bucket_reduce",
    "device.kernel.segment_reduce_lax",
    "device.kernel.segment_reduce_sharded",
    "device.kernel.agg_channels_device",
    "device.kernel.agg_channels_host",
    "controller.ticks",
    "controller.actuations",
    "controller.actuation_failures",
    "controller.deferred",
    "controller.heals",
    "controller.scale",
    "controller.health_probe_errors",
    "fleet.worker.scaled",
    "faults.delays_injected",
    "obs.journal.records",
    "obs.journal.errors",
    "obs.journal.segments_sealed",
    "obs.journal.evictions",
    "controller.incidents",
    "controller.incident_errors",
    "ingest.ticks",
    "ingest.commits",
    "ingest.commit_failures",
    "ingest.rows",
    "ingest.bytes",
    "ingest.compactions",
    "ingest.compact_failures",
    "ingest.deferred",
    "ingest.snapshots",
    "ingest.pinned_reads",
)

_counters = {name: _metrics.counter(name) for name in KNOWN_COUNTERS}


def increment(name: str, n: int = 1) -> None:
    c = _counters.get(name)
    if c is None:
        raise KeyError(
            f"undeclared counter {name!r} — declare it in stats.KNOWN_COUNTERS "
            f"(silent typo counters are exactly what the declared registry removes)"
        )
    c.inc(n)


def get(name: str) -> int:
    c = _counters.get(name)
    if c is None:
        raise KeyError(f"undeclared counter {name!r} (see stats.KNOWN_COUNTERS)")
    return c.value


def snapshot() -> dict[str, int]:
    """Point-in-time copy of every declared counter."""
    return {name: c.value for name, c in _counters.items()}


def reset() -> None:
    for c in _counters.values():
        c._reset()
