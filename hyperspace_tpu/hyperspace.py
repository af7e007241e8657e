"""User facade and session integration.

Reference parity: com/microsoft/hyperspace/Hyperspace.scala:24-133 (the 8
user APIs delegating to the collection manager, with a context holding the
session + caching manager) and package.scala:34-77 (enable/disable toggling
the optimizer rule batch). There is no SparkSession here; `HyperspaceSession`
owns the configuration, the device mesh, the executor, and the
enable/disable switch, and `session.run(plan)` is the query entry point
that applies the rewrite rules when enabled.
"""

from __future__ import annotations

import dataclasses
import threading
from pathlib import Path

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.dataset import Dataset
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.index.collection_manager import CachingIndexCollectionManager
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.obs import events as obs_events
from hyperspace_tpu.plan.nodes import LogicalPlan, Scan
from hyperspace_tpu.rules.base import apply_rules

# Structured health-plane events (obs/events.py): the query plane's
# degradations become operator-visible records on /debug/events, each
# carrying the active trace id.
_EVT_FALLBACK = obs_events.declare("fallback.replan")
_EVT_QUARANTINED = obs_events.declare("index.quarantined")
_EVT_DEMOTED = obs_events.declare("advisor.routing.demoted")


@dataclasses.dataclass
class QueryOutcome:
    """Per-query handle state: everything one `run` produced, owned by
    the caller instead of smeared across session globals. Two concurrent
    queries each get their own outcome; the session keeps a lock-guarded
    *view* of the most recent one (`last_query_stats` / `last_profile()`)
    for the single-caller API. The serving plane (docs/serving.md)
    attaches an outcome to each QueryHandle."""

    result: object  # ColumnTable
    stats: dict
    physical_plan: object
    profile: object
    replans: int = 0
    used_indexes: bool = True


class HyperspaceSession:
    """The engine session: configuration + mesh + executor + rule toggle.

    Thread-safety: `run()` may be called from N threads (the serving
    plane does exactly that). Each query's mutable state lives in a
    per-query :class:`QueryOutcome`; the shared session view
    (`last_query_stats`, `last_physical_plan`, `last_profile()`, the
    corruption-quarantine `index_health` map, lazy manager init) is
    guarded by one reentrant lock."""

    def __init__(self, system_path: str | None = None, num_buckets: int | None = None, mesh=None):
        kwargs = {}
        if system_path is not None:
            kwargs["system_path"] = str(system_path)
        if num_buckets is not None:
            kwargs["num_buckets"] = int(num_buckets)
        from hyperspace_tpu.parallel.mesh import enable_compile_cache

        enable_compile_cache()
        self.conf = HyperspaceConf(**kwargs)
        self.mesh = mesh
        self._enabled = False
        self._manager: CachingIndexCollectionManager | None = None
        # Guards the session view below + lazy manager construction.
        self._state_lock = threading.RLock()
        # Executed-plan evidence of the most recent run(): Executor.stats
        # and the executed PhysicalNode tree.
        self.last_query_stats: dict = {}
        self.last_physical_plan = None
        # QueryProfile of the most recent run() (docs/observability.md);
        # always populated — the physical-plan side of the profile costs
        # two perf_counter calls per operator even with tracing off.
        self._last_profile = None
        # Per-index health map (index root -> failure record). An index
        # that served corrupt data is quarantined from the rewrite rules
        # for the rest of the session; queries transparently fall back to
        # the source (docs/fault_tolerance.md). recover()/refresh clears.
        # Mutations go through _state_lock; per-query snapshots keep one
        # query's replan decisions consistent.
        self.index_health: dict[str, dict] = {}
        # Advisor plane (docs/advisor.md): the bounded workload ring every
        # run_query appends to, and the adaptive-routing ledger. Both lazy
        # (constructed under _state_lock on first use).
        self._workload = None
        self._routing = None

    # -- rule toggle (package.scala:46-70) --------------------------------
    def enable_hyperspace(self) -> "HyperspaceSession":
        self._enabled = True
        return self

    def disable_hyperspace(self) -> "HyperspaceSession":
        self._enabled = False
        return self

    def is_hyperspace_enabled(self) -> bool:
        return self._enabled

    # -- wiring -----------------------------------------------------------
    @property
    def manager(self) -> CachingIndexCollectionManager:
        if self._manager is None:
            with self._state_lock:
                if self._manager is None:
                    def writer_factory():
                        from hyperspace_tpu.execution.builder import DeviceIndexBuilder

                        w = DeviceIndexBuilder(
                            mesh=self.mesh,
                            memory_budget_bytes=self.conf.build_memory_budget_bytes,
                            chunk_bytes=self.conf.build_chunk_bytes or None,
                            venue=self.conf.build_venue,
                            pipeline_enabled=self.conf.build_pipeline_enabled,
                            pipeline_max_inflight_bytes=self.conf.build_pipeline_max_inflight_bytes,
                            workers=self.conf.build_workers,
                            exchange_dir=self.conf.build_exchange_dir or None,
                        )
                        self._last_writer = w
                        return w

                    self._manager = CachingIndexCollectionManager(self.conf, writer_factory)
        return self._manager

    @property
    def workload(self):
        """The session's bounded workload log (docs/advisor.md): one
        :class:`~hyperspace_tpu.advisor.workload.WorkloadRecord` per
        run_query, the advisor's learning input."""
        if self._workload is None:
            with self._state_lock:
                if self._workload is None:
                    from hyperspace_tpu.advisor.workload import WorkloadLog

                    self._workload = WorkloadLog(self.conf.advisor_workload_max_records)
        return self._workload

    def routing_ledger(self):
        """The adaptive-routing outcome ledger (advisor/routing.py);
        constructed lazily — sessions that never enable
        ``hyperspace.advisor.routing.enabled`` still get a readable view
        of it for reports."""
        if self._routing is None:
            with self._state_lock:
                if self._routing is None:
                    from hyperspace_tpu.advisor.routing import RoutingLedger

                    self._routing = RoutingLedger(self)
        return self._routing

    @property
    def last_build_stats(self) -> dict:
        """Stats of the most recent index build in this session,
        including the per-phase wall-time breakdown (decode / hash+lanes
        / partition+exchange / carve+encode+write)."""
        return dict(getattr(getattr(self, "_last_writer", None), "last_build_stats", {}) or {})

    # -- data access ------------------------------------------------------
    def parquet(self, root: str | Path) -> Scan:
        """Register a parquet dataset and return its scan plan (the
        DataFrame-equivalent; LogicalPlan carries the fluent API)."""
        return Dataset.parquet(root).scan()

    def orc(self, root: str | Path) -> Scan:
        return Dataset.orc(root).scan()

    def csv(self, root: str | Path) -> Scan:
        return Dataset.csv(root).scan()

    def json(self, root: str | Path) -> Scan:
        """Register a line-delimited JSON dataset."""
        return Dataset.json(root).scan()

    def optimized_plan(self, plan: LogicalPlan, snapshot=None) -> LogicalPlan:
        if not self._enabled:
            return plan
        from hyperspace_tpu.plan.prune import prune_columns
        from hyperspace_tpu.plan.pushdown import push_down_filters

        # Predicate pushdown + column pruning FIRST (the analog of Spark
        # running PushDownPredicate/ColumnPruning before the
        # extraOptimizations batch): side-local filters reach the join
        # sides (where the index rules cover them) and scans narrow to
        # what the query needs.
        if snapshot is not None:
            # MVCC pinned read (ingest/snapshot.py): the candidate set is
            # the entries captured at admission, NOT the live listing —
            # versions a concurrent micro-batch commits are invisible.
            indexes = snapshot.entries()
        else:
            indexes = self.manager.get_indexes()
        with self._state_lock:
            unhealthy = set(self.index_health)
        if unhealthy:
            # Indexes that served corrupt data are out of the candidate
            # set until recovered — degradation is sticky per session,
            # not re-discovered (and re-failed) on every query.
            indexes = [
                e for e in indexes
                if str(Path(e.content.root)) not in unhealthy
            ]
        return apply_rules(prune_columns(push_down_filters(plan)), indexes, conf=self.conf)

    def run(self, plan: LogicalPlan, profile_dir: str | Path | None = None, snapshot=None):
        """Execute a plan (rewriting through indexes when enabled);
        returns a ColumnTable. With `profile_dir`, the execution runs
        under jax.profiler.trace and writes an xplane artifact there
        (SURVEY.md §5: the TPU profiling story) — open with TensorBoard
        or xprof. With `snapshot` (a PinnedSnapshot from
        :meth:`pin_snapshot`), the read repeats against the pinned
        version stamp no matter what commits concurrently.

        Corruption fallback (`hyperspace.fallback.enabled`): when an
        index scan hits unreadable index data mid-query, the failing
        index is recorded in `index_health` and the query transparently
        re-plans — first through the remaining healthy indexes, then
        (if corruption persists) straight against the source data. The
        query answers either way; `hyperspace_tpu.stats` counts it."""
        outcome = self.run_query(plan, profile_dir=profile_dir, snapshot=snapshot)
        self._publish(outcome)
        return outcome.result

    def pin_snapshot(self):
        """Pin an MVCC repeatable-read view of the collection at the
        current per-index version stamp (ingest/snapshot.py,
        docs/ingestion.md "snapshot semantics"). Pass the handle to
        `run(..., snapshot=snap)`; release it (or use it as a context
        manager) when done."""
        from hyperspace_tpu.ingest.snapshot import PinnedSnapshot

        return PinnedSnapshot(self)

    def run_query(
        self,
        plan: LogicalPlan,
        profile_dir: str | Path | None = None,
        plan_cache=None,
        snapshot=None,
    ) -> QueryOutcome:
        """Execute a plan into a per-query :class:`QueryOutcome` without
        touching the session view — the concurrency-safe entry point the
        serving plane uses (docs/serving.md). `plan_cache` (a
        serve.PlanCache) memoizes `optimized_plan` per versioned plan
        key; its key includes the quarantine set, so a mid-query
        corruption replan re-optimizes under the new key instead of
        hitting the poisoned entry."""
        import time

        from hyperspace_tpu import stats
        from hyperspace_tpu.exceptions import IndexCorruptionError
        from hyperspace_tpu.execution import device_cache
        from hyperspace_tpu.execution import io as hio
        from hyperspace_tpu.execution.executor import Executor
        from hyperspace_tpu.obs import profile as obs_profile
        from hyperspace_tpu.obs import trace as obs_trace

        from hyperspace_tpu.signature import plan_signature

        cache_before = self._cache_counts(hio, device_cache)
        if snapshot is not None:
            # Pin every raw source leaf to the snapshot's file lists
            # BEFORE planning: the rewrite rules then exact-match the
            # pinned entries and any raw fallback scans the pinned
            # files — a repeatable read across concurrent commits.
            plan = snapshot.pin_plan(plan)
            stats.increment("ingest.pinned_reads")
        replans = 0
        use_indexes = True
        # Advisor plane (docs/advisor.md): the plan's structural
        # signature keys both the workload record and the routing
        # ledger. Adaptive routing (opt-in) consults measured history
        # BEFORE planning: a signature whose indexed path measured
        # slower than raw is demoted to a straight source scan.
        sig = plan_signature(plan)
        routing_on = self.conf.advisor_routing_enabled
        routed = routing_stamp = ledger = None
        if routing_on:
            from hyperspace_tpu.advisor import routing as adv_routing

            ledger = self.routing_ledger()
            # A pinned query keys the ledger on its OWN read point —
            # the live stamp moves under concurrent commits the pinned
            # view cannot see, and a moved stamp WIPES the ledger.
            routing_stamp = (
                adv_routing.snapshot_stamp(snapshot)
                if snapshot is not None
                else adv_routing.collection_stamp(self)
            )
            if self._enabled:
                routed = ledger.decide(sig, stamp=routing_stamp)
                if routed == "raw":
                    use_indexes = False
                    obs_trace.event("advisor.routing.demoted", signature=sig)
                    _EVT_DEMOTED.emit(signature=sig)
        t_start = time.perf_counter()
        with obs_trace.trace("query") as root_span:
            while True:
                executor = Executor(mesh=self.mesh, conf=self.conf)
                with obs_trace.span("plan.optimize", indexes_enabled=self._enabled):
                    if not use_indexes:
                        optimized = plan
                    elif plan_cache is not None and self._enabled:
                        optimized = plan_cache.get_or_optimize(self, plan, snapshot=snapshot)
                    else:
                        optimized = self.optimized_plan(plan, snapshot=snapshot)
                    if use_indexes and self._enabled and self.conf.scan_prefetch_enabled:
                        # Query-tail prefetch: footers + first chunk of
                        # the index files the pruner keeps start loading
                        # on a background pool NOW, so the executor's
                        # cold reads below begin warm (advisory — see
                        # execution/prefetch.py).
                        from hyperspace_tpu.execution import prefetch as _prefetch

                        with obs_trace.span("plan.prefetch"):
                            _prefetch.prefetch_plan(optimized)
                try:
                    if profile_dir is not None:
                        import jax

                        with jax.profiler.trace(str(profile_dir)):
                            result = executor.execute(optimized)
                    else:
                        result = executor.execute(optimized)
                    break
                except IndexCorruptionError as e:
                    if not (self._enabled and use_indexes and self.conf.fallback_enabled):
                        raise
                    root = str(Path(e.index_root)) if e.index_root is not None else None
                    with self._state_lock:
                        newly_quarantined = root is not None and root not in self.index_health
                        if root is None or root in self.index_health:
                            # No provenance to quarantine by (or quarantining
                            # it didn't help): indexes go off wholesale for
                            # this query — the loop provably terminates.
                            use_indexes = False
                        if root is not None:
                            self.index_health[root] = {"reason": e.msg, "path": e.path}
                    stats.increment("fallback.queries")
                    replans += 1
                    obs_trace.event("fallback.replan", index=root, reason=e.msg)
                    _EVT_FALLBACK.emit(index=root, reason=e.msg)
                    if newly_quarantined:
                        _EVT_QUARANTINED.emit(index=root, reason=e.msg)
                    import logging

                    logging.getLogger("hyperspace_tpu").warning(
                        "index data unreadable (%s); re-planning query against source", e.msg
                    )
        total_s = time.perf_counter() - t_start
        with self._state_lock:
            degraded = sorted(self.index_health)
        query_stats = executor.stats
        if degraded:
            query_stats["degraded_indexes"] = degraded
        if routing_on and ledger is not None:
            # Fold the measured outcome back into the ledger (EMA per
            # signature per mode) — the demotion evidence of future runs.
            mode = "indexed" if (self._enabled and use_indexes) else "raw"
            ledger.record(sig, mode, total_s, stamp=routing_stamp)
            query_stats["advisor_routing"] = {
                "decision": mode,
                "demoted": routed == "raw",
            }
        cache_after = self._cache_counts(hio, device_cache)
        profile = obs_profile.build_profile(
            total_s=total_s,
            physical_plan=executor.physical_plan,
            stats=query_stats,
            venue=self._venue_info(),
            cache={k: cache_after[k] - cache_before[k] for k in cache_after},
            fallback={
                "replans": replans,
                "degraded_indexes": degraded,
                "used_indexes": use_indexes,
            },
            trace_root=root_span if isinstance(root_span, obs_trace.Span) else None,
        )
        from hyperspace_tpu.advisor.workload import WorkloadRecord, used_index_names

        self.workload.record(WorkloadRecord(
            signature=sig,
            plan=plan,
            total_s=total_s,
            bytes_scanned=int(query_stats.get("bytes_scanned", 0) or 0),
            used_indexes=use_indexes and self._enabled,
            index_names=used_index_names(optimized),
            profile=profile,
            routed=routed,
        ))
        return QueryOutcome(
            result=result,
            stats=query_stats,
            physical_plan=executor.physical_plan,
            profile=profile,
            replans=replans,
            used_indexes=use_indexes,
        )

    def _publish(self, outcome: QueryOutcome) -> None:
        """Install a finished query's outcome as the session view
        (`last_query_stats` / `last_physical_plan` / `last_profile()`)
        in one locked step, so a reader never sees the stats of one
        query next to the profile of another."""
        with self._state_lock:
            self.last_query_stats = outcome.stats
            self.last_physical_plan = outcome.physical_plan
            self._last_profile = outcome.profile

    @staticmethod
    def _cache_counts(hio, device_cache) -> dict:
        t = hio.table_cache_stats()
        d, h = device_cache.DEVICE_CACHE, device_cache.HOST_DERIVED
        return {
            "table_hits": t["hits"], "table_misses": t["misses"],
            "device_hits": d.hits, "device_misses": d.misses,
            "derived_hits": h.hits, "derived_misses": h.misses,
        }

    def _venue_info(self) -> dict:
        """Where this session's queries physically run (profile evidence)."""
        info: dict = {"mesh": self.mesh is not None}
        try:
            import jax

            dev = jax.devices()[0]
            info["platform"] = dev.platform
            info["device_kind"] = getattr(dev, "device_kind", None)
            info["device_count"] = jax.device_count()
        except Exception:
            info["platform"] = None
        return info

    def last_profile(self):
        """The QueryProfile of the most recent run() in this session
        (None before the first query). Render it with
        `Hyperspace.explain(plan, mode="analyze")` or inspect
        `.to_json()` (docs/observability.md). Under concurrent serving,
        per-query profiles ride the QueryHandle instead
        (docs/serving.md) — this view is only "the most recent"."""
        with self._state_lock:
            return self._last_profile

    def serve(self, **kwargs):
        """Construct a concurrent QueryServer over this session
        (docs/serving.md): bounded worker pool, admission control, and
        the versioned plan/result caches. Keyword arguments override the
        `hyperspace.serve.*` config defaults. The serving subsystem is
        otherwise off — plain `run()` callers never pay for it."""
        from hyperspace_tpu.serve import QueryServer

        return QueryServer(self, **kwargs)

    def to_pandas(self, plan: LogicalPlan):
        import pandas as pd

        return pd.DataFrame(self.run(plan).decode())


class Hyperspace:
    """The 8-method user API (Hyperspace.scala:32-104)."""

    def __init__(self, session: HyperspaceSession):
        self.session = session

    def create_index(self, plan: LogicalPlan, index_config: IndexConfig) -> None:
        self.session.manager.create(plan, index_config)

    def create_vector_index(self, plan: LogicalPlan, config) -> None:
        """Build an ANN index over an embedding column (VectorIndexConfig)."""
        self.session.manager.create_vector(plan, config)

    def ann_search(self, plan: LogicalPlan, queries, k: int, nprobe: int | None = None,
                   embedding_column: str | None = None, metric: str | None = None):
        """Top-k nearest neighbours; probes a matching vector index when
        hyperspace is enabled, else brute-forces the source (exact)."""
        from hyperspace_tpu.vector.search import ann_search

        return ann_search(self.session, plan, queries, k, nprobe, embedding_column, metric)

    def delete_index(self, name: str) -> None:
        self.session.manager.delete(name)

    def restore_index(self, name: str) -> None:
        self.session.manager.restore(name)

    def vacuum_index(self, name: str) -> None:
        self.session.manager.vacuum(name)

    def refresh_index(self, name: str, mode: str = "full") -> None:
        """Rebuild an index. mode="full" re-executes the logged lineage;
        mode="incremental" indexes only appended source files into per-
        bucket delta files (pair with optimize_index to compact)."""
        self.session.manager.refresh(name, mode)
        self._lift_quarantine(name)

    def optimize_index(self, name: str) -> None:
        self.session.manager.optimize(name)
        self._lift_quarantine(name)

    def _lift_quarantine(self, name: str) -> None:
        """A successful rebuild supersedes whatever corruption got the
        index quarantined in this session — let it serve queries again."""
        root = str(self.session.manager.path_resolver.get_index_path(name))
        with self.session._state_lock:
            self.session.index_health.pop(root, None)

    def cancel(self, name: str) -> None:
        self.session.manager.cancel(name)

    def recover(self, name: str | None = None) -> dict:
        """Crash recovery (docs/fault_tolerance.md): quarantine torn log
        entries, roll a transient latest entry to the last stable state
        (cancel semantics), refresh the latestStable pointer, and GC
        version dirs no stable entry references. With no name, every
        index under the system path is recovered. Also lifts the
        session's corruption quarantine (`session.index_health`) so
        repaired indexes serve queries again. Idempotent."""
        mgr = self.session.manager
        if name is not None:
            report = mgr.recover(name)
            root = str(mgr.path_resolver.get_index_path(name))
            with self.session._state_lock:
                self.session.index_health.pop(root, None)
            return report
        reports = {d.name: mgr.recover(d.name) for d in mgr.path_resolver.list_index_paths()}
        with self.session._state_lock:
            self.session.index_health.clear()
        return reports

    def indexes(self):
        return self.session.manager.indexes()

    # -- advisor (docs/advisor.md) ----------------------------------------
    def recommend(self):
        """Ranked create/drop/rebucket/optimize recommendations for the
        session's observed workload — the what-if analyzer replaying
        recorded plans through the real rewrite rules against
        hypothetical indexes. Pure analysis; nothing is mutated."""
        from hyperspace_tpu.advisor.whatif import WhatIfAnalyzer

        return WhatIfAnalyzer(self.session).recommend()

    def lifecycle(self):
        """The autonomous lifecycle policy engine over this API
        (advisor/lifecycle.py). All its gates
        (`hyperspace.advisor.lifecycle.*`) default off — construct it and
        call `.sweep()` after opting in."""
        from hyperspace_tpu.advisor.lifecycle import LifecyclePolicy

        return LifecyclePolicy(self)

    def controller(self, server=None, **kwargs):
        """The self-driving operations controller over this API
        (serve/controller.py, docs/fault_tolerance.md "self-driving
        operations"): a reconciliation loop consuming SLO burn verdicts
        and the structured event ring, actuating only through the
        crash-safe protocols this facade exposes. Gated by
        `hyperspace.controller.enabled` (default off) — construct it,
        opt in, and call `.start()` (or drive `.step()` yourself)."""
        from hyperspace_tpu.serve.controller import OpsController

        return OpsController(self, server=server, **kwargs)

    def ingest(self, **kwargs):
        """The continuous-ingestion daemon over this API
        (hyperspace_tpu/ingest/, docs/ingestion.md): CDC tailing,
        micro-batch commits through the two-phase refresh action, and
        advisor-gated compaction. Register indexes with `.watch(name,
        changelog=...)`, then `.start()` / `.drain()` / `.stop()` — or
        drive `.tick()` yourself. Gated by `hyperspace.ingest.enabled`
        (default off): every tick is a no-op until you opt in."""
        from hyperspace_tpu.ingest.daemon import IngestDaemon

        return IngestDaemon(self, **kwargs)

    def explain(
        self,
        plan: LogicalPlan,
        verbose: bool = False,
        physical: bool = False,
        mode: str | None = None,
    ) -> str:
        """Rules-off/on plan diff. physical=True EXECUTES both variants
        and diffs the physical plans that actually ran (files read,
        kernels, bucket/device counts, rows per operator).
        mode="analyze" EXECUTES the query once under the session's
        current enablement and renders its QueryProfile — per-operator
        measured wall time, rows in/out, bytes, venue, cache and
        fallback outcomes (docs/observability.md)."""
        from hyperspace_tpu.explain.plan_analyzer import (
            explain_analyze,
            explain_executed,
            explain_string,
        )

        if mode == "analyze":
            return explain_analyze(plan, self.session)
        if mode not in (None, "diff"):
            raise HyperspaceError(f"unknown explain mode {mode!r} (diff|analyze)")
        if physical:
            return explain_executed(plan, self.session)
        return explain_string(plan, self.session, verbose=verbose)
