"""Plan executor: runs logical plans against the device plane.

The analog of Spark's physical planning + execution for the IR's node
types (SURVEY.md §7 design stance). What matters for TPU performance:

- **bucket pruning** (Filter over an index scan with equality literals on
  every bucket column): recompute the canonical row hash on the literal
  tuple and read ONLY that bucket's file — the reference cannot do this
  (its FilterIndexRule keeps a full scan, FilterIndexRule.scala:114-120);
  for a point lookup this divides IO by numBuckets;
- **zero-exchange join** (Join over two index scans bucketed on the join
  keys with equal bucket counts): per-bucket sort-merge join, all buckets
  in one vmapped device kernel (ops/join.py) — the analog of the
  reference's shuffle-free SortMergeJoin;
- predicates evaluate as one fused XLA computation (ops/filter.py).

Round-5 layout: this module owns dispatch, venue selection, and the
order/limit/union operators; the heavy operator families live in
per-operator mixins (exec_scan / exec_side / exec_join / exec_join_agg /
exec_agg) over the shared support layer (exec_common).
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path

import numpy as np

from hyperspace_tpu.obs import trace as obs_trace

from hyperspace_tpu import native
from hyperspace_tpu.config import DEFAULT_VENUE
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.execution import io as hio
from hyperspace_tpu.execution.build_exchange import compute_row_hashes, hash_scalar_key
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.dataset import format_suffix, list_data_files
from hyperspace_tpu.ops.filter import apply_filter, eval_predicate_mask
from hyperspace_tpu.ops.hashing import bucket_ids
from hyperspace_tpu.ops import join as join_ops
from hyperspace_tpu.plan.expr import And, BinOp, Col, Expr, Lit, evaluate, split_conjuncts
from hyperspace_tpu.plan.nodes import (
    Aggregate,
    Filter,
    Join,
    Limit,
    LogicalPlan,
    Project,
    Scan,
    Sort,
    Union,
    Window,
)


from hyperspace_tpu.execution.exec_agg import AggregateMixin
from hyperspace_tpu.execution.exec_common import (  # noqa: F401  (re-exports)
    AlignedSide,
    KeyBounds,
    SideData,
    _TableLeaf,
    _broadcast_probe,
    _bucket_sorted_codes,
    _composite_keys,
    _concat_side_cached,
    _copy_field,
    _desugar_count_distinct,
    _factorize_keys,
    _factorize_keys_cached,
    _filter_side,
    _group_ids_cached,
    _hash_fields_compatible,
    _logical_key,
    _null_field,
    _pad_bucket_major,
    _stable_table_refs,
    key_bounds,
    predicate_all_key_bounds,
)
from hyperspace_tpu.execution.exec_join import JoinMixin
from hyperspace_tpu.execution.exec_join_agg import FusedJoinAggMixin
from hyperspace_tpu.execution.exec_scan import ScanFilterMixin
from hyperspace_tpu.execution.exec_side import JoinSidesMixin


class Executor(
    ScanFilterMixin,
    JoinSidesMixin,
    JoinMixin,
    FusedJoinAggMixin,
    AggregateMixin,
):
    """Runs plans on the device plane. With a mesh, the query plane is
    distributed: the bucket-aligned SMJ shards its bucket dimension over
    the mesh (zero collectives — the analog of the reference's
    cluster-parallel zero-exchange SortMergeJoin across executors,
    JoinIndexRule.scala:124-153) and filter predicates shard their row
    dimension (FilterIndexRule.scala:114-120 keeps full scan parallelism).
    `stats` records what physically ran (files read, kernels, devices) —
    the executed-plan evidence explain consumes."""

    def __init__(self, mesh=None, conf=None):
        self.mesh = mesh
        self.conf = conf
        self.stats: dict = {
            "files_read": 0,
            "files_pruned": 0,
            "rows_pruned": 0,
            "bytes_scanned": 0,
            "join_path": None,
            "join_kernel": None,
            "join_devices": 1,
            "num_buckets": None,
            "agg_path": None,
        }
        # Executed physical plan, built as the query runs (the analog of
        # the reference diffing executedPlans, PlanAnalyzer.scala:163-178).
        self.physical_plan = None
        self._cur_phys = None
        # Bucket-preserving join outputs: id(table) -> (weakref, offsets,
        # lowered key names, hash-domain fields). Bounded; weakrefs keep
        # id-reuse from matching a dead table.
        self._bucketed_outputs: dict[int, tuple] = {}

    def _stash_bucketed(self, table: ColumnTable, offsets, keys, hash_fields) -> None:
        import weakref

        if len(self._bucketed_outputs) >= 16:
            self._bucketed_outputs.clear()
        self._bucketed_outputs[id(table)] = (
            weakref.ref(table),
            offsets,
            tuple(k.lower() for k in keys),
            hash_fields,
        )

    def _preserved_sidedata(self, table: ColumnTable, join_on: list[str]) -> "SideData | None":
        e = self._bucketed_outputs.get(id(table))
        if e is None or e[0]() is not table:
            return None
        if e[2] != tuple(k.lower() for k in join_on):
            return None
        return SideData(table, e[1], False, hash_fields=e[3])

    def _propagate_stash(self, src: ColumnTable, dst: ColumnTable) -> ColumnTable:
        """Row-preserving transforms (column selection) keep a stashed
        bucket grouping valid — carry it to the derived table so chained
        star joins still find it (select() builds a NEW ColumnTable, so
        identity lookups would otherwise go dead)."""
        e = self._bucketed_outputs.get(id(src))
        if e is not None and e[0]() is src and dst is not src:
            names = {n.lower() for n in dst.schema.names}
            if all(k in names for k in e[2]):  # bucket keys survived
                self._stash_bucketed(dst, e[1], list(e[2]), e[3])
        return dst

    def execute(self, plan: LogicalPlan) -> ColumnTable:
        from hyperspace_tpu.plan.prune import prune_columns
        from hyperspace_tpu.plan.pushdown import push_down_filters

        from hyperspace_tpu.utils.jit_memory import maybe_relieve_jit_pressure

        # Long-lived processes compiling many distinct programs can hit
        # the kernel's vm.max_map_count and SIGSEGV inside LLVM on the
        # next compile; drop jax caches before that point (sampled).
        maybe_relieve_jit_pressure()
        validate = self.conf is None or getattr(self.conf, "validate_plans", True)
        if validate:
            # Pre-execution analysis (analysis/validator.py): reject a
            # malformed plan with node-provenance diagnostics up front
            # instead of an opaque mid-execution KeyError / XLA error.
            from hyperspace_tpu.analysis.validator import check_plan, validate_rewrite

            check_plan(plan)
        optimized = prune_columns(push_down_filters(plan))
        if validate:
            # Guard our own rewrites: pushdown/prune must preserve the
            # output schema and never push a filter beneath the
            # null-extended side of an outer join.
            validate_rewrite(plan, optimized)
        return self._execute(optimized)

    def _execute(self, plan: LogicalPlan) -> ColumnTable:
        from hyperspace_tpu.execution.physical import PhysicalNode

        node = PhysicalNode(op=type(plan).__name__)
        parent, self._cur_phys = self._cur_phys, node
        if parent is not None:
            parent.children.append(node)
        else:
            self.physical_plan = node
        files_before = self.stats["files_read"]
        bytes_before = self.stats["bytes_scanned"]
        sp = obs_trace.span(f"execute.{type(plan).__name__}")
        t0 = time.perf_counter()
        with sp:
            try:
                result = self._dispatch(plan)
            finally:
                self._cur_phys = parent
                # Wall time of this operator's frame (children included);
                # recorded even on failure so partial profiles stay honest.
                node.wall_s = time.perf_counter() - t0
                sp.rename(f"execute.{node.op}")
            # Physical file IO attributed to THIS operator = its frame's delta
            # minus what child frames already claimed.
            subtree = self.stats["files_read"] - files_before
            node._subtree_files = subtree
            own = subtree - sum(getattr(c, "_subtree_files", 0) for c in node.children)
            if own > 0:
                node.detail.setdefault("files", own)
            sub_bytes = self.stats["bytes_scanned"] - bytes_before
            node._subtree_bytes = sub_bytes
            own_bytes = sub_bytes - sum(getattr(c, "_subtree_bytes", 0) for c in node.children)
            if own_bytes > 0:
                node.detail.setdefault("bytes", own_bytes)
            node.rows_out = result.num_rows
            sp.set(rows_out=result.num_rows)
            if own > 0:
                sp.set(files=own, bytes=own_bytes)
        return result

    def _dispatch(self, plan: LogicalPlan) -> ColumnTable:
        if isinstance(plan, Scan):
            # Labeled here, not in _scan: _scan also runs as a subroutine
            # of other operators (hybrid delta reads) whose node must not
            # be renamed.
            if plan.bucket_spec is not None:
                self._phys("IndexScan", buckets=plan.bucket_spec[0])
            else:
                self._phys("TableScan")
            return self._scan(plan)
        if isinstance(plan, Filter):
            return self._filter(plan)
        if isinstance(plan, Project):
            self._cur_phys.detail["columns"] = list(plan.output_names)
            child = self._execute(plan.child)
            if plan.is_simple:
                return self._propagate_stash(child, child.select(plan.columns))
            from hyperspace_tpu.ops.project import project_table

            self._phys(
                "ProjectCompute",
                computed=[c[0] for c in plan.columns if not isinstance(c, str)],
            )
            return project_table(child, plan.columns, plan.schema)
        if isinstance(plan, Join):
            return self._join(plan)
        if isinstance(plan, Union):
            self._cur_phys.op = "HybridScanUnion"
            return self._union(plan)
        if isinstance(plan, Aggregate):
            return self._aggregate(plan)
        if isinstance(plan, Window):
            from hyperspace_tpu.ops.window import window_table

            t = self._execute(plan.child)
            self._phys(
                "WindowSortedSegments",
                partitions=list(plan.partition_by),
                frame=plan.frame,
                funcs=[f.fn for f in plan.funcs],
            )
            return window_table(
                t, plan.partition_by, plan.order_by, plan.funcs, plan.frame, plan.schema
            )
        if isinstance(plan, Sort):
            return self._sort(plan)
        if isinstance(plan, _TableLeaf):
            return plan.table
        if isinstance(plan, Limit):
            self._cur_phys.detail["n"] = plan.n
            if isinstance(plan.child, Sort):
                return self._top_n(plan.child, plan.n)
            early = self._limit_early_out(plan.child, plan.n)
            if early is not None:
                return early
            t = self._execute(plan.child)
            return t.take(np.arange(min(plan.n, t.num_rows)))
        raise HyperspaceError(f"cannot execute plan node {type(plan).__name__}")

    def _limit_early_out(self, child: LogicalPlan, n: int) -> ColumnTable | None:
        """LIMIT over an unordered linear scan chain: pull rows file by
        file and STOP once n rows survive, instead of materializing the
        whole child (any n rows are a correct answer without ORDER BY —
        the analog of Spark's CollectLimit incremental take). Returns
        None when the shape doesn't apply (non-linear child, single
        file, pinned hybrid scans)."""
        import functools

        chain: list[LogicalPlan] = []
        node = child
        while isinstance(node, (Project, Filter)):
            chain.append(node)
            node = node.child
        if not isinstance(node, Scan):
            return None
        files = self._scan_files(node)
        preds = [w.predicate for w in chain if isinstance(w, Filter)]
        if node.bucket_spec is not None and preds:
            # Index scans prune FIRST — a point lookup must stay a
            # single-file IndexPointLookup, not a file-by-file walk
            # through non-owning buckets.
            pred = functools.reduce(And, preds)
            pruned = self._prune_bucket_files(node, pred)
            if pruned is None:
                ranged = self._range_prune_list(node, pred)
                pruned = ranged[0] if ranged is not None else None
            if pruned is not None:
                files = pruned
        if len(files) <= 1:
            return None
        parts: list[ColumnTable] = []
        total = 0
        scanned = 0
        for f in files:
            sub: LogicalPlan = dataclasses.replace(node, files=[f])
            for wrapper in reversed(chain):
                sub = dataclasses.replace(wrapper, child=sub)
            # Sequential by design: stopping early is the point; the
            # non-limited path keeps its thread-pooled parallel reads.
            t = self._execute(sub)
            scanned += 1
            if t.num_rows:
                parts.append(t)
                total += t.num_rows
            if total >= n:
                break
        self._phys(
            "LimitEarlyOut", files_scanned=scanned, files_total=len(files)
        )
        if not parts:
            return ColumnTable.empty(child.schema)
        out = ColumnTable.concat(parts) if len(parts) > 1 else parts[0]
        return out.take(np.arange(min(n, out.num_rows)))

    def _phys(self, op: str | None = None, **detail) -> None:
        """Annotate the operator currently executing."""
        if self._cur_phys is None:
            return
        if op is not None:
            self._cur_phys.op = op
        self._cur_phys.detail.update(detail)

    # -- aggregate / sort -------------------------------------------------

    def _venue(self, op: str) -> str:
        """The session's `hyperspace.<op>.venue` for op in join, filter,
        agg, sort: "device", or "host" — the parity reference and the
        explicit choice. The host join merge is the native library's, so
        asking for it without the library raises."""
        venue = getattr(self.conf, f"{op}_venue") if self.conf is not None else DEFAULT_VENUE
        if venue == "host" and op == "join":
            native.require("hyperspace.join.venue")
        return venue

    def _fused_kernels(self) -> str:
        """Gate of the join-aggregate's Pallas run-bounds kernel
        ("auto"/"off", `hyperspace.device.fusedKernels`): auto engages it
        when the shape is eligible; the jitted lax searchsorted is the
        always-available fallback (docs/architecture.md "device data
        path")."""
        return self.conf.device_fused_kernels if self.conf is not None else "auto"

    def _top_n(self, sort_plan: "Sort", n: int) -> ColumnTable:
        """ORDER BY ... LIMIT n as an O(rows) selection: np.partition on
        the first sort column finds the n-th threshold, only the (ties-
        inclusive) candidate set gets the full lexicographic sort. The
        TopK analog of Spark's TakeOrderedAndProject."""
        from hyperspace_tpu.ops.sortkeys import column_lanes, lanes_as_unsigned

        table = self._execute(sort_plan.child)
        rows = table.num_rows
        if n <= 0:
            return table.take(np.arange(0))
        if rows <= max(2 * n, 1024):
            # Full sort (venue-aware via _sort's own machinery).
            self._phys("TopN", n=n, kernel="full-sort")
            full = self._sorted_table(table, sort_plan)
            return full.take(np.arange(min(n, full.num_rows)))
        # Pack the FIRST sort column's lanes into one u64 selection key
        # (DESC via the same lane inversion the full sort uses). A
        # constant validity lane is dropped so both 32-bit words carry
        # real key entropy (else a low-entropy hi word degenerates the
        # selection to ~all rows).
        c0, asc0 = sort_plan.by[0]
        has_nulls = table.valid_mask(c0) is not None
        lanes = column_lanes(table, c0, force_validity=has_nulls)
        if not asc0:
            lanes = [~l for l in lanes]
        lu = lanes_as_unsigned(lanes[:2])
        from hyperspace_tpu.parallel.mesh import make_mesh, mesh_size

        sharded = self.mesh is not None and mesh_size(self.mesh) > 1
        # The sort venue decides, as for every other operator: the device
        # selects across a real mesh or on its one device; the host
        # venue keeps the partition select.
        if self._venue("sort") == "device":
            # Per-device first-n + one threshold broadcast; on a mesh
            # the ORDER BY participates in every device.
            from hyperspace_tpu.ops.sortkeys import distributed_top_n_candidates

            mesh = self.mesh if sharded else make_mesh(n=1)
            cand = distributed_top_n_candidates(lu, n, mesh)
            if cand is not None:
                sub = table.take(cand)
                self._phys(
                    "TopN",
                    n=n,
                    kernel=("mesh-sharded-select" if sharded else "device-select") + " + sort",
                    candidates=len(cand),
                    devices=mesh_size(mesh),
                )
                full = self._sorted_table(sub, sort_plan)
                return full.take(np.arange(min(n, full.num_rows)))
        kpack = (lu[0].astype(np.uint64) << np.uint64(32)) | (
            lu[1].astype(np.uint64) if lu.shape[0] > 1 else np.uint64(0)
        )
        thr = np.partition(kpack, n - 1)[n - 1]
        # The selection key may be a PREFIX of the first column's order
        # (extra lanes unseen) — prefix-ties stay in, and every true
        # top-n row provably has prefix <= thr; the exact sort of the
        # candidate set settles the rest.
        cand = np.flatnonzero(kpack <= thr)
        sub = table.take(cand)
        self._phys("TopN", n=n, kernel="host-partition-select + sort", candidates=len(cand))
        full = self._sorted_table(sub, sort_plan)
        return full.take(np.arange(min(n, full.num_rows)))

    def _sort(self, plan: "Sort") -> ColumnTable:
        table = self._execute(plan.child)
        venue = self._venue("sort")
        self._phys(f"{venue.capitalize()}Sort", keys=[c for c, _ in plan.by], venue=venue)
        return self._sorted_table(table, plan, venue)

    def _sorted_table(self, table: ColumnTable, plan: "Sort", venue: str | None = None) -> ColumnTable:
        """Venue-aware total order of an already-materialized table."""
        from hyperspace_tpu.ops.sortkeys import (
            device_order_perm,
            lexsort_lanes,
            order_lanes,
        )

        if table.num_rows <= 1:
            return table
        if venue is None:
            venue = self._venue("sort")
        if venue == "host":
            return table.take(lexsort_lanes(order_lanes(table, plan.by)))
        return table.take(device_order_perm(table, plan.by))

    # -- union (hybrid scan) ----------------------------------------------
    def _union(self, plan: Union) -> ColumnTable:
        schema = plan.schema
        parts = []
        for child in plan.inputs:
            t = self._execute(child)
            # Remap onto the union schema's exact field names/order (child
            # names are validated case-insensitively compatible).
            cols, dicts, val = {}, {}, {}
            for f in schema.fields:
                cf = t.schema.field(f.name)
                cols[f.name] = t.columns[cf.name]
                if cf.name in t.dictionaries:
                    dicts[f.name] = t.dictionaries[cf.name]
                if cf.name in t.validity:
                    val[f.name] = t.validity[cf.name]
            parts.append(ColumnTable(schema, cols, dicts, val))
        return ColumnTable.concat(parts)

    # -- scan ------------------------------------------------------------
