"""Scan + filter execution: cached decode, bucket pruning, range
pruning, hybrid scan reads (Executor mixin)."""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np
import pyarrow as pa

from hyperspace_tpu import stats as _ft_stats
from hyperspace_tpu.exceptions import IndexCorruptionError
from hyperspace_tpu.execution import io as hio
from hyperspace_tpu.execution.build_exchange import hash_scalar_key
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.dataset import format_suffix, list_data_files
from hyperspace_tpu.ops.filter import apply_filter
from hyperspace_tpu.ops.hashing import bucket_ids
from hyperspace_tpu.plan.expr import BinOp, Col, Expr, Lit, split_conjuncts
from hyperspace_tpu.plan.nodes import Filter, LogicalPlan, Scan, Union

from hyperspace_tpu.execution.exec_common import (
    KeyBounds,
    _convert_bounds,
    _stats_overlap,
    key_bounds,
    predicate_all_key_bounds,
)


def _corruption(e: BaseException, index_root: str, files: list[str]) -> IndexCorruptionError:
    """Wrap an unreadable-index-file failure with provenance (which index,
    which files) for the session's health map and fallback re-plan."""
    _ft_stats.increment("index.corruption")
    return IndexCorruptionError(
        f"unreadable index data under {index_root}: {e}",
        index_root=index_root,
        path=files[0] if files else None,
    )


# Bucket pruning reads at most this many point combinations; above it
# the (still-correct) range/mask machinery takes over.
MAX_POINT_COMBOS = 64


def scan_files(scan: Scan) -> list[str]:
    if scan.files is not None:
        return list(scan.files)
    return [fi.path for fi in list_data_files(scan.root, suffix=format_suffix(scan.format))]


def point_prune_names(scan: Scan, predicate: Expr, max_combos: int = MAX_POINT_COMBOS) -> set[str] | None:
    """Bucket file NAMES owned by the predicate's equality/IN literals on
    every bucket column, or None when the predicate does not pin them (or
    the combination count exceeds `max_combos`). Pure — shared by the
    executor's pruner and the plan-time prefetcher. The analog of
    partition pruning the reference cannot do (FilterIndexRule keeps a
    full scan, FilterIndexRule.scala:114-120); IN on the bucket column
    divides IO by numBuckets/|IN| instead of 1."""
    import itertools
    import math

    from hyperspace_tpu.plan.expr import InList

    num_buckets, bucket_cols = scan.bucket_spec
    cand: dict[str, list] = {}
    for conj in split_conjuncts(predicate):
        got: tuple[str, list] | None = None
        if isinstance(conj, BinOp) and conj.op == "eq":
            if isinstance(conj.left, Col) and isinstance(conj.right, Lit):
                got = (conj.left.name.lower(), [conj.right.value])
            elif isinstance(conj.right, Col) and isinstance(conj.left, Lit):
                got = (conj.right.name.lower(), [conj.left.value])
        elif isinstance(conj, InList) and isinstance(conj.child, Col):
            got = (conj.child.name.lower(), list(conj.values))
        if got is not None:
            name, vals = got
            # Conjunctive constraints: any one conjunct's list is a
            # valid superset of the reachable values — keep the
            # smallest.
            if name not in cand or len(vals) < len(cand[name]):
                cand[name] = vals
    try:
        lists = [cand[c.lower()] for c in bucket_cols]
    except KeyError:
        return None
    if math.prod(len(l) for l in lists) > max_combos:
        return None
    fields = [scan.scan_schema.field(c) for c in bucket_cols]
    names: set[str] = set()
    for combo in itertools.product(*lists):
        h = hash_scalar_key(list(combo), fields)
        names.add(hio.bucket_file_name(int(bucket_ids(h, num_buckets, np)[0])))
    return names


class ScanFilterMixin:
    def _scan_files(self, scan: Scan) -> list[str]:
        return scan_files(scan)

    def _cached_read(self, files: list[str], columns, schema, index_root: str | None = None) -> ColumnTable:
        """Index-file read through the decoded-table cache; files_read
        counts only physical (miss) reads. With `index_root` (the read
        serves an INDEX scan), an unreadable file — missing, truncated,
        or garbage parquet — surfaces as a typed IndexCorruptionError so
        the session can quarantine the index and re-plan against the
        source instead of failing the query.

        Index scans decode PER FILE and concatenate through the cached
        side-concat — the join-side pattern. Two wins over one
        multi-file decode: each single-chunk per-file column stages as a
        zero-copy Arrow buffer view (a 16-file concat is multi-chunk and
        can never stage — this was the whole filter/group_agg staging
        tax), and per-file cache entries are shared across queries with
        DIFFERENT surviving file subsets (pruning no longer forces a
        full re-decode). The frozen concat itself is identity-cached, so
        repeat queries skip it entirely."""
        before = hio.table_cache_stats()
        try:
            if index_root is not None and len(files) > 1:
                from concurrent.futures import ThreadPoolExecutor

                from hyperspace_tpu.execution.exec_common import _concat_side_cached
                from hyperspace_tpu.obs import trace as obs_trace

                read = obs_trace.wrap(
                    lambda f: hio.read_parquet_cached([f], columns=columns, schema=schema)
                )
                with ThreadPoolExecutor(max_workers=min(8, len(files))) as ex:
                    tables = list(ex.map(read, files))
                table = _concat_side_cached(tables)
            else:
                table = hio.read_parquet_cached(files, columns=columns, schema=schema)
        except IndexCorruptionError:
            raise
        except (OSError, pa.ArrowException) as e:
            if index_root is None:
                raise
            raise _corruption(e, index_root, files) from e
        finally:
            after = hio.table_cache_stats()
            self.stats["files_read"] += after["miss_files"] - before["miss_files"]
            self.stats["bytes_scanned"] += after["miss_bytes"] - before["miss_bytes"]
        return table

    def _scan(self, scan: Scan, columns: list[str] | None = None) -> ColumnTable:
        files = self._scan_files(scan)
        cols = columns if columns is not None else scan.scan_schema.names
        if not files:  # everything pruned away
            return ColumnTable.empty(scan.scan_schema.select(cols))
        if scan.format == "parquet":
            # ALL parquet scans ride the decoded-table cache, not just
            # index files: the cache validates per-file mtimes, so a
            # mutated source re-decodes while repeat queries over stable
            # sources (dimension tables above all) skip the decode — the
            # analog of Spark's in-memory relation cache.
            root = scan.root if scan.bucket_spec is not None else None
            return self._cached_read(files, cols, scan.scan_schema, index_root=root)
        self.stats["files_read"] += len(files)
        import os as _os

        try:
            self.stats["bytes_scanned"] += sum(_os.path.getsize(f) for f in files)
        except OSError:
            pass
        return hio.read_table_files(files, scan.format, columns=cols, schema=scan.scan_schema)

    # -- filter (with index bucket pruning) ------------------------------
    def _filter(self, plan: Filter) -> ColumnTable:
        child = plan.child
        # Per-OPERATOR pruning evidence: deltas of the query-cumulative
        # counters from this frame's start.
        fp0, rp0 = self.stats["files_pruned"], self.stats["rows_pruned"]
        mask_venue = self._venue("filter")
        mask_kernel = "host-mask" if mask_venue == "host" else "fused-xla-mask"
        if isinstance(child, Scan) and child.bucket_spec is not None:
            pruned = self._prune_bucket_files(child, plan.predicate)
            if pruned is not None:
                self._phys(
                    "IndexPointLookup",
                    files_pruned=self.stats["files_pruned"] - fp0,
                    kernel=f"bucket-hash-prune + {mask_kernel}",
                )
                table = self._cached_read(
                    pruned, child.scan_schema.names, child.scan_schema, index_root=child.root
                )
                return apply_filter(table, plan.predicate, mesh=self.mesh, venue=mask_venue)
            key_only = predicate_all_key_bounds(plan.predicate, child.bucket_spec[1][0])
            # When the slice is the whole predicate, it is the operator's
            # only compute: the device venue finds it on the device.
            ranged = self._range_read(
                child, plan.predicate, on_device=key_only and mask_venue != "host"
            )
            if ranged is not None:
                table, exact, slicer = ranged
                if exact and key_only:
                    # The slice IS the predicate: every conjunct bounds the
                    # sorted key, so the residual mask would be all-true —
                    # skip its evaluation (and the device round-trip).
                    self._phys(
                        "IndexRangeScan",
                        files_pruned=self.stats["files_pruned"] - fp0,
                        rows_pruned=self.stats["rows_pruned"] - rp0,
                        kernel=f"minmax-prune + {slicer} (exact, mask skipped)",
                    )
                    return table
                self._phys(
                    "IndexRangeScan",
                    files_pruned=self.stats["files_pruned"] - fp0,
                    rows_pruned=self.stats["rows_pruned"] - rp0,
                    kernel=f"minmax-prune + {slicer} + {mask_kernel}",
                )
                return apply_filter(table, plan.predicate, mesh=self.mesh, venue=mask_venue)
        if isinstance(child, Union):
            # Hybrid scan: prune the bucketed input(s), keep deltas whole.
            new_inputs: list[LogicalPlan] = []
            for inp in child.inputs:
                if isinstance(inp, Scan) and inp.bucket_spec is not None:
                    pruned = self._prune_bucket_files(inp, plan.predicate)
                    if pruned is None:
                        ranged = self._range_prune_list(inp, plan.predicate)
                        pruned = ranged[0] if ranged is not None else None  # (kept, bounds, stats)
                    if pruned is not None:
                        inp = dataclasses.replace(inp, files=pruned)
                new_inputs.append(inp)
            self._phys(
                "HybridScanFilter",
                files_pruned=self.stats["files_pruned"] - fp0,
                kernel=f"bucket/minmax-prune + {mask_kernel}",
            )
            return apply_filter(
                self._union(Union(new_inputs)), plan.predicate,
                mesh=self.mesh, venue=mask_venue,
            )
        self._phys(kernel=mask_kernel)
        return apply_filter(self._execute(child), plan.predicate, mesh=self.mesh, venue=mask_venue)

    def _prune_bucket_files(self, scan: Scan, predicate: Expr) -> list[str] | None:
        """If the predicate pins every bucket column with equality
        literals — single (eq) or multi-point (IN) — return only the
        owning buckets' files (see point_prune_names)."""
        names = point_prune_names(scan, predicate)
        if names is None:
            return None
        files = self._scan_files(scan)
        matches = [f for f in files if Path(f).name in names]
        if matches:
            self.stats["files_pruned"] += len(files) - len(matches)
            return matches
        return None

    def _range_prune_list(
        self, scan: Scan, predicate: Expr
    ) -> tuple[list[str], KeyBounds, dict] | None:
        """File-level range (min/max) pruning: drop bucket files whose
        manifest key stats cannot overlap the predicate's bounds on the
        leading indexed column. The analog of FileSourceScanExec's parquet
        min/max pruning (SURVEY.md §2.2), which the reference inherits
        from Spark. Comparisons run in the filter mask's own numeric
        domain so pruning never disagrees with it. Returns None when no
        literal bounds or no stats exist."""
        key = scan.bucket_spec[1][0]
        bounds = key_bounds(predicate, key)
        files = self._scan_files(scan)
        stats = hio.file_key_stats(files) if bounds is not None else {}
        if bounds is not None and stats:
            bounds, stat_conv = _convert_bounds(scan.scan_schema.field(key), bounds)
        else:
            stat_conv = None
        # Included-column pruning: any OTHER referenced column with
        # manifest columnStats and literal bounds prunes too (the
        # reference gets this from parquet per-column min/max via
        # FileSourceScanExec, SURVEY.md §2.2).
        refs = {r.lower() for r in predicate.references()}
        extra: list[tuple[KeyBounds, object, dict]] = []
        for c in scan.scan_schema.names:
            if c.lower() == key.lower() or c.lower() not in refs:
                continue
            b = key_bounds(predicate, c)
            if b is None:
                continue
            cstats = hio.file_column_stats(files, c)
            if not cstats:
                continue
            cb, cconv = _convert_bounds(scan.scan_schema.field(c), b)
            extra.append((cb, cconv, cstats))
        if stat_conv is None and not extra:
            return None
        kept: list[str] = []
        for f in files:
            keep = True
            if stat_conv is not None and f in stats:
                s = stats[f]
                # s is None ⇔ bucket empty or all-null key: no row can
                # satisfy a literal comparison (3VL), safe to skip.
                keep = s is not None and _stats_overlap(bounds, stat_conv(s[0]), stat_conv(s[1]))
            for cb, cconv, cstats in extra:
                if not keep:
                    break
                if f in cstats:
                    s = cstats[f]
                    keep = s is not None and _stats_overlap(cb, cconv(s[0]), cconv(s[1]))
            if keep:
                kept.append(f)
        if stat_conv is None and len(kept) == len(files):
            # Included-column stats pruned nothing and the key gives no
            # slicing bounds: stay on the plain scan path (whole cached
            # bucket files — the device upload cache keys on them).
            return None
        self.stats["files_pruned"] += len(files) - len(kept)
        return kept, (bounds if stat_conv is not None else None), stats

    def _range_read(
        self, scan: Scan, predicate: Expr, on_device: bool = False
    ) -> tuple[ColumnTable, bool, str] | None:
        """File-level range pruning + within-file searchsorted slicing
        (each surviving file is key-sorted by construction, so qualifying
        rows form one contiguous run). Dictionary codes are not
        value-ordered across files and null prefixes break sortedness —
        both fall back to reading the file whole (mask handles the rest).
        Returns (table, exact, slicer): exact ⇔ every row returned provably
        satisfies the key bounds (all parts sliced on a sorted, null-free,
        stats-backed key); slicer names where the run bounds were found.
        With `on_device`, an integer key's bounds for every file come
        from one device call (ops/sortkeys.device_slice_bounds)."""
        from hyperspace_tpu.ops.sortkeys import device_slice_bounds

        from concurrent.futures import ThreadPoolExecutor

        pruned = self._range_prune_list(scan, predicate)
        if pruned is None:
            return None
        kept, bounds, stats_files = pruned
        schema = scan.scan_schema
        field = schema.field(scan.bucket_spec[1][0])
        slicer = "searchsorted-slice"
        if not kept:
            return ColumnTable.empty(schema), True, slicer
        before = hio.table_cache_stats()
        try:
            with ThreadPoolExecutor(max_workers=min(8, len(kept))) as pool:
                tables = list(
                    pool.map(
                        lambda fp: hio.read_parquet_cached([fp], columns=schema.names, schema=schema),
                        kept,
                    )
                )
        except IndexCorruptionError:
            raise
        except (OSError, pa.ArrowException) as e:
            raise _corruption(e, scan.root, kept) from e
        finally:
            after = hio.table_cache_stats()
            self.stats["files_read"] += after["miss_files"] - before["miss_files"]
            self.stats["bytes_scanned"] += after["miss_bytes"] - before["miss_bytes"]
        parts: list[ColumnTable] = []
        # Float keys can hold NaN VALUES (sorted last by the build); a
        # lower-bound-only slice would include them while the mask drops
        # them — never claim exactness for float key columns. bounds is
        # None when only included-column stats pruned: no key slicing.
        exact = bounds is not None and field.device_dtype.kind != "f"
        live = [(fp, t) for fp, t in zip(kept, tables) if t.num_rows]
        sliceable = [
            bounds is not None
            and not field.is_string
            and t.valid_mask(field.name) is None
            and fp in stats_files  # stats-backed ⇒ written key-sorted
            for fp, t in live
        ]
        runs = None
        if on_device and live and all(sliceable) and field.device_dtype.kind in "iu":
            runs = device_slice_bounds([t.columns[field.name] for _, t in live], bounds)
            if runs is not None:
                slicer = "device-searchsorted-slice"
        for i, ((_fp, t), cut) in enumerate(zip(live, sliceable)):
            if cut:
                colv = t.columns[field.name]
                lo_i, hi_i = 0, t.num_rows
                if runs is not None:
                    lo_i, hi_i = int(runs[i, 0]), int(runs[i, 1])
                else:
                    if bounds.lo is not None:
                        lo_i = int(np.searchsorted(colv, bounds.lo, side="right" if bounds.lo_strict else "left"))
                    if bounds.hi is not None:
                        hi_i = int(np.searchsorted(colv, bounds.hi, side="left" if bounds.hi_strict else "right"))
                if hi_i <= lo_i:
                    self.stats["rows_pruned"] += t.num_rows
                    continue
                if lo_i > 0 or hi_i < t.num_rows:
                    self.stats["rows_pruned"] += t.num_rows - (hi_i - lo_i)
                    t = t.take(np.arange(lo_i, hi_i))
            else:
                exact = False
            parts.append(t)
        if not parts:
            return ColumnTable.empty(schema), True, slicer
        out = ColumnTable.concat(parts) if len(parts) > 1 else parts[0]
        return out, exact, slicer

    # -- join ------------------------------------------------------------
