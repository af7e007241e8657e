"""Join execution: the per-bucket merge join over bucket-grouped
layouts, match-pair derivation, broadcast-hash fallback, outer/semi/
anti composition, and ON-residual matching (Executor mixin)."""

from __future__ import annotations

import numpy as np

from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.ops.filter import eval_predicate_mask
from hyperspace_tpu.ops import join as join_ops
from hyperspace_tpu.plan.nodes import Join

from hyperspace_tpu.execution.exec_common import (
    SideData,
    _broadcast_probe,
    _bucket_sorted_codes,
    _composite_keys,
    _copy_field,
    _factorize_keys_cached,
    _null_field,
    _pad_bucket_major_cached,
)


class JoinMixin:
    def _join(self, plan: Join) -> ColumnTable:
        lside, rside, left_side, right_side = self._join_sides(plan)
        # Path from THIS frame's decision (the _join_sides call above
        # sets it LAST, after any nested joins it executed ran). buckets/
        # devices are read after _partition_join, which sets them for the
        # kernel that just ran (this join's own).
        path = self.stats["join_path"]
        if left_side is not None:
            out = self._aligned_join(plan, left_side, right_side, lside, rside)
        else:
            out = self._partition_join(plan, lside, rside)
        if self.stats["join_kernel"] == "host-broadcast-hash":
            path = "broadcast-hash"
            self.stats["join_path"] = path
        if plan.condition is not None and plan.how == "inner":
            # Inner-join ON residual: a plain 3-valued filter over the
            # matched rows, venue- and mesh-aware like every other
            # predicate site. (Outer/semi/anti residuals alter MATCHING
            # and are applied inside _partition_join.) The filtered
            # table deliberately does NOT inherit any preserved bucket
            # grouping (per-bucket counts changed).
            before = out.num_rows
            mask = eval_predicate_mask(
                out, plan.condition, mesh=self.mesh, venue=self._venue("filter")
            )
            out = out.filter_mask(mask)
            self._phys(residual_condition=True, residual_rows_dropped=before - out.num_rows)
        self._phys(
            "BroadcastHashJoin" if path == "broadcast-hash" else "SortMergeJoin",
            path=path,
            kernel=self.stats["join_kernel"],
            buckets=self.stats["num_buckets"],
            devices=self.stats["join_devices"],
        )
        return out

    def _partition_join(self, plan: Join, lside: "SideData", rside: "SideData") -> ColumnTable:
        """Per-bucket merge join over the concatenated bucket-grouped
        layout: everything host-side is vectorized (pad-gather in, one
        repeat+add to globalize match indices, ONE native gather per
        column out) — no per-bucket Python loop (round 1 weakness #4).
        Non-inner join types derive from the same match pairs: outer
        variants append the unmatched side's rows null-extended, semi/anti
        keep left rows by match flag (the join-type surface Spark's
        SortMergeJoinExec serves over the reference's rewritten bucketed
        relations, JoinIndexRule.scala:124-153)."""
        lt, rt = lside.table, rside.table
        how = plan.how

        if how in ("semi", "anti") and plan.condition is None:
            # Existence is a membership probe, not a join: never expand the
            # match pairs (a hot key repeated k×k ways would materialize k²
            # pairs only to collapse into |L| bits).
            matched = self._semi_match_mask(plan, lside, rside)
            out = lt.filter_mask(matched if how == "semi" else ~matched)
            return ColumnTable(plan.schema, out.columns, out.dictionaries, out.validity)

        lidx, ridx, totals = self._match_pairs(plan, lside, rside)

        if how in ("semi", "anti"):
            # Residual existence (EXISTS with extra conditions): a left
            # row matches iff SOME equi-pair also passes the residual —
            # gather ONLY the columns the condition reads (the pairs are
            # k x k expanded; none of the payload survives the |L|-bit
            # reduction), evaluate, and reduce surviving lidx to bits.
            from hyperspace_tpu.schema import Schema as _Schema

            refs = {r.lower() for r in plan.condition.references()}
            rkeys_low = {rt.schema.field(c).name.lower() for c in plan.right_on}
            lkeep = [f.name for f in lt.schema.fields if f.name.lower() in refs]
            if not lkeep:  # keep one cheap key lane so row count survives
                lkeep = [lt.schema.field(plan.left_on[0]).name]
            rkeep = [rt.schema.field(c).name for c in plan.right_on] + [
                f.name
                for f in rt.schema.fields
                if f.name.lower() in refs and f.name.lower() not in rkeys_low
            ]
            sub_schema = _Schema(
                tuple(lt.schema.select(lkeep).fields)
                + tuple(
                    f for f in rt.schema.select(rkeep).fields
                    if f.name.lower() not in rkeys_low
                )
            )
            pairs = self._gather_pairs(
                plan, lt.select(lkeep), rt.select(rkeep), lidx, ridx, schema=sub_schema
            )
            pmask = eval_predicate_mask(
                pairs, plan.condition, mesh=self.mesh, venue=self._venue("filter")
            )
            matched = np.zeros(lt.num_rows, dtype=bool)
            matched[lidx[pmask]] = True
            self._phys(residual_condition=True, residual_pairs_dropped=int((~pmask).sum()))
            out = lt.filter_mask(matched if how == "semi" else ~matched)
            return ColumnTable(plan.schema, out.columns, out.dictionaries, out.validity)

        inner = self._gather_pairs(plan, lt, rt, lidx, ridx)
        if plan.condition is not None and how != "inner":
            # Outer-join ON residual alters MATCHING: a pair failing it
            # is no match, so its rows fall through to the null-extended
            # unmatched parts below (computed from the SURVIVING pairs).
            pmask = eval_predicate_mask(
                inner, plan.condition, mesh=self.mesh, venue=self._venue("filter")
            )
            inner = inner.filter_mask(pmask)
            lidx, ridx = lidx[pmask], ridx[pmask]
            self._phys(residual_condition=True, residual_pairs_dropped=int((~pmask).sum()))
        if how == "inner":
            # Bucket-preserving output: an inner join over B>1 buckets
            # emits pairs bucket-major, so the result STAYS bucket-
            # grouped on the (merged, left-named) join keys — a later
            # join on the same keys reuses the grouping with no exchange
            # (SURVEY §2.3: chained star joins stay bucket-parallel).
            if (
                totals is not None
                and len(totals) > 1
                and lside.hash_fields is not None
            ):
                self._stash_bucketed(
                    inner,
                    np.concatenate([[0], np.cumsum(totals)]).astype(np.int64),
                    plan.left_on,
                    lside.hash_fields,
                )
            return inner
        parts = [inner]
        if how in ("left", "full"):
            lmask = np.zeros(lt.num_rows, dtype=bool)
            lmask[lidx] = True
            parts.append(self._left_unmatched(plan, lt, rt, ~lmask))
        if how in ("right", "full"):
            rmask = np.zeros(rt.num_rows, dtype=bool)
            rmask[ridx] = True
            parts.append(self._right_unmatched(plan, lt, rt, ~rmask))
        parts = [p for p in parts if p.num_rows > 0]
        if not parts:
            return inner
        # Concat builds from plan.schema, so any extra physical columns a
        # wide index scan carried along are dropped here; the outer-join
        # output is exactly the declared join schema.
        return ColumnTable.concat(parts) if len(parts) > 1 else parts[0]

    def _semi_match_mask(self, plan: Join, lside: "SideData", rside: "SideData") -> np.ndarray:
        """Per-left-row existence of an equi-match in the right side:
        one sorted membership probe over (bucket, key-code) composites —
        O((n+m) log m) on host, no pair expansion, no device round-trip
        (the result is |L| bits the mask filter consumes on host anyway).
        Null-keyed rows carry side-distinct negative codes and never
        match (SQL: NULL = NULL is not true), so anti keeps them —
        unless the join is null-safe (set-op desugar), where NULL is a
        real per-column domain value and matches its twin."""
        lt, rt = lside.table, rside.table
        lkeys = [lt.schema.field(c).name for c in plan.left_on]
        rkeys = [rt.schema.field(c).name for c in plan.right_on]
        lc0, rc0 = _factorize_keys_cached(lt, rt, lkeys, rkeys, null_safe=plan.null_safe)
        lcodes = lc0.astype(np.int64)
        rcodes = rc0.astype(np.int64)
        b = len(lside.offsets) - 1
        self.stats["num_buckets"] = b
        self.stats["join_kernel"] = "host-membership-probe"
        comp_l = _composite_keys(lcodes, lside.offsets)
        comp_r = np.sort(_composite_keys(rcodes, rside.offsets))
        pos = np.searchsorted(comp_r, comp_l)
        matched = np.zeros(lt.num_rows, dtype=bool)
        in_range = pos < len(comp_r)
        matched[in_range] = comp_r[pos[in_range]] == comp_l[in_range]
        return matched

    def _match_pairs(self, plan: Join, lside: "SideData", rside: "SideData"):
        """(lidx, ridx) global match row indices of the equi-join, from the
        venue-selected merge kernel over bucket-sorted key codes. A
        heavily asymmetric single-partition join takes the broadcast hash
        path instead: only the small side is sorted, the large side
        probes it — the analog of Spark's BroadcastExchange fallback the
        reference environment supplies for small sides
        (PhysicalOperatorAnalyzer.scala:46-50)."""
        lt, rt = lside.table, rside.table
        lkeys = [lt.schema.field(c).name for c in plan.left_on]
        rkeys = [rt.schema.field(c).name for c in plan.right_on]

        # Shared order-preserving factorization of the key tuples.
        lcodes, rcodes = _factorize_keys_cached(
            lt, rt, lkeys, rkeys, null_safe=plan.null_safe
        )

        b0 = len(lside.offsets) - 1
        if b0 == 1 and self._should_broadcast(lt.num_rows, rt.num_rows):
            res = _broadcast_probe(lcodes, rcodes)
            if res is not None:
                self.stats["num_buckets"] = 1
                self.stats["join_kernel"] = "host-broadcast-hash"
                return res[0], res[1], None

        # Non-aligned sides re-group through the fused bucket+key device
        # sort when the sort venue allows (host np.lexsort otherwise —
        # identical stable permutation either way).
        regroup_venue = self._venue("sort")
        lcodes, lperm = _bucket_sorted_codes(lcodes, lside, venue=regroup_venue)
        rcodes, rperm = _bucket_sorted_codes(rcodes, rside, venue=regroup_venue)
        b = len(lside.offsets) - 1
        self.stats["num_buckets"] = b

        host_res = None
        if (
            lcodes.dtype == np.int32
            and rcodes.dtype == np.int32
            and self._venue("join") == "host"
        ):
            from hyperspace_tpu import native

            host_res = native.merge_join_sorted(
                lcodes, lside.offsets, rcodes, rside.offsets
            )
        if host_res is not None:
            # Host venue: exact bucket-parallel C++ merge over the already
            # host-resident sorted runs — no device round-trip.
            lidx, ridx, totals = host_res
            self.stats["join_kernel"] = "host-native-merge"
        else:
            lk = _pad_bucket_major_cached(lcodes, lside.offsets)
            rk = _pad_bucket_major_cached(rcodes, rside.offsets)
            if self.mesh is not None:
                from hyperspace_tpu.parallel.mesh import mesh_for_parallelism, mesh_size

                jmesh = mesh_for_parallelism(self.mesh, b)
                li_flat, ri_flat, totals = join_ops.merge_join_sharded(lk, rk, jmesh)
                self.stats["join_devices"] = mesh_size(jmesh)
            else:
                li_flat, ri_flat, totals = join_ops.merge_join(lk, rk)
            self.stats["join_kernel"] = "device-searchsorted"
            # Local (within-bucket) match indices → global row indices.
            lidx = np.repeat(lside.offsets[:-1], totals) + li_flat
            ridx = np.repeat(rside.offsets[:-1], totals) + ri_flat
        if lperm is not None:
            lidx = lperm[lidx]
        if rperm is not None:
            ridx = rperm[ridx]
        # Pair order stays bucket-major through the perm mapping, so
        # `totals` doubles as the OUTPUT's bucket grouping.
        return lidx, ridx, np.asarray(totals, dtype=np.int64)

    def _should_broadcast(self, n_l: int, n_r: int) -> bool:
        """Small-enough and asymmetric-enough for the broadcast probe."""
        from hyperspace_tpu.config import DEFAULT_JOIN_BROADCAST_MAX_ROWS

        cap = (
            self.conf.join_broadcast_max_rows
            if self.conf is not None
            else DEFAULT_JOIN_BROADCAST_MAX_ROWS
        )
        if cap <= 0:
            return False
        small, large = min(n_l, n_r), max(n_l, n_r)
        return 0 < small <= cap and large >= 4 * small

    def _gather_pairs(
        self, plan: Join, lt: ColumnTable, rt: ColumnTable, lidx, ridx, schema=None
    ) -> ColumnTable:
        """Materialize matched rows: left columns + right non-key columns.
        `schema` overrides the output schema (semi/anti residual
        evaluation gathers in the inner-join shape)."""
        schema = schema if schema is not None else plan.schema
        rkeys_low = {rt.schema.field(c).name.lower() for c in plan.right_on}
        lgather = lt.take(lidx)
        cols = dict(lgather.columns)
        dicts = dict(lgather.dictionaries)
        val = dict(lgather.validity)
        rnames = [f.name for f in rt.schema.fields if f.name.lower() not in rkeys_low]
        rgather = rt.select(rnames).take(ridx)
        cols.update(rgather.columns)
        dicts.update(rgather.dictionaries)
        val.update(rgather.validity)
        return ColumnTable(schema, cols, dicts, val)

    def _left_unmatched(self, plan: Join, lt: ColumnTable, rt: ColumnTable, mask) -> ColumnTable:
        """Unmatched left rows, right-side fields null-extended."""
        sub = lt.filter_mask(mask)
        lnames = {x.lower() for x in plan.left.schema.names}
        cols: dict = {}
        dicts: dict = {}
        val: dict = {}
        for f in plan.schema.fields:
            if f.name.lower() in lnames:
                _copy_field(f, sub, f.name, cols, dicts, val)
            else:
                _null_field(f, sub.num_rows, rt, cols, dicts, val)
        return ColumnTable(plan.schema, cols, dicts, val)

    def _right_unmatched(self, plan: Join, lt: ColumnTable, rt: ColumnTable, mask) -> ColumnTable:
        """Unmatched right rows: key columns coalesce to the RIGHT key's
        values (under the left-named output column), right non-key fields
        carry their values, left-only fields are null-extended."""
        sub = rt.filter_mask(mask)
        key_src = {l.lower(): r for l, r in zip(plan.left_on, plan.right_on)}
        rnames = {x.lower() for x in plan.right.schema.names}
        cols: dict = {}
        dicts: dict = {}
        val: dict = {}
        for f in plan.schema.fields:
            low = f.name.lower()
            if low in key_src:
                _copy_field(f, sub, key_src[low], cols, dicts, val)
            elif low in rnames:
                _copy_field(f, sub, f.name, cols, dicts, val)
            else:
                _null_field(f, sub.num_rows, lt, cols, dicts, val)
        return ColumnTable(plan.schema, cols, dicts, val)


