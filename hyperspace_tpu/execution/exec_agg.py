"""Aggregate execution: segment reduce, partial-agg pushdown, distinct
expansion, and grouping-set re-folds (Executor mixin)."""

from __future__ import annotations


import numpy as np

from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.plan.expr import Col, Lit
from hyperspace_tpu.plan.nodes import Aggregate, Join, LogicalPlan

from hyperspace_tpu.execution.exec_common import (
    _TableLeaf,
    _copy_field,
    _desugar_count_distinct,
    _group_ids_cached,
    _null_field,
)


class AggregateMixin:
    def _aggregate(self, plan: "Aggregate") -> ColumnTable:
        from hyperspace_tpu.ops.aggregate import aggregate_table

        if plan.grouping_sets is not None:
            return self._grouping_sets_aggregate(plan)
        if any(a.fn == "count_distinct" for a in plan.aggs):
            for a in plan.aggs:
                if a.fn == "count_distinct" and not isinstance(a.expr, Col):
                    raise HyperspaceError("count_distinct requires a plain column")
            dcols = {a.expr.name.lower() for a in plan.aggs if a.fn == "count_distinct"}
            if len(dcols) == 1 and not any(a.fn == "mean" for a in plan.aggs):
                # Single distinct column, no mean: the plan-level two-phase
                # desugar keeps the inner aggregate eligible for the fused
                # Aggregate(Join) path.
                self._phys("CountDistinctReaggregate")
                plan2, count_aliases = _desugar_count_distinct(plan)
                out = self._execute(plan2)
                # SQL count is never NULL: the outer SUM of count partials
                # yields NULL over zero inner rows — restore the 0.
                for alias in count_aliases:
                    f = out.schema.field(alias)
                    v = out.validity.pop(f.name, None)
                    if v is not None:
                        out.columns[f.name] = np.where(v, out.columns[f.name], 0)
                return out
            return self._distinct_aggregate(plan, sorted(dcols))
        venue = self._venue("agg")
        pushed = self._try_partial_agg_pushdown(plan)
        if isinstance(pushed, ColumnTable):
            return pushed
        if pushed is not None:
            # Pushdown bailed AFTER materializing the left side: continue
            # with the spliced plan so nothing below re-executes it.
            plan = pushed
        # Fuse Aggregate(Join) on both venues: the device run-prefix
        # kernel avoids the match-pair readback; the host C++
        # merge+accumulate avoids materializing the pairs at all.
        fused = self._try_fused_join_aggregate(plan)
        if fused is not None:
            self._phys(
                "FusedJoinAggregate",
                join_path=self.stats["join_path"],
                kernel=self.stats["join_kernel"],
                buckets=self.stats["num_buckets"],
            )
            return fused
        table = self._execute(plan.child)
        self.stats["agg_path"] = f"segment-reduce-{venue}"
        mesh = self.mesh if venue == "device" else None
        if mesh is not None:
            from hyperspace_tpu.parallel.mesh import mesh_size

            self.stats["agg_devices"] = mesh_size(mesh)
        self._phys(
            "SegmentReduceAggregate",
            venue=venue,
            groups=len(plan.group_by),
            aggs=len(plan.aggs),
            devices=self.stats.get("agg_devices", 1),
        )
        return aggregate_table(
            table, plan.group_by, plan.aggs, plan.schema, venue=venue, mesh=mesh,
            # Identity-cached factorization: repeat aggregations over a
            # stable index version skip re-factorizing the keys.
            groups=_group_ids_cached(table, plan.group_by),
        )

    def _try_partial_agg_pushdown(self, plan: "Aggregate") -> "ColumnTable | Aggregate | None":
        """Partial aggregation pushdown (Spark's PartialAggregate /
        aggregate-through-join analog): for Aggregate(Join(L, R)) where
        every aggregate reads only the L side — optionally inside a
        CASE whose CONDITION reads only the R side (the q43/q59 weekly
        pivot shape; R attributes are constant per join-key run, so the
        case splits into the outer re-aggregation) — pre-aggregate L by
        (join keys + L group columns), join the FEW partial rows, and
        re-fold. Adaptive: bails when the partial grouping would not
        actually shrink L (measured, not guessed), in which case the
        normal fused path re-executes the (cheap, cached) L side."""
        from hyperspace_tpu.ops.aggregate import aggregate_table
        from hyperspace_tpu.plan.expr import Case, Lit
        from hyperspace_tpu.plan.nodes import AggSpec

        child = plan.child
        if not isinstance(child, Join) or child.how != "inner" or child.condition is not None:
            return None
        if isinstance(child.left, _TableLeaf) or isinstance(child.right, _TableLeaf):
            return None  # already pushed (recursion guard)
        lnames = {n.lower() for n in child.left.schema.names}
        rnames = {n.lower() for n in child.right.schema.names}
        g_l = [c for c in plan.group_by if c.lower() in lnames]
        g_r = [c for c in plan.group_by if c.lower() not in lnames]
        if any(c.lower() not in rnames for c in g_r):
            return None

        partial_specs: list[AggSpec] = []
        outer_specs: list[AggSpec] = []
        mean_parts: dict[str, tuple[str, str]] = {}  # alias -> (sum, cnt) temp names
        count_aliases: list[str] = []
        uses_r = bool(g_r)
        for i, a in enumerate(plan.aggs):
            refs = {r.lower() for r in a.references()}
            if a.fn == "count" and a.expr is None:
                partial_specs.append(AggSpec("count", None, f"__pp{i}"))
                outer_specs.append(AggSpec("sum", Col(f"__pp{i}"), a.alias))
                count_aliases.append(a.alias)
                continue
            if a.fn in ("sum", "count", "min", "max") and refs and refs <= lnames:
                partial_specs.append(AggSpec(a.fn, a.expr, f"__pp{i}"))
                fn2 = "sum" if a.fn in ("sum", "count") else a.fn
                outer_specs.append(AggSpec(fn2, Col(f"__pp{i}"), a.alias))
                if a.fn == "count":
                    count_aliases.append(a.alias)
                continue
            if a.fn == "mean" and refs and refs <= lnames:
                partial_specs.append(AggSpec("sum", a.expr, f"__pp{i}s"))
                partial_specs.append(AggSpec("count", a.expr, f"__pp{i}c"))
                outer_specs.append(AggSpec("sum", Col(f"__pp{i}s"), f"__po{i}s"))
                outer_specs.append(AggSpec("sum", Col(f"__pp{i}c"), f"__po{i}c"))
                mean_parts[a.alias] = (f"__po{i}s", f"__po{i}c")
                continue
            if (
                a.fn == "sum"
                and isinstance(a.expr, Case)
                and len(a.expr.branches) == 1
                and isinstance(a.expr.default, Lit)
                and a.expr.default.value in (0, 0.0)
            ):
                cond, val = a.expr.branches[0]
                crefs = {r.lower() for r in cond.references()}
                vrefs = {r.lower() for r in val.references()}
                if crefs and crefs <= rnames and vrefs <= lnames:
                    uses_r = True
                    partial_specs.append(AggSpec("sum", val, f"__pp{i}"))
                    from hyperspace_tpu.plan.expr import when as _when

                    outer_specs.append(
                        AggSpec("sum", _when(cond, Col(f"__pp{i}")).otherwise(0.0), a.alias)
                    )
                    continue
            return None
        if not uses_r:
            # The aggregate never needs R beyond the join's filtering
            # effect — the fused path already handles that shape better.
            return None

        pkeys: list[str] = list(child.left_on)
        pk_low = {c.lower() for c in pkeys}
        for c in g_l:
            if c.lower() not in pk_low:
                pkeys.append(c)
                pk_low.add(c.lower())

        probe_at = len(self._cur_phys.children) if self._cur_phys is not None else 0
        lt = self._execute(child.left)
        gid, k, rep = _group_ids_cached(lt, pkeys)
        if k > max(64, lt.num_rows // 8):
            # Less than ~8x shrink: the extra factorize + re-fold beats
            # nothing the fused path doesn't already do better. When the
            # left side is a deep subtree, it is already MATERIALIZED —
            # hand back a plan with it spliced in so nothing below
            # re-executes it. An index-aligned scan side stays a PLAN:
            # splicing would knock it off the zero-exchange aligned path
            # (and its DPP pruning), which beats the re-execution it
            # avoids (the scan is cache-served anyway).
            if self._aligned_side(child.left) is not None:
                # The aligned join reads the side again itself: the probe
                # leaves the operator tree and stays as evidence here.
                if self._cur_phys is not None:
                    del self._cur_phys.children[probe_at:]
                self._phys(pushdown_probe_rows=lt.num_rows, pushdown_probe_groups=k)
                return None
            return Aggregate(
                Join(_TableLeaf(lt), child.right, child.left_on, child.right_on,
                     child.how, condition=child.condition),
                list(plan.group_by),
                list(plan.aggs),
            )

        from hyperspace_tpu.plan.nodes import Aggregate as _Agg

        pschema = _Agg(_TableLeaf(lt), pkeys, partial_specs).schema
        venue = self._venue("agg")
        partial = aggregate_table(
            lt, pkeys, partial_specs, pschema, venue=venue, groups=(gid, k, rep),
        )
        self._phys(
            "PartialAggPushdown",
            partial_rows=partial.num_rows,
            input_rows=lt.num_rows,
            keys=pkeys,
        )
        outer_plan: LogicalPlan = _Agg(
            Join(_TableLeaf(partial), child.right, child.left_on, child.right_on, "inner"),
            list(plan.group_by),
            outer_specs,
        )
        out = self._execute(outer_plan)
        # Re-shape to the original output: means recompose from their
        # sum/count partials (NULL when no valid input), counts restore
        # SQL's never-NULL zero, columns return in declared order.
        cols: dict[str, np.ndarray] = {}
        dicts: dict[str, np.ndarray] = {}
        validity: dict[str, np.ndarray] = {}
        for f in plan.schema.fields:
            low = f.name.lower()
            if low in {c.lower() for c in plan.group_by}:
                _copy_field(f, out, f.name, cols, dicts, validity)
                continue
            if f.name in mean_parts or low in {a.lower() for a in mean_parts}:
                s_name, c_name = mean_parts[f.name]
                s = out.column(s_name).astype(np.float64)
                c = out.column(c_name).astype(np.float64)
                with np.errstate(invalid="ignore", divide="ignore"):
                    cols[f.name] = np.where(c > 0, s / np.maximum(c, 1), 0.0)
                if (c == 0).any():
                    validity[f.name] = c > 0
                continue
            _copy_field(f, out, f.name, cols, dicts, validity)
            if f.name in count_aliases:
                v = validity.pop(f.name, None)
                if v is not None:
                    cols[f.name] = np.where(v, cols[f.name], 0)
        return ColumnTable(plan.schema, cols, dicts, validity)

    def _distinct_aggregate(self, plan: "Aggregate", dcols: list[str]) -> ColumnTable:
        """General distinct expansion (the Spark planner's Expand analog
        for multi-distinct aggregates, q38/q87 shapes): execute the child
        ONCE, factorize the group keys ONCE, run the non-distinct specs
        as a normal segment reduce sharing that factorization, and count
        each distinct column by factorizing (group keys, column) pairs —
        the representative row of each pair maps back to its outer group,
        so a bincount over pair representatives IS the distinct count.
        No join, no per-spec re-execution; mean shares freely."""
        from hyperspace_tpu.ops.aggregate import aggregate_table, group_ids
        from hyperspace_tpu.schema import Schema

        ct = self._execute(plan.child)
        venue = self._venue("agg")
        gid, k, rep = _group_ids_cached(ct, plan.group_by)
        self._phys(
            "DistinctExpandAggregate",
            distinct_cols=dcols,
            groups=len(plan.group_by),
            venue=venue,
        )
        out_schema = plan.schema
        if k == 0 or (ct.num_rows == 0 and plan.group_by):
            return ColumnTable.empty(out_schema)
        regular = [a for a in plan.aggs if a.fn != "count_distinct"]
        reg_fields = [out_schema.field(c) for c in plan.group_by]
        reg_fields += [out_schema.field(a.alias) for a in regular]
        base = aggregate_table(
            ct, plan.group_by, regular, Schema(tuple(reg_fields)),
            venue=venue, groups=(gid, k, rep),
        )
        cols = dict(base.columns)
        dicts = dict(base.dictionaries)
        validity = dict(base.validity)
        pair_counts: dict[str, np.ndarray] = {}
        for d in dcols:
            pgid, pk, prep = group_ids(ct, [*plan.group_by, d])
            del pgid, pk
            outer = gid[prep]
            vd = ct.valid_mask(d)
            if vd is not None:
                outer = outer[vd[prep]]  # SQL: distinct counts exclude NULL
            pair_counts[d] = np.bincount(outer, minlength=k).astype(np.int64)
        for a in plan.aggs:
            if a.fn == "count_distinct":
                cols[out_schema.field(a.alias).name] = pair_counts[a.expr.name.lower()]
        return ColumnTable(out_schema, cols, dicts, validity)

    def _grouping_sets_aggregate(self, plan: "Aggregate") -> ColumnTable:
        """ROLLUP / CUBE / GROUPING SETS as ONE finest-grain aggregate
        (which gets the fused Aggregate(Join) path when it applies) plus
        cheap re-aggregations of its partials per set — the two-phase
        machinery the count_distinct desugar introduced, generalized.
        The union null-extends group columns a set aggregates away;
        grouping() flags tell data NULLs from subtotal NULLs."""
        from hyperspace_tpu.ops.aggregate import aggregate_table
        from hyperspace_tpu.plan.expr import Col
        from hyperspace_tpu.plan.nodes import AggSpec
        from hyperspace_tpu.schema import Field, Schema

        if any(a.fn == "count_distinct" for a in plan.aggs):
            # Distinct counts do not compose from partials (the same value
            # in two finest groups of one coarser group would double
            # count), so the re-fold below cannot serve them: materialize
            # the child ONCE and aggregate each set directly over it —
            # the plain-aggregate path owns the distinct machinery.
            return self._grouping_sets_distinct(plan)

        # Phase 1: finest grain over the full group_by, means split into
        # sum+count partials so coarser sets can recompose them exactly.
        base_specs: list[AggSpec] = []
        for a in plan.aggs:
            if a.fn == "grouping":
                continue
            if a.fn == "mean":
                base_specs.append(AggSpec("sum", a.expr, f"__gs_sum_{a.alias}"))
                base_specs.append(AggSpec("count", a.expr, f"__gs_cnt_{a.alias}"))
            else:
                base_specs.append(AggSpec(a.fn, a.expr, a.alias))
        base = Aggregate(plan.child, plan.group_by, base_specs)
        bt = self._execute(base)

        out_schema = plan.schema
        venue = self._venue("agg")
        self._phys(
            "GroupingSetsReaggregate",
            sets=[list(s) for s in plan.grouping_sets],
            venue=venue,
        )

        def refold(a: AggSpec) -> list[AggSpec]:
            """Phase-2 spec(s) re-aggregating a phase-1 partial column."""
            if a.fn == "mean":
                return [
                    AggSpec("sum", Col(f"__gs_sum_{a.alias}"), f"__gs_sum_{a.alias}"),
                    AggSpec("sum", Col(f"__gs_cnt_{a.alias}"), f"__gs_cnt_{a.alias}"),
                ]
            fn2 = "sum" if a.fn in ("sum", "count") else a.fn
            return [AggSpec(fn2, Col(a.alias), a.alias)]

        # ROLLUP's sets are prefixes of group_by: the mixed-radix combined
        # key of a prefix is a monotone quotient of the full key's, so ONE
        # factorize+sort of the finest key serves EVERY level (q67's
        # 9-level refold was 9 independent factorizations before this).
        prefix_groups = self._prefix_chain_groups(bt, plan.group_by, plan.grouping_sets)

        parts: list[ColumnTable] = []
        for s in plan.grouping_sets:
            specs2 = [sp for a in plan.aggs if a.fn != "grouping" for sp in refold(a)]
            fields = [bt.schema.field(c) for c in s]
            for sp in specs2:
                src = bt.schema.field(sp.expr.name)
                dtype = src.dtype if sp.fn in ("min", "max") else (
                    "int64" if src.dtype in ("int32", "int64", "bool", "date") else "float64"
                )
                fields.append(Field(sp.alias, dtype))
            sub = aggregate_table(
                bt, list(s), specs2, Schema(tuple(fields)), venue=venue,
                groups=None if prefix_groups is None else prefix_groups.get(len(s)),
            )

            def agg_col(f, spec, cols, dicts, validity, sub=sub):
                if spec.fn == "mean":
                    ssum = sub.column(f"__gs_sum_{spec.alias}").astype(np.float64)
                    scnt = sub.column(f"__gs_cnt_{spec.alias}").astype(np.float64)
                    sv = sub.valid_mask(f"__gs_sum_{spec.alias}")
                    with np.errstate(invalid="ignore", divide="ignore"):
                        cols[f.name] = np.where(scnt > 0, ssum / np.maximum(scnt, 1), 0.0)
                    if sv is not None or (scnt == 0).any():
                        ok = scnt > 0
                        validity[f.name] = ok if sv is None else (ok & sv)
                elif spec.fn == "count":
                    # COUNT is never NULL: zero-row re-folds yield a NULL
                    # sum partial — restore 0 (same rule as the
                    # count_distinct desugar's outer sum).
                    v = sub.valid_mask(spec.alias)
                    c = sub.column(spec.alias)
                    cols[f.name] = np.where(v, c, 0) if v is not None else c
                else:
                    _copy_field(f, sub, spec.alias, cols, dicts, validity)

            parts.append(self._gs_assemble(plan, out_schema, sub, s, bt, agg_col))
        return ColumnTable.concat(parts)

    @staticmethod
    def _prefix_chain_groups(bt: ColumnTable, group_by, sets):
        """Per-set (gid, K, rep) factorizations for prefix-chain grouping
        sets (ROLLUP), all derived from ONE sort. The finest combined key
        is mixed-radix over the per-column codes; a length-L prefix's key
        is its quotient by the trailing radix product — monotone, so the
        full-key sort order is already sorted for every prefix and each
        level needs only an O(n) segment mask. None when the sets are not
        a prefix chain or the radix product overflows (caller falls back
        to per-set factorization)."""
        from hyperspace_tpu.ops.aggregate import _column_codes

        gb_low = [c.lower() for c in group_by]
        lens = set()
        for s in sets:
            if [c.lower() for c in s] != gb_low[: len(s)]:
                return None
            lens.add(len(s))
        if not group_by or bt.num_rows == 0:
            return None
        codes = []
        cards = []
        for c in group_by:
            cd, card = _column_codes(bt, c)
            codes.append(cd)
            cards.append(np.int64(card))
        total = np.int64(1)
        for card in cards:
            if int(total) * int(card) >= np.iinfo(np.int64).max:
                return None
            total *= card
        combined = codes[0].astype(np.int64, copy=True)
        for cd, card in zip(codes[1:], cards[1:]):
            combined *= card
            combined += cd
        # Trailing radix products: suffix[L] divides the full key down to
        # the length-L prefix's key.
        suffix = [np.int64(1)] * (len(group_by) + 1)
        for i in range(len(group_by) - 1, -1, -1):
            suffix[i] = suffix[i + 1] * cards[i]
        perm = np.argsort(combined, kind="stable")
        sc = combined[perm]
        n = len(sc)
        out = {}
        for length in sorted(lens):
            if length == 0:
                out[0] = (np.zeros(n, np.int64), 1, np.zeros(1, np.int64))
                continue
            q = sc // suffix[length]
            newseg = np.empty(n, dtype=bool)
            newseg[0] = True
            newseg[1:] = q[1:] != q[:-1]
            seg = np.cumsum(newseg) - 1
            gid = np.empty(n, dtype=np.int64)
            gid[perm] = seg
            out[length] = (gid, int(seg[-1]) + 1, perm[np.flatnonzero(newseg)])
        return out

    def _gs_assemble(
        self, plan: "Aggregate", out_schema, sub: ColumnTable, s, dict_src, agg_col
    ) -> ColumnTable:
        """One grouping set's output part, shared by the re-fold and
        distinct grouping-set paths: group columns in `s` copy through,
        group columns aggregated away null-extend, grouping() flags
        derive from set membership, and `agg_col(field, spec, cols,
        dicts, validity)` fills the aggregate columns."""
        in_set = {c.lower() for c in s}
        gb_low = {c.lower() for c in plan.group_by}
        cols: dict[str, np.ndarray] = {}
        dicts: dict[str, np.ndarray] = {}
        validity: dict[str, np.ndarray] = {}
        nrows = sub.num_rows
        for f in out_schema.fields:
            low = f.name.lower()
            if low in gb_low:
                if low in in_set:
                    _copy_field(f, sub, f.name, cols, dicts, validity)
                else:
                    _null_field(
                        f, nrows, dict_src if f.is_string else None, cols, dicts, validity
                    )
                continue
            spec = next(a for a in plan.aggs if a.alias.lower() == low)
            if spec.fn == "grouping":
                cols[f.name] = np.full(
                    nrows, 0 if spec.expr.name.lower() in in_set else 1, np.int64
                )
            else:
                agg_col(f, spec, cols, dicts, validity)
        return ColumnTable(out_schema, cols, dicts, validity)

    def _grouping_sets_distinct(self, plan: "Aggregate") -> ColumnTable:
        """GROUPING SETS with count_distinct aggregates (q14/q18 shapes):
        the child materializes once, then every set aggregates it
        directly — per-set work instead of the partial re-fold, because
        distinct counts cannot be composed from finer partials."""

        ct = self._execute(plan.child)
        leaf = _TableLeaf(ct)
        out_schema = plan.schema
        self._phys(
            "GroupingSetsDistinct",
            sets=[list(s) for s in plan.grouping_sets],
            distinct_cols=sorted(
                a.expr.name.lower() for a in plan.aggs if a.fn == "count_distinct"
            ),
        )
        parts: list[ColumnTable] = []
        for s in plan.grouping_sets:
            specs = [a for a in plan.aggs if a.fn != "grouping"]
            sub = self._execute(Aggregate(leaf, list(s), specs))

            def agg_col(f, spec, cols, dicts, validity, sub=sub):
                _copy_field(f, sub, spec.alias, cols, dicts, validity)

            parts.append(self._gs_assemble(plan, out_schema, sub, s, ct, agg_col))
        return ColumnTable.concat(parts)

