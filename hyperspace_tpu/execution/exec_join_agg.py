"""Fused Aggregate(Join): the run-prefix device kernel and the host
merge+accumulate venue that never materialize the joined pairs
(Executor mixin)."""

from __future__ import annotations


import numpy as np

from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.plan.nodes import Aggregate, Join, Project

from hyperspace_tpu.execution.exec_common import (
    _RunExtremum,
    _agg_channels_cached,
    _bucket_sorted_codes,
    _factorize_keys_cached,
    _group_ids_cached,
    _pad_bucket_major_cached,
    _stack_cached,
)


class FusedJoinAggMixin:
    def _try_fused_join_aggregate(self, plan: Aggregate) -> ColumnTable | None:
        """Aggregate(Join) without materializing the joined pairs
        (ops/join_agg.py). Applies when every aggregate is
        sum/count/mean/min/max over a single side's numeric expression
        and the grouping columns (if any) come from one side; cross-side
        expressions fall back to the materialized join. min/max run as
        run-extremum channels on BOTH venues (all equal-key secondary
        rows are one contiguous run of the sorted side, and extrema are
        multiplicity-independent): the host C++ pass walks runs directly;
        the device kernel takes the segmented-prefix-scan value at each
        run end and folds groups with segment_min/max."""
        from hyperspace_tpu.ops.aggregate import agg_input, finalize_agg_values, group_ids

        child = plan.child
        if isinstance(child, Project):
            child = child.child
        if not isinstance(child, Join) or child.how != "inner" or child.condition is not None:
            return None
        join = child
        lnames = {n.lower() for n in join.left.schema.names}
        rnames = {n.lower() for n in join.right.schema.names}

        def side_of(cols) -> str | None:
            cl = {c.lower() for c in cols}
            if cl and cl <= lnames:
                return "left"
            if cl and cl <= rnames:
                return "right"
            return None

        gside = None
        if plan.group_by:
            gside = side_of(plan.group_by)
            if gside is None:
                return None
        from hyperspace_tpu.plan.expr import Case

        spec_sides: list[str | None] = []
        for a in plan.aggs:
            if a.fn not in ("sum", "count", "mean", "min", "max"):
                return None
            if a.expr is None:
                spec_sides.append(None)  # count(*)
                continue
            refs = a.references()
            # Constant expressions (sum(lit(2))) and cross-side expressions
            # have no single owning side — use the materialized join.
            s = side_of(refs)
            if s is None:
                return None
            sch = join.left.schema if s == "left" else join.right.schema
            if any(sch.field(r).is_vector for r in refs):
                return None
            # Case conditions handle strings via the predicate machinery;
            # any other string reference cannot feed a numeric channel.
            if not isinstance(a.expr, Case) and any(sch.field(r).is_string for r in refs):
                return None
            spec_sides.append(s)
        primary = gside or "left"

        lside, rside, _, _ = self._join_sides(join)
        data = {"left": lside, "right": rside}
        self.stats["agg_path"] = "fused-join-agg"
        self.stats["num_buckets"] = len(data["left"].offsets) - 1

        lkeys = [data["left"].table.schema.field(c).name for c in join.left_on]
        rkeys = [data["right"].table.schema.field(c).name for c in join.right_on]
        lc0, rc0 = _factorize_keys_cached(
            data["left"].table, data["right"].table, lkeys, rkeys,
            null_safe=join.null_safe,
        )
        codes = {}
        perms = {}
        regroup_venue = self._venue("sort")
        codes["left"], perms["left"] = _bucket_sorted_codes(lc0, data["left"], venue=regroup_venue)
        codes["right"], perms["right"] = _bucket_sorted_codes(rc0, data["right"], venue=regroup_venue)
        secondary = "right" if primary == "left" else "left"

        # Group ids on the primary table (original row order; memoized
        # for stable index-backed sides).
        gid_orig, k, first_idx = _group_ids_cached(data[primary].table, plan.group_by)
        if k == 0:  # empty primary side
            if plan.group_by:
                return ColumnTable.empty(plan.schema)
            k, gid_orig, first_idx = 1, np.zeros(0, np.int64), np.zeros(0, np.int64)

        def spec_input(side: str, spec):
            """(masked values, indicator) per original row of `side` with
            the plain aggregate path's null semantics (ops/aggregate);
            memoized per (expression, input identity) for stable sides."""
            return _agg_channels_cached(data[side].table, spec)

        host_res = None
        if (
            self._venue("join") == "host"
            and codes[primary].dtype == np.int32
            and codes[secondary].dtype == np.int32
        ):
            host_res = self._host_fused_channels(
                plan, data, codes, perms, primary, secondary, spec_sides,
                gid_orig, k, spec_input,
            )
        if host_res is not None:
            self.stats["join_kernel"] = "host-native-merge-accumulate"
            out, spec_layout = host_res
        else:
            self.stats["join_kernel"] = "device-run-prefix"
            # Group keys that hold the primary side's join keys put every
            # group in one bucket.
            pkeys = join.left_on if primary == "left" else join.right_on
            bucket_local = {c.lower() for c in pkeys} <= {c.lower() for c in plan.group_by}
            out, spec_layout = self._device_fused_channels(
                plan, data, codes, perms, primary, secondary, spec_sides,
                gid_orig, k, spec_input, fused=self._fused_kernels(),
                bucket_local=bucket_local,
            )
        star = out[0]

        keep = star > 0 if plan.group_by else np.ones(k, bool)
        out_schema = plan.schema
        cols: dict[str, np.ndarray] = {}
        dicts: dict[str, np.ndarray] = {}
        validity: dict[str, np.ndarray] = {}
        ptable = data[primary].table
        # first_idx may be empty when the primary side has no rows but a
        # global (no group_by) aggregate still emits its one k=1 row.
        kept_first = first_idx[keep[: len(first_idx)]]
        for c in plan.group_by:
            f = ptable.schema.field(c)
            out_f = out_schema.field(c)
            cols[out_f.name] = ptable.columns[f.name][kept_first]
            if f.name in ptable.dictionaries:
                dicts[out_f.name] = ptable.dictionaries[f.name]
            gv = ptable.valid_mask(c)
            if gv is not None:
                validity[out_f.name] = gv[kept_first]
        for spec, (vi, ci) in zip(plan.aggs, spec_layout):
            out_f = out_schema.field(spec.alias)
            cnt = out[ci][keep]
            if spec.fn == "count":
                cols[out_f.name] = cnt.astype(np.int64)
                continue
            val = out[vi][keep]
            if spec.fn == "mean":
                with np.errstate(invalid="ignore", divide="ignore"):
                    val = val / cnt
            empty = cnt == 0
            cols[out_f.name] = finalize_agg_values(val, empty, out_f.device_dtype)
            if empty.any():
                validity[out_f.name] = ~empty
        return ColumnTable(out_schema, cols, dicts, validity)

    def _device_fused_channels(
        self, plan, data, codes, perms, primary, secondary, spec_sides, gid_orig, k,
        spec_input, fused: str = "off", bucket_local: bool = False,
    ):
        """Device venue: the run-prefix kernel over bucket-major padded
        channels (ops/join_agg.py). Pads, the channel stacks, and the
        uploads all route through the identity caches, so repeat queries
        over a stable index version serve from HBM. With `fused` = auto
        the pad widths round up to the 128-lane tile so the Pallas
        run-bounds kernel can engage (extra pads are sentinels/dead
        rows — results are unchanged by construction), and further to a
        power of two: a filtered side whose largest bucket moves by a few
        rows from one query's literals to the next keeps one compiled
        program (a finer ladder still splits such a side at a step)."""
        from hyperspace_tpu.execution import device_cache as dcache
        from hyperspace_tpu.ops.join_agg import fused_join_aggregate

        def width_of(offsets) -> int | None:
            if fused != "auto":
                return None  # natural Lmax width
            counts = np.diff(offsets)
            lm = max(int(counts.max()) if counts.size else 1, 128)
            return 1 << (lm - 1).bit_length()

        pk = _pad_bucket_major_cached(
            codes[primary], data[primary].offsets, width=width_of(data[primary].offsets)
        )
        sk = _pad_bucket_major_cached(
            codes[secondary], data[secondary].offsets, width=width_of(data[secondary].offsets)
        )
        b, lp = pk.shape
        ls = sk.shape[1]

        def pad_rows(side: str, vals: np.ndarray, fill=0.0) -> np.ndarray:
            """Per-orig-row values of `side` → bucket-sorted padded [B, L]."""
            v = np.asarray(vals, np.float64)
            if perms[side] is not None:
                v = v[perms[side]]
            width = lp if side == primary else ls
            return _pad_bucket_major_cached(v, data[side].offsets, fill=fill, width=width)

        # pad_rows reorders by perm internally — pass the ORIGINAL-order gid;
        # pads carry group id k (the dead segment).
        def build_gid():
            return pad_rows(primary, gid_orig, fill=float(k)).astype(np.int32)

        if dcache.is_stable(gid_orig) and perms[primary] is None:
            # Cacheable only when NO per-join permutation applies: the
            # perm depends on the join keys, which this key does not
            # carry — a different-keyed join sharing gid_orig must not
            # reuse the other layout's pad.
            gid_pad = dcache.derived(
                ("gidpad", id(gid_orig), data[primary].offsets.tobytes(), k, lp),
                (gid_orig,),
                build_gid,
            )
        else:
            gid_pad = build_gid()

        channels: list[tuple] = [("star",)]
        p_arrays: list[np.ndarray] = []
        s_arrays: list[np.ndarray] = []

        def add_channel(side: str, padded: np.ndarray, fn: str | None = None) -> int:
            base = "p" if side == primary else "s"
            kind = base + fn if fn in ("min", "max") else base
            if side == primary:
                p_arrays.append(padded)
                channels.append((kind, len(p_arrays) - 1))
            else:
                s_arrays.append(padded)
                channels.append((kind, len(s_arrays) - 1))
            return len(channels) - 1

        def mm_values(vals: np.ndarray, ind: np.ndarray, fn: str) -> np.ndarray:
            """Extremum channel input: nulls (and later pads) carry the
            ±inf identity instead of the sum channels' zero. Identity-
            cached so the derived pad/upload caches stay warm for stable
            sides."""
            ident = np.inf if fn == "min" else -np.inf

            def build():
                out = np.where(ind > 0, vals, ident)
                dcache.freeze(out)
                return out

            if dcache.is_stable(vals) and dcache.is_stable(ind):
                return dcache.derived(
                    ("mmvals", id(vals), id(ind), fn), (vals, ind), build
                )
            return np.where(ind > 0, vals, ident)

        spec_layout: list[tuple[int | None, int]] = []  # (value ch, count ch; 0=star)
        for spec, s in zip(plan.aggs, spec_sides):
            if s is None:  # count(*)
                spec_layout.append((None, 0))
                continue
            vals, ind = spec_input(s, spec)
            vi = None
            if spec.fn in ("sum", "mean"):
                vi = add_channel(s, pad_rows(s, vals))
            elif spec.fn in ("min", "max"):
                ident = np.inf if spec.fn == "min" else -np.inf
                vi = add_channel(
                    s, pad_rows(s, mm_values(vals, ind, spec.fn), fill=ident), spec.fn
                )
            ci = add_channel(s, pad_rows(s, ind))
            spec_layout.append((vi, ci))

        pvals = _stack_cached(p_arrays, (0, b, lp))
        svals = _stack_cached(s_arrays, (0, b, ls))
        out = fused_join_aggregate(
            pk, sk, pvals, svals, gid_pad, k, tuple(channels), fused=fused,
            bucket_local=bucket_local,
        )
        return out, spec_layout

    def _host_fused_channels(
        self, plan, data, codes, perms, primary, secondary, spec_sides, gid_orig, k, spec_input
    ):
        """Host venue: one C++ merge+accumulate pass computes per-primary-
        row channel sums and match counts (no pair materialization), then
        per-group bincounts produce the same [K] channel layout the device
        kernel emits. Returns None when the native library is missing."""
        from hyperspace_tpu import native

        if not native.available():
            return None
        tbl_s = data[secondary].table
        sec_arrays: list[np.ndarray] = []  # SORTED secondary order
        parts: list[tuple] = []

        def sec_sorted(a: np.ndarray) -> np.ndarray:
            return a[perms[secondary]] if perms[secondary] is not None else a

        for spec, s in zip(plan.aggs, spec_sides):
            if s is None:
                parts.append(("star",))
                continue
            vals, ind = spec_input(s, spec)
            if spec.fn in ("min", "max"):
                # Extremum channels bypass the sum accumulator: per-KEY
                # run extrema (secondary) / matched-row extrema (primary).
                parts.append(("mm", spec.fn, s, vals, ind))
            elif s == secondary:
                vi = None
                if spec.fn in ("sum", "mean"):
                    sec_arrays.append(sec_sorted(vals))
                    vi = len(sec_arrays) - 1
                sec_arrays.append(sec_sorted(ind))
                parts.append(("sec", vi, len(sec_arrays) - 1))
            else:
                parts.append(("pri", vals if spec.fn in ("sum", "mean") else None, ind))

        rvals = _stack_cached(sec_arrays, (0, tbl_s.num_rows))
        res = native.merge_join_accumulate(
            codes[primary], data[primary].offsets,
            codes[secondary], data[secondary].offsets, rvals,
        )
        if res is None:
            return None
        acc_sorted, match_sorted = res
        n_l = data[primary].table.num_rows
        pperm = perms[primary]
        if pperm is not None:
            matches = np.empty(n_l)
            matches[pperm] = match_sorted
            acc = np.empty_like(acc_sorted)
            acc[:, pperm] = acc_sorted
        else:
            matches, acc = match_sorted, acc_sorted

        def greduce(w: np.ndarray) -> np.ndarray:
            if n_l == 0:
                return np.zeros(k)
            return np.bincount(gid_orig, weights=w, minlength=k)

        mm_rows = None
        if any(p[0] == "mm" for p in parts):
            mm_rows = _RunExtremum(
                codes[primary], data[primary].offsets, pperm,
                codes[secondary], data[secondary].offsets, perms[secondary],
                matches, n_l,
            )

        out: list[np.ndarray] = [greduce(matches)]  # star = pairs per group
        spec_layout: list[tuple[int | None, int]] = []
        for part in parts:
            if part[0] == "star":
                spec_layout.append((None, 0))
            elif part[0] == "sec":
                _, vi, ci = part
                v_idx = None
                if vi is not None:
                    out.append(greduce(acc[vi]))
                    v_idx = len(out) - 1
                out.append(greduce(acc[ci]))
                spec_layout.append((v_idx, len(out) - 1))
            elif part[0] == "mm":
                from hyperspace_tpu.ops.aggregate import aggregate_arrays_host

                _, fn, s, vals, ind = part
                row_ext, row_valid = mm_rows.per_primary_row(fn, s, secondary, vals, ind)
                res, cnt = aggregate_arrays_host([(row_ext, row_valid, fn)], gid_orig, k)
                out.append(res[0])
                out.append(cnt[0])
                spec_layout.append((len(out) - 2, len(out) - 1))
            else:
                _, vals, ind = part
                v_idx = None
                if vals is not None:
                    out.append(greduce(vals * matches))
                    v_idx = len(out) - 1
                out.append(greduce(ind * matches))
                spec_layout.append((v_idx, len(out) - 1))
        return out, spec_layout

