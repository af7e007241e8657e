"""Executor support: plan leaves, side descriptors, key-bound analysis,
identity caches, and the shared row-materialization helpers. Split out of
executor.py (round 5); the executor mixins import from here.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.execution import io as hio
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.ops.filter import eval_predicate_mask
from hyperspace_tpu.ops import join as join_ops
from hyperspace_tpu.plan.expr import BinOp, Col, Expr, Lit, split_conjuncts
from hyperspace_tpu.plan.nodes import Aggregate, Join, LogicalPlan, Scan



@dataclasses.dataclass
class _TableLeaf(LogicalPlan):
    """Executor-internal leaf wrapping an already-materialized table
    (partial-aggregation pushdown splices one under a Join). Never
    serialized; never seen by the rules."""

    table: ColumnTable

    @property
    def schema(self):
        return self.table.schema

    def children(self) -> list[LogicalPlan]:
        return []

    def to_json(self):
        raise HyperspaceError("_TableLeaf is executor-internal")


@dataclasses.dataclass
class AlignedSide:
    scan: Scan
    project: list[str] | None  # columns to keep after the join gather
    # Hybrid scan: unbucketed delta scans whose rows are bucketized
    # on the fly and merged into the index buckets before the SMJ.
    # Any number of deltas is accepted (a Union of the index scan with
    # several appended-file scans, not just the canonical two-input
    # shape the rewrite rule emits today).
    deltas: tuple[Scan, ...] = ()
    # Side-local filter (JoinIndexRule keeps linear sides with filters):
    # applied per bucket BEFORE the merge, preserving bucket grouping and
    # within-bucket sort order (a filtered subsequence stays sorted).
    predicate: Expr | None = None


@dataclasses.dataclass
class SideData:
    """One join side in concatenated bucket-grouped layout: rows of bucket
    b occupy [offsets[b], offsets[b+1])."""

    table: ColumnTable
    offsets: np.ndarray  # [B+1] int64
    sorted_within: bool  # buckets key-sorted (index files are)?
    # Fields defining the bucket hash domain (the dtypes the row hash was
    # computed in) — two bucketings pair only when these are compatible.
    hash_fields: tuple | None = None


def _hash_fields_compatible(a, b) -> bool:
    """Equal key values bucket identically under both domains."""
    if a is None or b is None or len(a) != len(b):
        return False
    for fa, fb in zip(a, b):
        if fa.is_string != fb.is_string:
            return False
        if not fa.is_string and np.dtype(fa.device_dtype) != np.dtype(fb.device_dtype):
            return False
    return True


def _filter_side(side: SideData, predicate, mesh, venue: str = "device") -> SideData:
    """Apply a side-local filter to bucket-grouped data, recomputing the
    bucket offsets over the surviving rows (grouping and within-bucket
    order are preserved — a filtered subsequence stays sorted)."""
    t = side.table
    if t.num_rows == 0:
        return side
    mask = eval_predicate_mask(t, predicate, mesh=mesh, venue=venue)
    counts = np.diff(side.offsets)
    bucket_of = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    new_counts = np.bincount(bucket_of[mask], minlength=len(counts))
    offsets = np.concatenate([[0], np.cumsum(new_counts)]).astype(np.int64)
    return SideData(t.filter_mask(mask), offsets, side.sorted_within)


def _bucket_sorted_codes(codes: np.ndarray, side: SideData, venue: str = "host"):
    """Ensure codes are non-decreasing within each bucket. Returns
    (sorted codes, perm) where perm maps sorted positions back to the
    side's row order (None when already sorted — the index-file case,
    verified with one vectorized pass, memoized for stable codes).
    `venue` picks where the re-grouping permutation is computed: "device"
    fuses the bucket lane and the code lanes into ONE lax.sort
    (ops/sortkeys.device_lanes_perm) instead of the host np.lexsort
    pass; both produce the identical stable permutation, so the memo
    cache never keys on the venue."""
    from hyperspace_tpu.execution import device_cache as dc

    n = len(codes)
    if n == 0:
        return codes, None
    if side.sorted_within:

        def check() -> bool:
            if n > (1 << 25):
                # Index files are sorted by CONTRACT (the builder writes
                # them that way); at 33M+ rows the O(n) belt-and-braces
                # verification costs real seconds, so sample: the LAST
                # within-bucket adjacency of every bucket (end-2, end-1 —
                # the likely spot for a builder merge bug) plus 64k
                # random adjacencies still catches systematic violations.
                rng = np.random.default_rng(0)
                bounds = np.asarray(side.offsets)
                idx = rng.integers(0, n - 1, 65_536)
                ends = bounds[1:]
                tail_probes = ends[ends >= 2] - 2  # pair (end-2, end-1)
                probes = np.concatenate([idx, tail_probes])
                probes = probes[probes + 1 < n]
                bucket_of_probe = np.searchsorted(bounds, probes, side="right") - 1
                same_bucket = bucket_of_probe == (
                    np.searchsorted(bounds, probes + 1, side="right") - 1
                )
                bad = (codes[probes + 1] < codes[probes]) & same_bucket
                return not bool(bad.any())
            counts0 = np.diff(side.offsets)
            b_of = np.repeat(np.arange(len(counts0), dtype=np.int64), counts0)
            d = np.diff(codes)
            return not np.any(d[b_of[:-1] == b_of[1:]] < 0)

        if dc.is_stable(codes):
            ok = dc.HOST_DERIVED.get_or_build(
                ("sortck", id(codes), side.offsets.tobytes()),
                (codes,),
                lambda: (check(), 1),
            )
        else:
            ok = check()
        if ok:
            return codes, None
    def build_sorted(cacheable: bool):
        counts = np.diff(side.offsets)
        bucket_of = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
        if venue == "device":
            from hyperspace_tpu.ops.sortkeys import device_lanes_perm, value_lanes

            lanes = value_lanes(bucket_of.astype(np.int32)) + value_lanes(codes)
            perm = device_lanes_perm(lanes).astype(np.int64)
        else:
            perm = np.lexsort((codes, bucket_of))  # stable; regroups identically
        sc = codes[perm]
        nbytes = sc.nbytes + perm.nbytes
        if cacheable and nbytes <= dc.HOST_DERIVED.budget // 4:
            # Freeze ONLY what the cache will actually keep (same rule as
            # the decoded-table cache): a frozen-but-uncached result would
            # masquerade as stable and pile dead downstream entries.
            sc, perm = dc.freeze(sc), dc.freeze(perm)
        return (sc, perm), nbytes

    if dc.is_stable(codes):
        # Stable (identity-cached) codes: memoize the sort itself, not
        # just the sortedness check — repeat queries over the same index
        # version skip the O(n log n) pass entirely, and the frozen
        # outputs keep the downstream pad/upload caches engaged.
        return dc.HOST_DERIVED.get_or_build(
            ("bsort", id(codes), side.offsets.tobytes()), (codes,),
            lambda: build_sorted(True),
        )
    return build_sorted(False)[0]


@dataclasses.dataclass
class KeyBounds:
    """Conjunct bounds on one column: lo/hi literal (None = unbounded) and
    whether each bound is strict (< / >) rather than inclusive."""

    lo: object = None
    lo_strict: bool = False
    hi: object = None
    hi_strict: bool = False


_FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}


def _conjunct_col_lit(conj) -> tuple[str, str, object] | None:
    """Destructure one conjunct as (column, op, literal), normalizing
    `lit op col` by flipping the comparison. NaN literals are rejected
    (they defeat ordered-bound reasoning: every comparison is False, but
    searchsorted treats NaN as largest). Returns None otherwise."""
    if not isinstance(conj, BinOp):
        return None
    op = conj.op
    if isinstance(conj.left, Col) and isinstance(conj.right, Lit):
        name, v = conj.left.name, conj.right.value
    elif isinstance(conj.right, Col) and isinstance(conj.left, Lit):
        name, v = conj.right.name, conj.left.value
        op = _FLIP.get(op, op)
    else:
        return None
    if v is None:
        return None
    if isinstance(v, (float, np.floating)) and np.isnan(v):
        return None
    return name, op, v


def _like_prefix(pattern: str) -> str | None:
    """The literal prefix of a prefix-shaped LIKE pattern ('PROMO%'), or
    None when the pattern isn't prefix-shaped."""
    if pattern.endswith("%") and len(pattern) > 1:
        body = pattern[:-1]
        if "%" not in body and "_" not in body:
            return body
    return None


def _prefix_upper(prefix: str) -> str | None:
    """Smallest string ABOVE every string with `prefix` (exclusive upper
    bound for prefix matching); None when the last char can't increment."""
    last = ord(prefix[-1])
    if last >= 0x10FFFF:
        return None
    return prefix[:-1] + chr(last + 1)


def _conjunct_bound_ops(conj, key: str) -> list[tuple[str, object]] | None:
    """One conjunct → literal (op, value) bounds it implies on `key`:
    plain comparisons pass through; IN gives its min/max envelope; a
    prefix LIKE gives [prefix, next-prefix). The residual filter mask
    still applies the exact predicate — bounds only need to be a valid
    superset."""
    from hyperspace_tpu.plan.expr import InList, Like

    if isinstance(conj, InList) and isinstance(conj.child, Col):
        if conj.child.name.lower() != key:
            return None
        vals = conj.values
        if any(isinstance(v, (float, np.floating)) and np.isnan(v) for v in vals):
            return None
        try:
            return [("ge", min(vals)), ("le", max(vals))]
        except TypeError:
            return None
    if isinstance(conj, Like) and isinstance(conj.child, Col):
        if conj.child.name.lower() != key:
            return None
        prefix = _like_prefix(conj.pattern)
        if prefix is None:
            if "%" not in conj.pattern and "_" not in conj.pattern:
                return [("eq", conj.pattern)]  # wildcard-free LIKE = equality
            return None
        out: list[tuple[str, object]] = [("ge", prefix)]
        upper = _prefix_upper(prefix)
        if upper is not None:
            out.append(("lt", upper))
        return out
    if isinstance(conj, BinOp) and conj.is_comparison:
        from hyperspace_tpu.ops.filter import _translate_date_part_cmp
        from hyperspace_tpu.plan.expr import DatePart

        l, r, op = conj.left, conj.right, conj.op
        if isinstance(r, DatePart) and isinstance(l, Lit):
            l, r, op = r, l, _FLIP.get(op, op)
        if isinstance(l, DatePart) and isinstance(r, Lit):
            # year(d) OP lit → the same day-range tree the filter layer
            # lowers to; recurse so the range feeds pruning too.
            t = _translate_date_part_cmp(op, l, r.value)
            if t is None:
                return None
            out: list[tuple[str, object]] = []
            for sub in split_conjuncts(t):
                pairs = _conjunct_bound_ops(sub, key)
                if pairs is None:
                    return None  # ne-shaped (an OR): not a conjunct bound
                out.extend(pairs)
            return out
    dec = _conjunct_col_lit(conj)
    if dec is None:
        return None
    name, op, v = dec
    if name.lower() != key or op not in ("eq", "lt", "le", "gt", "ge"):
        return None
    return [(op, v)]


def key_bounds(predicate: Expr, key: str) -> KeyBounds | None:
    """Extract literal comparison bounds on `key` from the predicate's
    conjuncts (key op lit / lit op key; eq pins both ends; IN gives its
    envelope; prefix LIKE gives a string range). Returns None when no
    conjunct bounds the column. Incomparable literal types are ignored
    (the residual filter mask still applies them exactly)."""
    key = key.lower()
    b = KeyBounds()
    found = False
    for conj in split_conjuncts(predicate):
        pairs = _conjunct_bound_ops(conj, key)
        if pairs is None:
            continue
        for op, v in pairs:
            try:
                if op in ("gt", "ge", "eq") and (
                    b.lo is None or v > b.lo or (v == b.lo and op == "gt")
                ):
                    b.lo, b.lo_strict = v, op == "gt"
                    found = True
                if op in ("lt", "le", "eq") and (
                    b.hi is None or v < b.hi or (v == b.hi and op == "lt")
                ):
                    b.hi, b.hi_strict = v, op == "lt"
                    found = True
            except TypeError:
                continue
    return b if found else None


def predicate_all_key_bounds(predicate: Expr, key: str) -> bool:
    """True iff EVERY conjunct is a comparable literal bound on `key`
    (eq/lt/le/gt/ge) — i.e. an exact searchsorted slice on the sorted key
    fully implements the predicate and the residual mask is redundant."""
    key = key.lower()
    for conj in split_conjuncts(predicate):
        dec = _conjunct_col_lit(conj)
        if dec is None:
            return False
        name, op, v = dec
        if name.lower() != key or op not in ("eq", "lt", "le", "gt", "ge"):
            return False
        if not isinstance(v, (int, float, bool, np.number)):
            return False
    return True


def _stats_overlap(bounds: KeyBounds, mn, mx) -> bool:
    """Can any value in [mn, mx] satisfy the bounds?"""
    try:
        if bounds.hi is not None and (mn > bounds.hi or (bounds.hi_strict and mn == bounds.hi)):
            return False
        if bounds.lo is not None and (mx < bounds.lo or (bounds.lo_strict and mx == bounds.lo)):
            return False
    except TypeError:
        return True  # incomparable stats: keep the file
    return True


def _bounds_domain(field, bounds: KeyBounds):
    """Conversion putting pruning comparisons in the SAME numeric domain
    the filter mask uses (ops/filter.py _lower_col_lit's numpy promotion):
    float32 columns compare weak scalars in float32 (the literal ROUNDS),
    and int columns compare float literals in float64. Without this,
    pruning could drop rows the mask would keep. Returns None when raw
    comparison already matches (ints vs ints, strings)."""
    dt = field.device_dtype
    vals = [v for v in (bounds.lo, bounds.hi) if v is not None]
    if dt.kind == "f":
        weak = all(
            type(v) in (int, float, bool) or isinstance(v, (np.bool_, np.float32))
            for v in vals
        )
        return np.float32 if (dt.itemsize <= 4 and weak) else np.float64
    if dt.kind in "iu" and any(isinstance(v, (float, np.floating)) for v in vals):
        return np.float64
    return None


def _convert_bounds(field, bounds: KeyBounds) -> tuple[KeyBounds, object]:
    """(bounds cast into the comparison domain, stat-value converter)."""
    conv = _bounds_domain(field, bounds)
    if conv is None:
        return bounds, lambda v: v
    try:
        cast = KeyBounds(
            conv(bounds.lo) if bounds.lo is not None else None,
            bounds.lo_strict,
            conv(bounds.hi) if bounds.hi is not None else None,
            bounds.hi_strict,
        )
    except (TypeError, ValueError, OverflowError):
        return bounds, lambda v: v
    def stat_conv(v):
        try:
            return conv(v)
        except (TypeError, ValueError, OverflowError):
            return v
    return cast, stat_conv


def _pad_bucket_major(
    codes: np.ndarray,
    offsets: np.ndarray,
    fill=None,
    width: int | None = None,
) -> np.ndarray:
    """[n] bucket-grouped values → [B, L] padded array, built with one
    vectorized gather. Default fill is the dtype's sort-last sentinel
    (key codes); value channels pass an explicit fill and width."""
    counts = np.diff(offsets)
    b = len(counts)
    lmax = width if width is not None else max(int(counts.max()) if counts.size else 1, 1)
    sentinel = join_ops.sentinel_for(codes.dtype) if fill is None else fill
    if len(codes) == 0:
        return np.full((b, lmax), sentinel, dtype=codes.dtype)
    idx = offsets[:-1, None] + np.arange(lmax, dtype=np.int64)[None, :]
    mask = np.arange(lmax)[None, :] < counts[:, None]
    return np.where(mask, codes[np.minimum(idx, len(codes) - 1)], sentinel)




def _broadcast_probe(lcodes: np.ndarray, rcodes: np.ndarray):
    """Match pairs via a broadcast hash table: the smaller side builds a
    dense code -> (start, count) table, every large-side row probes it
    with ONE vectorized gather (no binary search — random-access
    searchsorted over millions of probes is ~10x slower than a
    cache-resident table), and duplicate runs expand vectorized. The
    large side is never sorted. Null codes are side-distinct negatives
    and never match. Returns None when the shared code space is too
    sparse for a table (caller falls back to the merge kernel); else
    (lidx, ridx) in the merge path's contract."""
    swap = len(lcodes) < len(rcodes)
    build, probe = (lcodes, rcodes) if swap else (rcodes, lcodes)
    top = 0
    if len(build):
        top = max(top, int(build.max()) + 1)
    if len(probe):
        top = max(top, int(probe.max()) + 1)
    if top == 0:
        # Every key on both sides is null-coded: no row can match.
        empty = np.zeros(0, dtype=np.int64)
        return empty, empty
    if top > 8 * len(build) + 65_536:
        return None  # sparse code space: the table would dwarf the side
    bvalid = build >= 0
    counts = np.bincount(build[bvalid], minlength=top)
    starts = np.concatenate([[0], np.cumsum(counts[:-1])]) if top else np.zeros(0, np.int64)
    order = np.argsort(build, kind="stable")  # null codes sort first
    nneg = int((~bvalid).sum())
    pvalid = probe >= 0
    pc = np.where(pvalid, probe, 0)
    cnt = np.where(pvalid, counts[pc], 0)
    lo = nneg + starts[pc]
    if not counts.size or counts.max() <= 1:
        # Unique build keys (the normal dimension-table case): each probe
        # row matches 0 or 1 build rows — no run expansion at all.
        matched = cnt > 0
        probe_idx = np.flatnonzero(matched)
        build_idx = order[lo[matched]]
        if swap:
            return build_idx, probe_idx
        return probe_idx, build_idx
    total = int(cnt.sum())
    probe_idx = np.repeat(np.arange(len(probe), dtype=np.int64), cnt)
    run_starts = np.cumsum(cnt) - cnt
    within = np.arange(total, dtype=np.int64) - np.repeat(run_starts, cnt)
    build_idx = order[np.repeat(lo, cnt) + within]
    if swap:
        return build_idx, probe_idx  # build side is the LEFT input
    return probe_idx, build_idx


def _copy_field(out_f, src: ColumnTable, src_name: str, cols, dicts, val) -> None:
    """Copy src column `src_name` into output field `out_f` (dtype-cast
    for numeric mismatches — outer-join key coalescing may source the
    left-named key column from the right side)."""
    sf = src.schema.field(src_name)
    arr = src.columns[sf.name]
    if sf.name in src.dictionaries:
        dicts[out_f.name] = src.dictionaries[sf.name]
        cols[out_f.name] = arr
    else:
        want = np.dtype(out_f.device_dtype)
        cols[out_f.name] = arr if arr.ndim > 1 or arr.dtype == want else arr.astype(want)
    v = src.validity.get(sf.name)
    if v is not None:
        val[out_f.name] = v


def _null_field(out_f, n: int, dict_src: ColumnTable | None, cols, dicts, val) -> None:
    """All-null column for output field `out_f` (outer-join null
    extension). String fields reuse `dict_src`'s dictionary for that
    field when available, so concat with the matched part needs no
    dictionary merge."""
    if out_f.is_vector:
        raise HyperspaceError(
            f"outer join cannot null-extend vector column {out_f.name!r}"
        )
    if out_f.is_string:
        d = None
        if dict_src is not None:
            try:
                sf = dict_src.schema.field(out_f.name)
                d = dict_src.dictionaries.get(sf.name)
            except Exception:
                d = None
        if d is None or len(d) == 0:
            d = np.array([""], dtype=object)
        cols[out_f.name] = np.zeros(n, dtype=np.int32)
        dicts[out_f.name] = d
    else:
        cols[out_f.name] = np.zeros(n, dtype=out_f.device_dtype)
    val[out_f.name] = np.zeros(n, dtype=bool)


def _concat_side_cached(tables: list[ColumnTable]) -> ColumnTable:
    """Concatenated bucket-grouped side table, memoized on the identity
    of the per-bucket cached tables (the device plane's HBM-resident
    container rests on this stability: frozen concat => stable codes =>
    cached pads => cached uploads). Falls through for single groups (the
    cached table passes through already frozen)."""
    from hyperspace_tpu.execution import device_cache as dc

    if len(tables) == 1:
        return tables[0]
    # Only identity-stable inputs may be memoized (and only then may the
    # output be frozen): per-query tables too large for the io cache get
    # fresh ids every time — caching against those would pile dead pinned
    # entries, and freezing their concat would let every downstream cache
    # mistake per-query arrays for stable ones.
    stable = all(
        all(
            dc.is_stable(a)
            for a in (*t.columns.values(), *t.validity.values(), *t.dictionaries.values())
        )
        for t in tables
    )
    if not stable:
        return ColumnTable.concat(tables)

    def build():
        out = ColumnTable.concat(tables)
        for arr in (*out.columns.values(), *out.validity.values(), *out.dictionaries.values()):
            dc.freeze(arr)
        # _table_nbytes counts string payloads, not just object pointers —
        # the budget must see what the entry actually retains.
        return out, int(hio._table_nbytes(out))

    return dc.HOST_DERIVED.get_or_build(
        ("sidecat", tuple(id(t) for t in tables)), tuple(tables), build
    )


def _composite_keys(codes: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """(bucket << 33) + code composites: codes span int32 (±2^31) and
    buckets are small, so the shifted sum is collision-free in int64 and
    globally SORTED for bucket-major key-sorted inputs. Shared by the
    semi/anti membership probe and the fused run-extremum channels."""
    b = np.repeat(np.arange(len(offsets) - 1, dtype=np.int64), np.diff(offsets))
    return (b << np.int64(33)) + codes.astype(np.int64)


class _RunExtremum:
    """Per-primary-row extrema over the secondary match runs, shared by
    every min/max channel of one fused join-aggregation. The secondary
    side is bucket-major key-sorted, so all rows with one key form a
    contiguous run; the composite key is globally sorted and each
    primary row's run bounds come from two searchsorteds (built LAZILY —
    primary-side-only channels never pay for them). Extrema are
    multiplicity-independent, so the per-KEY extremum stands in for
    every duplicate primary row with that key."""

    def __init__(self, pri_codes, pri_offsets, pperm, sec_codes, sec_offsets, sperm, matches, n_l):
        self.sperm = sperm
        self.pperm = pperm
        self.matches = matches
        self.n_l = n_l
        self._pri = (pri_codes, pri_offsets)
        self._sec = (sec_codes, sec_offsets)
        self._runs = None

    def _run_index(self):
        if self._runs is None:
            cp = _composite_keys(*self._pri)
            cs = _composite_keys(*self._sec)
            st = np.searchsorted(cs, cp, side="left")
            en = np.searchsorted(cs, cp, side="right")
            if len(cs):
                starts = np.concatenate([[0], np.flatnonzero(np.diff(cs) != 0) + 1])
                ridx = np.clip(
                    np.searchsorted(starts, st, side="right") - 1, 0, len(starts) - 1
                )
            else:
                starts = np.zeros(0, np.int64)
                ridx = np.zeros(len(cp), np.int64)
            self._runs = (st, en, en > st, starts, ridx)
        return self._runs

    def per_primary_row(self, fn: str, side: str, secondary: str, vals, ind):
        """(row extremum, row validity) in ORIGINAL primary order for one
        channel; `vals`/`ind` are the channel's per-orig-row arrays of
        `side` (invalid slots already zeroed, `ind` marking them)."""
        identity = np.inf if fn == "min" else -np.inf
        if side == secondary:
            _st, _en, has, starts, ridx = self._run_index()
            sv = vals if self.sperm is None else vals[self.sperm]
            si = ind if self.sperm is None else ind[self.sperm]
            if not len(starts):
                return np.full(self.n_l, identity), np.zeros(self.n_l, bool)
            op = np.minimum if fn == "min" else np.maximum
            sv = np.where(si > 0, np.asarray(sv, np.float64), identity)
            key_ext = op.reduceat(sv, starts)
            key_validcnt = np.add.reduceat(np.asarray(si, np.float64), starts)
            ext_sorted = np.where(has, key_ext[ridx], identity)
            valid_sorted = has & (key_validcnt[ridx] > 0)
            if self.pperm is not None:
                ext = np.empty(self.n_l)
                ext[self.pperm] = ext_sorted
                valid = np.empty(self.n_l, bool)
                valid[self.pperm] = valid_sorted
                return ext, valid
            return ext_sorted, valid_sorted
        # Primary-side channel: extremum over the group's MATCHED rows.
        v = np.where(np.asarray(ind) > 0, np.asarray(vals, np.float64), identity)
        valid = (self.matches > 0) & (np.asarray(ind) > 0)
        return v, valid


def _desugar_count_distinct(plan: "Aggregate"):
    """count(distinct col) as a TWO-PHASE re-aggregation: the inner
    aggregate groups by (group keys, distinct column) — its rows are the
    distinct (group, value) pairs — and computes partials for every
    sibling aggregate; the outer counts the distinct column (nulls
    excluded, SQL semantics) and recombines the partials (sum of sums /
    counts, min of mins, max of maxes). The Spark analog is the planner's
    distinct-aggregate Expand rewrite. Returns (desugared plan, aliases
    of the original count specs — the caller zero-fills their NULLs)."""
    from hyperspace_tpu.plan.nodes import AggSpec, Aggregate

    # The caller routes multi-distinct / mean-sharing aggregates to
    # _distinct_aggregate; this fast path sees exactly one distinct
    # column and no mean.
    dcol = next(a.expr.name for a in plan.aggs if a.fn == "count_distinct")
    group_low = {c.lower() for c in plan.group_by}
    inner_groups = list(plan.group_by) + ([dcol] if dcol.lower() not in group_low else [])
    inner_aggs: list = []
    outer_aggs: list = []
    count_aliases: list[str] = []
    for i, a in enumerate(plan.aggs):
        if a.fn == "count_distinct":
            outer_aggs.append(AggSpec("count", Col(dcol), a.alias))
            continue
        part = f"__partial_{i}"
        if a.fn == "count":
            inner_aggs.append(AggSpec("count", a.expr, part))
            outer_aggs.append(AggSpec("sum", Col(part), a.alias))
            count_aliases.append(a.alias)
        else:  # sum / min / max recombine with themselves
            inner_aggs.append(AggSpec(a.fn, a.expr, part))
            outer_aggs.append(AggSpec(a.fn, Col(part), a.alias))
    inner = Aggregate(plan.child, inner_groups, inner_aggs)
    return Aggregate(inner, list(plan.group_by), outer_aggs), count_aliases


def _stable_table_refs(table: ColumnTable, names: set[str]):
    """(refs, id-parts) over every array the named columns touch (data,
    dictionary, validity), or (None, None) when any is unstable."""
    from hyperspace_tpu.execution import device_cache as dc

    refs: list = []
    parts: list = []
    for nm in sorted(names):
        f = table.schema.field(nm)
        for a in (table.columns[f.name], table.dictionaries.get(f.name), table.validity.get(f.name)):
            if a is None:
                parts.append(None)
                continue
            if not dc.is_stable(a):
                return None, None
            refs.append(a)
            parts.append(id(a))
    return tuple(refs), tuple(parts)


def _group_ids_cached(table: ColumnTable, group_by: list[str]):
    """group_ids memoized on the identity of the (stable) group-key
    arrays — repeat aggregations over the same index version skip the
    factorization of millions of keys."""
    from hyperspace_tpu.execution import device_cache as dc
    from hyperspace_tpu.ops.aggregate import group_ids

    if not group_by:
        return group_ids(table, group_by)
    refs, parts = _stable_table_refs(table, {c.lower() for c in group_by})
    if refs is None:
        return group_ids(table, group_by)

    def build():
        gid, k, first = group_ids(table, group_by)
        dc.freeze(gid)
        dc.freeze(first)
        return (gid, k, first), int(gid.nbytes + first.nbytes)

    return dc.HOST_DERIVED.get_or_build(
        ("gid", tuple(c.lower() for c in group_by), parts), refs, build
    )


def _agg_channels_cached(tbl: ColumnTable, spec):
    """(masked values, indicator) channels for one AggSpec, memoized per
    (expression, input identity) for stable tables."""
    import json

    from hyperspace_tpu.execution import device_cache as dc
    from hyperspace_tpu.ops.aggregate import agg_input

    def raw():
        vals, valid, _ = agg_input(tbl, spec)
        vals = np.asarray(vals, dtype=np.float64)
        if valid is not None:
            vals = np.where(valid, vals, 0.0)
        ind = np.ones(tbl.num_rows, np.float64) if valid is None else valid.astype(np.float64)
        return vals, ind

    refs, parts = _stable_table_refs(tbl, {r.lower() for r in spec.references()})
    if not refs:  # unstable or constant expression: no identity to key on
        return raw()
    key = ("aggin", json.dumps(spec.expr.to_json(), sort_keys=True), parts)

    def build():
        vals, ind = raw()
        dc.freeze(vals)
        dc.freeze(ind)
        return (vals, ind), int(vals.nbytes + ind.nbytes)

    return dc.HOST_DERIVED.get_or_build(key, refs, build)


def _factorize_keys_cached(lt: ColumnTable, rt: ColumnTable, lkeys, rkeys,
                           null_safe: bool = False):
    """Pairwise key factorization memoized on the IDENTITY of every input
    it reads (key columns, dictionaries, validity) — valid only when all
    are stable (frozen index-cache arrays). Repeat joins over the same
    index version skip ranking entirely; codes are frozen so downstream
    pad/upload caches can key on them. Returns (lcodes, rcodes)."""
    from hyperspace_tpu.execution import device_cache as dc

    lrefs, lparts = _stable_table_refs(lt, {k.lower() for k in lkeys})
    rrefs, rparts = _stable_table_refs(rt, {k.lower() for k in rkeys})
    if lrefs is None or rrefs is None:
        lc, rc = _factorize_keys([lt], [rt], lkeys, rkeys, null_safe=null_safe)
        return lc[0], rc[0]
    refs = lrefs + rrefs
    parts = (lparts, rparts, null_safe)

    def build():
        lc, rc = _factorize_keys([lt], [rt], lkeys, rkeys, null_safe=null_safe)
        out = (dc.freeze(lc[0]), dc.freeze(rc[0]))
        return out, int(lc[0].nbytes + rc[0].nbytes)

    return dc.HOST_DERIVED.get_or_build(("fact", parts), refs, build)


def _pad_bucket_major_cached(
    codes: np.ndarray, offsets: np.ndarray, fill=None, width: int | None = None
) -> np.ndarray:
    """Bucket-major pad through the derived cache when the input is
    stable (index-sorted, frozen) — the [B, L] device upload then hits
    the HBM cache too."""
    from hyperspace_tpu.execution import device_cache as dc

    if dc.is_stable(codes):
        return dc.derived(
            ("padbm", id(codes), offsets.tobytes(), repr(fill), width),
            (codes,),
            lambda: _pad_bucket_major(codes, offsets, fill=fill, width=width),
        )
    return _pad_bucket_major(codes, offsets, fill=fill, width=width)


def _stack_cached(arrs: list, empty_shape: tuple) -> np.ndarray:
    """np.stack through the derived cache when every channel is stable
    (the [A, n] float64 stack is a 100MB-scale memcpy per query)."""
    from hyperspace_tpu.execution import device_cache as dc

    if not arrs:
        return np.zeros(empty_shape)
    if all(dc.is_stable(a) for a in arrs):
        return dc.derived(
            ("stack", tuple(id(a) for a in arrs)), tuple(arrs), lambda: np.stack(arrs)
        )
    return np.stack(arrs)


def _key_null_mask(table: ColumnTable, keys: list[str]) -> np.ndarray | None:
    """True where ANY key column is null (such rows never join — SQL:
    NULL = NULL is not true). None when every key column is null-free."""
    m = None
    for k in keys:
        valid = table.valid_mask(k)
        if valid is not None:
            m = ~valid if m is None else (m | ~valid)
    return m


def _apply_null_codes(lcodes, rcodes, lnulls, rnulls):
    """Null-keyed rows get side-distinct negative codes (-2 left, -1
    right): they sort first and can never equal across sides, so the merge
    kernel drops them with zero extra work."""
    for c, m in zip(lcodes, lnulls):
        if m is not None:
            c[m] = -2
    for c, m in zip(rcodes, rnulls):
        if m is not None:
            c[m] = -1
    return lcodes, rcodes


def _factorize_keys(ltables, rtables, lkeys, rkeys, null_safe=False):
    """Map each partition's key tuples to a shared int32 rank-code space
    whose order matches the lexicographic order of the raw key tuples.
    int32 keeps the device merge-join kernels on native 32-bit lanes (TPU
    emulates 64-bit); ranks always fit (bounded by total row count).

    `null_safe` switches the NULL treatment from SQL join equality (a
    null-keyed row never matches — side-distinct negative codes) to SQL
    set/IS NOT DISTINCT FROM equality: per key column, NULL becomes one
    extra domain value SHARED across sides (code `len(uniq)`), so
    (1, NULL) matches (1, NULL) but still not (1, 0) — the physical
    zero/"" a null slot holds can no longer collide with a real value."""
    lnulls = [_key_null_mask(t, lkeys) for t in ltables]
    rnulls = [_key_null_mask(t, rkeys) for t in rtables]
    has_nulls = any(m is not None for m in lnulls + rnulls)
    # Fast path: a single integer key whose value SPAN fits int32 needs no
    # ranking — values shifted by the minimum are order-preserving codes.
    # Codes are NON-NEGATIVE by construction, so a negative code always
    # means a null-keyed row (the invariant _broadcast_probe and the
    # null-code scheme below rely on). (Skipped with nulls: raw values
    # could collide with the null codes.)
    if len(lkeys) == 1 and not has_nulls:
        lvals = [_logical_key(t, lkeys[0]) for t in ltables]
        rvals = [_logical_key(t, rkeys[0]) for t in rtables]
        if all(np.issubdtype(v.dtype, np.integer) for v in lvals + rvals):
            lo = min((int(v.min()) for v in lvals + rvals if len(v)), default=0)
            hi = max((int(v.max()) for v in lvals + rvals if len(v)), default=0)
            # Span strictly below int32 max: the sentinel pad must still
            # sort last after the shift.
            if hi - lo < np.iinfo(np.int32).max - 1:
                shift = np.int64(lo)
                return (
                    [(v.astype(np.int64) - shift).astype(np.int32) for v in lvals],
                    [(v.astype(np.int64) - shift).astype(np.int32) for v in rvals],
                )

    per_col_codes_l: list[list[np.ndarray]] = [[] for _ in ltables]
    per_col_codes_r: list[list[np.ndarray]] = [[] for _ in rtables]
    cards: list[int] = []
    for lname, rname in zip(lkeys, rkeys):
        dict_res = _dict_domain_codes(ltables, rtables, lname, rname)
        if dict_res is not None:
            # Dictionary-coded string keys factorize in the DICTIONARY
            # domain: merge the small sorted dictionaries and remap each
            # side's codes with one O(n) gather — the per-row string
            # values never inflate on host (the O(n log n) string
            # np.unique below was a top line of BENCH_SF100's
            # key-factorization tax). Order and cross-side equality are
            # preserved exactly (the merged domain is sorted and covers
            # both sides); cardinality counts dictionary entries, a
            # superset of used values — the mixed-radix combination only
            # needs an injective order-preserving code space, so a
            # larger radix is still correct.
            lvals, rvals, card = dict_res
            if null_safe and has_nulls:
                masks = [t.valid_mask(lname) for t in ltables] + [
                    t.valid_mask(rname) for t in rtables
                ]
                if any(m is not None for m in masks):
                    lvals = [v.copy() for v in lvals]
                    rvals = [v.copy() for v in rvals]
                    any_null = False
                    for v, m in zip(lvals + rvals, masks):
                        if m is not None and (~m).any():
                            v[~m] = card
                            any_null = True
                    if any_null:
                        card += 1
            cards.append(max(card, 1))
            for i, v in enumerate(lvals):
                per_col_codes_l[i].append(v)
            for i, v in enumerate(rvals):
                per_col_codes_r[i].append(v)
            continue
        lvals = [_logical_key(t, lname) for t in ltables]
        rvals = [_logical_key(t, rname) for t in rtables]
        allv = np.concatenate(lvals + rvals) if (lvals or rvals) else np.array([])
        uniq, inv = np.unique(allv, return_inverse=True)
        card = max(len(uniq), 1)
        if null_safe and has_nulls:
            # NULL = one extra per-column domain value shared across
            # sides, so the physical zero/"" a null slot holds cannot
            # alias a real value of this column.
            masks = [t.valid_mask(lname) for t in ltables] + [
                t.valid_mask(rname) for t in rtables
            ]
            if any(m is not None for m in masks):
                alln = np.concatenate([
                    (~m if m is not None else np.zeros(len(v), dtype=bool))
                    for m, v in zip(masks, lvals + rvals)
                ])
                if alln.any():
                    inv = inv.copy()
                    inv[alln] = len(uniq)
                    card = len(uniq) + 1
        cards.append(card)
        pos = 0
        for i, v in enumerate(lvals):
            per_col_codes_l[i].append(inv[pos : pos + len(v)])
            pos += len(v)
        for i, v in enumerate(rvals):
            per_col_codes_r[i].append(inv[pos : pos + len(v)])
            pos += len(v)

    def combine(per_part):
        out = []
        for codes in per_part:
            acc = np.zeros(len(codes[0]) if codes else 0, dtype=np.int64)
            for c, k in zip(codes, cards):
                acc = acc * np.int64(k) + c.astype(np.int64)
            out.append(acc)
        return out

    import math

    if math.prod(cards) >= np.iinfo(np.int64).max:
        # The int64 mixed-radix combination itself would wrap — the codes
        # in `combine` below would collide before any re-rank could help.
        raise HyperspaceError(
            f"join key cardinalities {cards} overflow the int64 code space"
        )
    lcomb, rcomb = combine(per_col_codes_l), combine(per_col_codes_r)
    int32_max = np.iinfo(np.int32).max
    # Mixed-radix codes that provably fit int32 cast directly — no
    # re-rank pass needed (math.prod is exact, arbitrary precision).
    if math.prod(cards) < int32_max:
        lc = [c.astype(np.int32) for c in lcomb]
        rc = [c.astype(np.int32) for c in rcomb]
        if null_safe:
            # NULLs are already real domain values in the codes — the
            # never-match negative-code scheme must not touch them.
            return lc, rc
        return _apply_null_codes(lc, rc, lnulls, rnulls)
    # Otherwise re-rank the combined codes down to int32 (order preserved
    # by np.unique).
    allc = np.concatenate(lcomb + rcomb) if (lcomb or rcomb) else np.zeros(0, np.int64)
    uniq, inv = np.unique(allc, return_inverse=True)
    if len(uniq) >= int32_max:
        raise HyperspaceError(
            f"join key space has {len(uniq)} distinct tuples — exceeds the "
            "int32 code space"
        )
    inv = inv.astype(np.int32)
    pos, out_l, out_r = 0, [], []
    for c in lcomb:
        out_l.append(inv[pos : pos + len(c)])
        pos += len(c)
    for c in rcomb:
        out_r.append(inv[pos : pos + len(c)])
        pos += len(c)
    if null_safe:
        return out_l, out_r
    return _apply_null_codes(out_l, out_r, lnulls, rnulls)


def _dict_domain_codes(ltables, rtables, lname, rname):
    """Dictionary-domain factorization of one string key column:
    (per-left-table codes, per-right-table codes, cardinality) in the
    merged sorted-dictionary domain, or None when the column pair is not
    dictionary-coded on every table (the value-domain np.unique path
    handles it). The merged domain is the sorted union of the SMALL
    per-table dictionaries; each table's rows remap with one gather."""
    lfs = [t.schema.field(lname) for t in ltables]
    rfs = [t.schema.field(rname) for t in rtables]
    if not all(f.is_string for f in lfs + rfs):
        return None
    pairs = [(t, t.schema.field(lname).name) for t in ltables] + [
        (t, t.schema.field(rname).name) for t in rtables
    ]
    if any(nm not in t.dictionaries for t, nm in pairs):
        return None
    dicts = [np.asarray(t.dictionaries[nm]) for t, nm in pairs]
    first = dicts[0]
    if all(len(d) == len(first) and np.array_equal(d, first) for d in dicts[1:]):
        # One shared sorted dictionary (the common single-index-version
        # case): the codes already ARE the domain ranks — zero work.
        codes = [t.columns[nm].astype(np.int64, copy=False) for t, nm in pairs]
        card = len(first)
    else:
        merged = np.unique(np.concatenate([d.astype(str) for d in dicts]))
        codes = []
        for (t, nm), d in zip(pairs, dicts):
            old_to_new = np.searchsorted(merged, d.astype(str)).astype(np.int64)
            col = t.columns[nm]
            codes.append(old_to_new[col] if len(d) else col.astype(np.int64, copy=False))
        card = len(merged)
    nl = len(ltables)
    return codes[:nl], codes[nl:], card


def _logical_key(table: ColumnTable, name: str) -> np.ndarray:
    f = table.schema.field(name)
    arr = table.columns[f.name]
    if f.is_string:
        return table.dictionaries[f.name][arr]
    return arr
