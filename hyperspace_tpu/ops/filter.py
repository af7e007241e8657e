"""Device-side predicate evaluation.

The analog of Spark's WholeStageCodegen'd filter/project over the index scan
(SURVEY.md §2.2): the whole predicate tree evaluates as ONE jitted XLA
computation over the columns — XLA fuses the comparisons/boolean algebra
into a single pass over HBM, which is the TPU equivalent of the JVM's fused
codegen operator.

Device compute stays 32-bit native (TPU lanes are 32-bit; the process-wide
`jax_enable_x64` flag is never touched). 64-bit columns are handled by
*pairing*: each comparison against an int64/float64 column is lowered to an
equivalent boolean expression over two virtual uint32 columns — the hi/lo
words of an order-preserving 64-bit key (sign-flipped for ints, IEEE
total-order mapped for floats) — with the literal split the same way on
host. Comparisons XLA can't express this way (64-bit arithmetic, exotic
mixed-type shapes) fall back to one vectorized numpy evaluation on host.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import jax
import jax.numpy as jnp

from hyperspace_tpu.compat import jit, to_host
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.plan.expr import (
    And,
    BinOp,
    Col,
    DatePart,
    Expr,
    InList,
    IsNull,
    Like,
    Lit,
    Not,
    Or,
    Substr,
    evaluate,
)

# Virtual-column name pieces for the 64-bit pair lowering. "\x00" cannot
# appear in a real column name, so these never collide with the schema.
_SEP = "\x00"
_FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq", "ne": "ne"}


class _HostFallback(Exception):
    """Raised by the lowering pass when the predicate needs host numpy."""


@dataclasses.dataclass(eq=False, repr=True)
class _Cmp3(Expr):
    """A 3-valued comparison: `value` is the device boolean expression,
    `null` (optional) an expression over virtual is-null columns — when it
    is true the comparison's outcome is UNKNOWN (SQL semantics: any
    comparison with NULL is neither true nor false)."""

    value: Expr
    null: Expr | None

    def references(self):
        refs = self.value.references()
        return refs | self.null.references() if self.null is not None else refs


def _null_expr(table: ColumnTable, names: list[str]) -> Expr | None:
    """OR of is-null virtual columns for the given base columns (only those
    that actually carry validity masks); None when none do."""
    out: Expr | None = None
    for name in names:
        if table.valid_mask(name) is None:
            continue
        c = Col(f"{table.schema.field(name).name}{_SEP}nul")
        out = c if out is None else Or(out, c)
    return out


def _or_chain(parts: list[Expr]) -> Expr:
    """BALANCED disjunction (depth log2 n): a left-deep chain overflows
    every recursive walker past a few hundred terms."""
    if len(parts) == 1:
        return parts[0]
    mid = len(parts) // 2
    return Or(_or_chain(parts[:mid]), _or_chain(parts[mid:]))


# Above this many runs the desugared comparison tree stops being a win
# (hundreds of fused comparisons per row); a code->bool lookup table is
# one gather instead.
_MAX_CODE_RUNS = 64


@dataclasses.dataclass(eq=False, repr=True)
class _DictLut(Expr):
    """Internal leaf: boolean lookup over a string column's dictionary
    codes (lut[code]); produced by translate_predicate when a LIKE/IN
    match set is too scattered for range desugaring. Never serialized —
    it exists only between translation and evaluation."""

    col: Col
    lut: "np.ndarray"  # bool, [dictionary size]

    def references(self):
        return self.col.references()


@dataclasses.dataclass(eq=False, repr=True)
class _StrColCmp(Expr):
    """Internal leaf: comparison between two STRING-VALUED sides (columns
    or substrings of columns) whose dictionaries differ. Each side's codes
    map through `lmap`/`rmap` into one MERGED sorted dictionary, where
    integer comparison equals string comparison. Raw code comparison
    across two dictionaries is meaningless — this leaf is what
    translate_predicate rewrites it into. Host-evaluated (the lowering
    pass falls back)."""

    op: str
    left: Col
    right: Col
    lmap: "np.ndarray"  # [left dict size] int32 positions in the merged dict
    rmap: "np.ndarray"

    def references(self):
        return self.left.references() | self.right.references()


def _string_valued(table: ColumnTable, e: Expr):
    """(column name, per-code string values) when `e` is a string column
    or SUBSTRING of one; None otherwise."""
    if isinstance(e, Col):
        try:
            f = table.schema.field(e.name)
        except Exception:
            return None
        if f.is_string:
            return f.name, np.asarray(table.dictionaries[f.name], dtype=object)
        return None
    if isinstance(e, Substr) and isinstance(e.child, Col):
        f = table.schema.field(e.child.name)
        if f.is_string:
            name, vals = _substr_values(table, e)
            return name, np.asarray(vals, dtype=object)
    return None


def _codes_runs_expr(col: Col, codes: "np.ndarray", dict_size: int) -> Expr:
    """Matched dictionary codes (sorted int array) → the equivalent
    predicate in the code domain: an OR of contiguous code ranges (a
    prefix LIKE over a SORTED dictionary is always ONE range), or a
    dictionary lookup table when the match set is scattered (NOT LIKE
    over near-unique comments). All forms are device-lowerable and
    null-aware via the normal _Cmp3 machinery."""
    if len(codes) == 0:
        # No dictionary value matches: always-false but still UNKNOWN for
        # null inputs (-1 is never a real code).
        return BinOp("eq", col, Lit(np.int32(-1)))
    codes = np.asarray(codes, dtype=np.int64)
    breaks = np.flatnonzero(np.diff(codes) > 1)
    if len(breaks) + 1 > _MAX_CODE_RUNS:
        lut = np.zeros(dict_size, dtype=bool)
        lut[codes] = True
        return _DictLut(col, lut)
    starts = np.concatenate([[0], breaks + 1])
    ends = np.concatenate([breaks, [len(codes) - 1]])
    parts: list[Expr] = []
    for s, t in zip(starts, ends):
        a, b = int(codes[s]), int(codes[t])
        if a == b:
            parts.append(BinOp("eq", col, Lit(np.int32(a))))
        else:
            parts.append(
                And(BinOp("ge", col, Lit(np.int32(a))), BinOp("le", col, Lit(np.int32(b))))
            )
    return _or_chain(parts)


def like_regex(pattern: str):
    """Compiled regex for a SQL LIKE pattern (% = any run, _ = one char)."""
    import re

    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("".join(out), re.DOTALL)


def _like_codes(table: ColumnTable, colname: str, pattern: str) -> "np.ndarray":
    f = table.schema.field(colname)
    if not f.is_string:
        from hyperspace_tpu.exceptions import HyperspaceError

        raise HyperspaceError(f"LIKE requires a string column, got {colname!r}")
    rx = like_regex(pattern)
    d = table.dictionaries[f.name]
    return np.flatnonzero([rx.fullmatch(str(s)) is not None for s in d])


def _substr_values(table: ColumnTable, sub: Substr) -> tuple[str, "np.ndarray"]:
    """(column name, per-dictionary-entry substring values)."""
    from hyperspace_tpu.exceptions import HyperspaceError

    if not isinstance(sub.child, Col):
        raise HyperspaceError("SUBSTRING applies to a column")
    f = table.schema.field(sub.child.name)
    if not f.is_string:
        raise HyperspaceError(f"SUBSTRING requires a string column, got {sub.child.name!r}")
    lo = sub.start - 1
    d = table.dictionaries[f.name]
    return f.name, np.array([str(s)[lo : lo + sub.length] for s in d], dtype=object)


_NP_CMP = {"eq": "__eq__", "ne": "__ne__", "lt": "__lt__", "le": "__le__", "gt": "__gt__", "ge": "__ge__"}


def translate_predicate(table: ColumnTable, e: Expr) -> Expr:
    """Rewrite string-column comparisons against literals into the code
    domain of `table`'s dictionaries (order-preserving), and desugar the
    SQL predicate extensions — IN, LIKE, SUBSTRING comparisons, date-part
    comparisons — into plain comparison trees the device lowering and the
    host fallback both evaluate. Pure — returns a new tree, never mutates
    the plan's predicate."""
    if isinstance(e, BinOp) and e.is_comparison:
        l, r = e.left, e.right
        ls, rs = _string_valued(table, l), _string_valued(table, r)
        if ls is not None and rs is not None:
            # String-valued vs string-valued: codes from two different
            # dictionaries must NOT compare directly — remap both into
            # one merged sorted dictionary first (q19/q46's
            # city/zip-prefix inequality shapes).
            lname, lvals = ls
            rname, rvals = rs
            ls_str = lvals.astype(str)
            rs_str = rvals.astype(str)
            merged = np.unique(np.concatenate([ls_str, rs_str]))
            lmap = np.searchsorted(merged, ls_str).astype(np.int32)
            rmap = np.searchsorted(merged, rs_str).astype(np.int32)
            return _StrColCmp(e.op, Col(lname), Col(rname), lmap, rmap)
        if (ls is None) != (rs is None):
            other = r if ls is not None else l
            if not isinstance(other, Lit):
                from hyperspace_tpu.exceptions import HyperspaceError

                raise HyperspaceError(
                    "cannot compare a string column with a non-string expression"
                )
        if isinstance(r, (Substr, DatePart)) and isinstance(l, Lit):
            l, r = r, l
            e = BinOp(_FLIP[e.op], l, r)
        if isinstance(l, Substr) and isinstance(r, Lit):
            name, vals = _substr_values(table, l)
            cmp = getattr(vals.astype(str), _NP_CMP[e.op])
            codes = np.flatnonzero(cmp(str(r.value)))
            return _codes_runs_expr(Col(name), codes, len(vals))
        if isinstance(l, DatePart) and isinstance(r, Lit):
            t = _translate_date_part_cmp(e.op, l, r.value)
            if t is not None:
                return t
            return e  # month/day shapes: host evaluation
        if isinstance(l, Col) and isinstance(r, Lit) and table.schema.field(l.name).is_string:
            return BinOp(e.op, l, Lit(table.translate_literal(l.name, r.value, e.op)))
        if isinstance(r, Col) and isinstance(l, Lit) and table.schema.field(r.name).is_string:
            return translate_predicate(table, BinOp(_FLIP[e.op], r, l))
        return e
    if isinstance(e, InList):
        child = e.child
        if isinstance(child, Substr):
            name, vals = _substr_values(table, child)
            want = {str(v) for v in e.values}
            codes = np.flatnonzero([v in want for v in vals])
            return _codes_runs_expr(Col(name), codes, len(vals))
        if isinstance(child, Col):
            if table.schema.field(child.name).is_string:
                codes = []
                d = table.dictionaries[table.schema.field(child.name).name]
                for v in e.values:
                    pos = int(np.searchsorted(d, v))
                    codes.append(pos if pos < len(d) and d[pos] == v else -1)
                if 0 < len(codes) <= _MAX_CODE_RUNS:
                    # One equality per listed value (-1, never a real code,
                    # for a value the dictionary lacks): the compiled mask
                    # depends on the list's length, not on which values it
                    # holds or whether their codes are adjacent.
                    return _or_chain([BinOp("eq", child, Lit(np.int32(c))) for c in codes])
                hit = np.unique([c for c in codes if c >= 0])
                return _codes_runs_expr(child, hit, len(d))
            return _or_chain([BinOp("eq", child, Lit(v)) for v in e.values])
        return e  # DatePart / arithmetic probes: host evaluation
    if isinstance(e, Like):
        from hyperspace_tpu.exceptions import HyperspaceError

        if not isinstance(e.child, Col):
            raise HyperspaceError("LIKE applies to a column")
        f = table.schema.field(e.child.name)
        return _codes_runs_expr(
            Col(f.name),
            _like_codes(table, e.child.name, e.pattern),
            len(table.dictionaries[f.name]),
        )
    if isinstance(e, And):
        return And(translate_predicate(table, e.left), translate_predicate(table, e.right))
    if isinstance(e, Or):
        return Or(translate_predicate(table, e.left), translate_predicate(table, e.right))
    if isinstance(e, Not):
        return Not(translate_predicate(table, e.child))
    return e


def _days(y: int, m: int, d: int) -> int:
    import datetime

    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def _translate_date_part_cmp(op: str, dp: DatePart, value) -> Expr | None:
    """year(col) OP literal → the equivalent day-range comparison on the
    raw date column (device-lowerable; feeds min/max range pruning).
    month/day parts are not interval-shaped over days — return None."""
    if dp.part != "year" or not isinstance(dp.child, Col):
        return None
    if isinstance(value, (bool, np.bool_)) or not isinstance(value, (int, np.integer)):
        return None
    col = dp.child
    y = int(value)
    if y < 1 or y > 9998:  # keep datetime.date in range
        return None
    first, next_first = _days(y, 1, 1), _days(y + 1, 1, 1)
    if op == "eq":
        return And(BinOp("ge", col, Lit(first)), BinOp("lt", col, Lit(next_first)))
    if op == "ne":
        return Or(BinOp("lt", col, Lit(first)), BinOp("ge", col, Lit(next_first)))
    if op == "lt":
        return BinOp("lt", col, Lit(first))
    if op == "le":
        return BinOp("lt", col, Lit(next_first))
    if op == "ge":
        return BinOp("ge", col, Lit(first))
    if op == "gt":
        return BinOp("ge", col, Lit(next_first))
    return None


# -- 64-bit pair lowering ----------------------------------------------------

def _col_kind(table: ColumnTable, name: str) -> tuple[str, int]:
    """('i'|'f'|'b', byte width) of a column's device array."""
    f = table.schema.field(name)
    dt = np.dtype(f.device_dtype)
    if dt == np.bool_:
        return "b", 1
    return ("f" if dt.kind == "f" else "i"), dt.itemsize


def _ordered_u64(arr: np.ndarray, domain: str) -> np.ndarray:
    """Map a column to uint64 keys whose unsigned order equals the value
    order of `domain` ('i' = int64 order, 'f' = float64 total order with
    -0.0 canonicalized and NaN above +inf)."""
    if domain == "i":
        a = arr.astype(np.int64, copy=False)
        return a.view(np.uint64) ^ np.uint64(1 << 63)
    a = arr.astype(np.float64, copy=False)
    a = np.where(a == 0.0, 0.0, a)  # -0.0 → +0.0 so == matches IEEE
    a = np.where(np.isnan(a), np.nan, a)  # negative NaNs → canonical NaN,
    # so EVERY NaN keys above +inf and the guards catch them uniformly
    u = a.view(np.uint64)
    neg = (u >> np.uint64(63)).astype(bool)
    return np.where(neg, ~u, u | np.uint64(1 << 63))


def _key_parts(value: float | int, domain: str) -> tuple[np.uint32, np.uint32] | None:
    """hi/lo uint32 words of one literal's ordered key (None = NaN)."""
    if domain == "f":
        v = np.float64(value)
        if np.isnan(v):
            return None
        u = int(_ordered_u64(np.array([v]), "f")[0])
    else:
        u = int(_ordered_u64(np.array([int(value)], dtype=np.int64), "i")[0])
    return np.uint32(u >> 32), np.uint32(u & 0xFFFFFFFF)


def _pair_cols(name: str, domain: str) -> tuple[Col, Col]:
    return Col(f"{name}{_SEP}{domain}hi"), Col(f"{name}{_SEP}{domain}lo")


def _pair_cmp(op: str, hi, lo, hi2, lo2) -> Expr:
    """Lexicographic (hi, lo) comparison as a boolean expression. Operands
    are Col/Lit exprs over uint32 values."""
    if op == "eq":
        return And(BinOp("eq", hi, hi2), BinOp("eq", lo, lo2))
    if op == "ne":
        return Or(BinOp("ne", hi, hi2), BinOp("ne", lo, lo2))
    strict = {"lt": "lt", "le": "lt", "gt": "gt", "ge": "gt"}[op]
    inner = {"lt": "lt", "le": "le", "gt": "gt", "ge": "ge"}[op]
    return Or(
        BinOp(strict, hi, hi2),
        And(BinOp("eq", hi, hi2), BinOp(inner, lo, lo2)),
    )


_INT32_MIN, _INT32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max
_INT64_MIN, _INT64_MAX = np.iinfo(np.int64).min, np.iinfo(np.int64).max


def _normalize_int_literal(value, op: str):
    """Reduce a numeric literal compared against an INTEGER column to an
    int literal + op, or a constant bool when the comparison is decided.

    Returns ("const", bool) | ("cmp", op, int_value)."""
    if isinstance(value, (bool, np.bool_)):
        value = int(value)
    if isinstance(value, (float, np.floating)):
        f = float(value)
        if math.isnan(f):
            return ("const", op == "ne")
        if f == math.inf:
            return ("const", op in ("lt", "le", "ne"))
        if f == -math.inf:
            return ("const", op in ("gt", "ge", "ne"))
        if f == int(f):
            value = int(f)
        else:
            # x OP non-integral f over integers decides by floor/ceil.
            if op == "eq":
                return ("const", False)
            if op == "ne":
                return ("const", True)
            if op in ("lt", "le"):
                return ("cmp", "le", math.floor(f))
            return ("cmp", "ge", math.ceil(f))  # gt, ge
    v = int(value)
    if v > _INT64_MAX:
        return ("const", op in ("lt", "le", "ne"))
    if v < _INT64_MIN:
        return ("const", op in ("gt", "ge", "ne"))
    return ("cmp", op, v)


def _lower_col_lit(table: ColumnTable, op: str, colname: str, value) -> Expr:
    """Lower `col OP literal` to a device-safe expression."""
    kind, width = _col_kind(table, colname)
    if kind == "b":
        if isinstance(value, (bool, np.bool_)):
            return BinOp(op, Col(colname), Lit(np.bool_(value)))
        raise _HostFallback  # bool vs numeric literal: numpy int semantics
    if kind == "i":
        if isinstance(value, (float, np.floating)) and width > 4:
            # numpy compares int64 arrays with float scalars in float64,
            # ROUNDING the column above 2^53 — match it by comparing in the
            # float64 key domain (the pair prep casts the column the same
            # lossy way numpy does).
            return _float_domain_cmp(colname, op, value)
        norm = _normalize_int_literal(value, op)
        if norm[0] == "const":
            return Lit(np.bool_(norm[1]))
        _, op, v = norm
        if width <= 4:
            # int32 → float64 is exact, so floor/ceil normalization of a
            # float literal is equivalent to numpy's float64 comparison.
            if _INT32_MIN <= v <= _INT32_MAX:
                return BinOp(op, Col(colname), Lit(np.int32(v)))
            return Lit(np.bool_(op in ("lt", "le", "ne") if v > _INT32_MAX else op in ("gt", "ge", "ne")))
        hi, lo = _key_parts(v, "i")
        chi, clo = _pair_cols(colname, "i")
        return _pair_cmp(op, chi, clo, Lit(hi), Lit(lo))
    # float column
    if width <= 4:
        weak = type(value) in (int, float, bool) or isinstance(value, (np.bool_, np.float32))
        if weak:
            # numpy weak-scalar promotion (NEP 50): a python scalar against
            # a float32 array compares IN float32 — round the literal.
            return BinOp(op, Col(colname), Lit(np.float32(value)))
        # Strong 64-bit numpy scalar: numpy promotes to float64; widen the
        # column to the float64 pair domain (float32→float64 is exact).
    return _float_domain_cmp(colname, op, value)


def _float_domain_cmp(colname: str, op: str, value) -> Expr:
    """`col OP literal` in the float64 ordered-key pair domain."""
    parts = _key_parts(value, "f")
    if parts is None:  # NaN literal: IEEE says everything compares false
        return Lit(np.bool_(op == "ne"))
    hi, lo = parts
    chi, clo = _pair_cols(colname, "f")
    out = _pair_cmp(op, chi, clo, Lit(hi), Lit(lo))
    if op in ("gt", "ge"):
        # NaN keys sort above +inf; gt/ge must exclude them (IEEE: false).
        ihi, ilo = _key_parts(math.inf, "f")
        out = And(out, _pair_cmp("le", chi, clo, Lit(ihi), Lit(ilo)))
    return out


def _lower_col_col(table: ColumnTable, op: str, lname: str, rname: str) -> Expr:
    lkind, lwidth = _col_kind(table, lname)
    rkind, rwidth = _col_kind(table, rname)
    if lkind == "b" or rkind == "b":
        if lkind == rkind:
            return BinOp(op, Col(lname), Col(rname))
        raise _HostFallback
    if lwidth <= 4 and rwidth <= 4 and lkind == rkind:
        return BinOp(op, Col(lname), Col(rname))
    # Widen both sides into a shared ordered-key domain: int-int compares in
    # int64 order; anything involving a float compares in float64 order
    # (ints cast to float64 — numpy's promotion does the same).
    domain = "i" if (lkind == "i" and rkind == "i") else "f"
    lhi, llo = _pair_cols(lname, domain)
    rhi, rlo = _pair_cols(rname, domain)
    out = _pair_cmp(op, lhi, llo, rhi, rlo)
    if domain == "f":
        # NaN keys (any sign, canonicalized) sort above +inf; exclude them
        # on whichever side the op could leak through (IEEE: any comparison
        # with NaN is false, != is true).
        ihi, ilo = _key_parts(math.inf, "f")
        l_finite = _pair_cmp("le", lhi, llo, Lit(ihi), Lit(ilo))
        r_finite = _pair_cmp("le", rhi, rlo, Lit(ihi), Lit(ilo))
        if op in ("gt", "ge"):
            out = And(out, l_finite)
        elif op in ("lt", "le"):
            out = And(out, r_finite)
        elif op == "eq":  # NaN == NaN must be false despite equal keys
            out = And(out, l_finite)
        elif op == "ne":  # NaN != NaN must be true despite equal keys
            out = Or(out, Not(l_finite))
    return out


def _subtree_kinds(table: ColumnTable, e: Expr) -> set[str] | None:
    """Value kinds ('i'/'f'/'b') a non-comparison subtree touches, or None
    when it can't evaluate correctly in 32-bit device mode (64-bit columns,
    literals not 32-bit exact, or int division — numpy divides ints in
    float64, jnp in float32, so threshold comparisons could diverge)."""
    if isinstance(e, Col):
        kind, width = _col_kind(table, e.name)
        return {kind} if width <= 4 else None
    if isinstance(e, Lit):
        v = e.value
        if isinstance(v, (bool, np.bool_)):
            return {"b"}
        if isinstance(v, (int, np.integer)):
            return {"i"} if _INT32_MIN <= int(v) <= _INT32_MAX else None
        if isinstance(v, (float, np.floating)):
            ok = np.isnan(v) or float(np.float32(v)) == float(v)
            return {"f"} if ok else None
        return None
    if isinstance(e, BinOp):
        l = _subtree_kinds(table, e.left)
        r = _subtree_kinds(table, e.right)
        if l is None or r is None:
            return None
        kinds = l | r
        if len(kinds) > 1:
            # Mixed-kind arithmetic: numpy promotes int⊕float to float64,
            # the device would use float32 — lossy above 2^24. Host only.
            return None
        if e.op == "div" and kinds != {"f"}:
            return None  # int division: numpy float64, device float32
        return kinds
    return None


def _lower(table: ColumnTable, e: Expr) -> Expr:
    """Lower a (string-translated) predicate to a device-safe tree of
    And/Or/Not over _Cmp3 leaves (3-valued comparisons), raising
    _HostFallback where 32-bit device semantics can't match numpy."""
    if isinstance(e, And):
        return And(_lower(table, e.left), _lower(table, e.right))
    if isinstance(e, Or):
        return Or(_lower(table, e.left), _lower(table, e.right))
    if isinstance(e, Not):
        return Not(_lower(table, e.child))
    if isinstance(e, IsNull):
        # IS NULL is never UNKNOWN: it evaluates the validity lanes
        # directly (true where any referenced column is null).
        nul = _null_expr(table, sorted(e.references()))
        return _Cmp3(nul if nul is not None else Lit(np.bool_(False)), None)
    if isinstance(e, _DictLut):
        return _Cmp3(e, _null_expr(table, [e.col.name]))
    if isinstance(e, BinOp) and e.is_comparison:
        l, r = e.left, e.right
        if isinstance(l, Lit) and isinstance(r, Col):
            return _lower(table, BinOp(_FLIP[e.op], r, l))
        if isinstance(l, Col) and isinstance(r, Lit):
            value = _lower_col_lit(table, e.op, l.name, r.value)
            return _Cmp3(value, _null_expr(table, [l.name]))
        if isinstance(l, Col) and isinstance(r, Col):
            value = _lower_col_col(table, e.op, l.name, r.name)
            return _Cmp3(value, _null_expr(table, [l.name, r.name]))
        # Compound arithmetic sides: keep on device only when every piece
        # is exactly representable in 32-bit lanes AND both sides share one
        # value kind (mixed int/float comparisons promote to float64 under
        # numpy but float32 on device).
        lk = _subtree_kinds(table, l)
        rk = _subtree_kinds(table, r)
        if lk is not None and rk is not None and len(lk | rk) == 1:
            # A null in ANY input makes the whole comparison unknown.
            return _Cmp3(e, _null_expr(table, sorted(e.references())))
        raise _HostFallback
    if isinstance(e, Lit) and isinstance(e.value, (bool, np.bool_)):
        return _Cmp3(e, None)
    raise _HostFallback


# -- compiled evaluation ----------------------------------------------------

def _structure_key(e: Expr, lits: list) -> tuple:
    """Structural fingerprint of an expression with literals abstracted out
    (collected into `lits` in walk order). Predicates that differ only in
    literal values share one compiled evaluator."""
    if isinstance(e, _Cmp3):
        return (
            "cmp3",
            _structure_key(e.value, lits),
            _structure_key(e.null, lits) if e.null is not None else None,
        )
    if isinstance(e, _DictLut):
        # The lut enters as a traced array argument: same-structure
        # predicates over different dictionaries share the compiled fn.
        lits.append(e.lut)
        return ("dictlut", e.col.name.lower())
    if isinstance(e, Lit):
        lits.append(e.value)
        return ("lit",)
    if isinstance(e, Col):
        return ("col", e.name.lower())
    if isinstance(e, BinOp):
        return ("binop", e.op, _structure_key(e.left, lits), _structure_key(e.right, lits))
    if isinstance(e, And):
        return ("and", _structure_key(e.left, lits), _structure_key(e.right, lits))
    if isinstance(e, Or):
        return ("or", _structure_key(e.left, lits), _structure_key(e.right, lits))
    if isinstance(e, Not):
        return ("not", _structure_key(e.child, lits))
    raise ValueError(f"cannot fingerprint {e!r}")


def _eval_with_args(e: Expr, cols: dict, lit_iter) -> object:
    """Evaluate against traced column arrays and traced literal scalars
    (consumed in the same walk order _structure_key used)."""
    if isinstance(e, Lit):
        return next(lit_iter)
    if isinstance(e, _DictLut):
        lut = next(lit_iter)
        return lut[cols[e.col.name.lower()]]
    if isinstance(e, Col):
        return cols[e.name.lower()]
    if isinstance(e, BinOp):
        a = _eval_with_args(e.left, cols, lit_iter)
        b = _eval_with_args(e.right, cols, lit_iter)
        return evaluate(BinOp(e.op, Lit(a), Lit(b)), None, jnp)
    if isinstance(e, And):
        return jnp.logical_and(_eval_with_args(e.left, cols, lit_iter), _eval_with_args(e.right, cols, lit_iter))
    if isinstance(e, Or):
        return jnp.logical_or(_eval_with_args(e.left, cols, lit_iter), _eval_with_args(e.right, cols, lit_iter))
    if isinstance(e, Not):
        return jnp.logical_not(_eval_with_args(e.child, cols, lit_iter))
    raise ValueError(f"cannot evaluate {e!r}")


def _eval3(e: Expr, cols: dict, lit_iter):
    """Kleene evaluation → (definitely-true, definitely-false) mask pair.
    Unknown = neither. This is how SQL's 3-valued logic stays a pair of
    plain boolean lanes the TPU fuses for free."""
    if isinstance(e, _Cmp3):
        v = _eval_with_args(e.value, cols, lit_iter)
        if e.null is None:
            return v, jnp.logical_not(v)
        n = _eval_with_args(e.null, cols, lit_iter)
        known = jnp.logical_not(n)
        return jnp.logical_and(v, known), jnp.logical_and(jnp.logical_not(v), known)
    if isinstance(e, And):
        t1, f1 = _eval3(e.left, cols, lit_iter)
        t2, f2 = _eval3(e.right, cols, lit_iter)
        return jnp.logical_and(t1, t2), jnp.logical_or(f1, f2)
    if isinstance(e, Or):
        t1, f1 = _eval3(e.left, cols, lit_iter)
        t2, f2 = _eval3(e.right, cols, lit_iter)
        return jnp.logical_or(t1, t2), jnp.logical_and(f1, f2)
    if isinstance(e, Not):
        t, f = _eval3(e.child, cols, lit_iter)
        return f, t
    raise ValueError(f"cannot 3-value evaluate {e!r}")


# (structure, column layout, literal dtypes, padded length) → jitted fn.
# Literals enter as traced scalars and shapes are padded to powers of two,
# so repeated point lookups with different keys / different bucket sizes
# hit the XLA compile cache instead of re-tracing per query. Lock-guarded
# for concurrent serve workers (a racing double-trace is harmless but the
# insert must not tear the dict).
import threading

_MASK_FN_CACHE: dict = {}
_MASK_FN_LOCK = threading.Lock()


def _pow2(n: int) -> int:
    return 1 << max(1, (n - 1)).bit_length() if n > 1 else 1


def _resolve_column(table: ColumnTable, name: str, memo: dict) -> np.ndarray:
    """A physical or virtual (pair-lowered hi/lo, is-null) column as a
    host array. Virtual columns derived from STABLE (frozen, cached)
    base columns are memoized across queries — repeat filters over the
    same index version skip the 64-bit key derivation entirely."""
    from hyperspace_tpu.execution import device_cache as dc

    if _SEP not in name:
        return table.columns[table.schema.field(name).name]
    base, tag = name.split(_SEP, 1)
    if tag == "nul":
        valid = table.valid_mask(base)
        if dc.is_stable(valid):
            return dc.derived(("nul", id(valid)), (valid,), lambda: ~valid)
        return ~valid
    domain, word = tag[0], tag[1:]
    base_arr = table.columns[table.schema.field(base).name]
    key = (base.lower(), domain)
    u = memo.get(key)
    if u is None:
        if dc.is_stable(base_arr):
            u = dc.derived(
                ("u64", id(base_arr), domain), (base_arr,),
                lambda: _ordered_u64(base_arr, domain),
            )
        else:
            u = _ordered_u64(base_arr, domain)
        memo[key] = u
    if word == "hi":
        compute = lambda: (u >> np.uint64(32)).astype(np.uint32)  # noqa: E731
    else:
        compute = lambda: (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)  # noqa: E731
    if dc.is_stable(u):
        return dc.derived(("word", id(u), word), (u,), compute)
    return compute()


def _host_mask(table: ColumnTable, predicate: Expr) -> np.ndarray:
    """Vectorized numpy fallback: full 64-bit semantics + Kleene logic.
    Returns the definitely-true mask (what a SQL filter keeps)."""

    def resolve(name: str):
        return table.columns[table.schema.field(name).name]

    n_rows = table.num_rows

    def known_mask(e: Expr) -> np.ndarray:
        """True where every column input of `e` is non-null."""
        known = np.ones(n_rows, dtype=bool)
        for name in e.references():
            valid = table.valid_mask(name)
            if valid is not None:
                known = known & valid
        return known

    def tri(e: Expr):
        if isinstance(e, And):
            t1, f1 = tri(e.left)
            t2, f2 = tri(e.right)
            return t1 & t2, f1 | f2
        if isinstance(e, Or):
            t1, f1 = tri(e.left)
            t2, f2 = tri(e.right)
            return t1 | t2, f1 & f2
        if isinstance(e, Not):
            t, f = tri(e.child)
            return f, t
        if isinstance(e, IsNull):
            known = known_mask(e.child)
            return ~known, known  # IS NULL is never UNKNOWN
        if isinstance(e, _DictLut):
            v = e.lut[resolve(e.col.name)]
            known = known_mask(e)
            return v & known, ~v & known
        if isinstance(e, _StrColCmp):
            fn = {"eq": np.equal, "ne": np.not_equal, "lt": np.less,
                  "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal}[e.op]
            lv = e.lmap[resolve(e.left.name)]
            rv = e.rmap[resolve(e.right.name)]
            v = fn(lv, rv)
            known = known_mask(e)
            return v & known, ~v & known
        # Leaf comparison/expression: any null input makes it unknown.
        with np.errstate(all="ignore"):
            v = np.broadcast_to(np.asarray(evaluate(e, resolve, np), dtype=bool), (n_rows,))
        known = known_mask(e)
        return v & known, ~v & known

    t, _ = tri(predicate)
    return t


def eval_predicate_mask(
    table: ColumnTable, predicate: Expr, mesh=None, venue: str = "device"
) -> np.ndarray:
    """Evaluate the predicate; returns a host bool mask. The host venue
    is the exact numpy evaluation (_host_mask — the same one unliftable
    predicates already use). On device, with a mesh the row dimension is
    sharded across it (purely elementwise — zero collectives; the analog
    of the reference keeping full scan parallelism,
    FilterIndexRule.scala:114-120)."""
    predicate = translate_predicate(table, predicate)
    if venue == "host":
        return _host_mask(table, predicate)
    try:
        lowered = _lower(table, predicate)
    except _HostFallback:
        return _host_mask(table, predicate)

    lits: list = []
    struct = _structure_key(lowered, lits)
    names = sorted(lowered.references())

    n = table.num_rows
    n_pad = _pow2(n)
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from hyperspace_tpu.parallel.mesh import mesh_axes, mesh_size

        if mesh_size(mesh) > 1 and n_pad % mesh_size(mesh) == 0:
            sharding = NamedSharding(mesh, PartitionSpec(mesh_axes(mesh)))
    from hyperspace_tpu.execution.device_cache import device_put_padded

    arrays = []
    layout = []
    memo: dict = {}
    for name in names:
        arr = _resolve_column(table, name, memo)
        # Stable (frozen index-cache or derived) columns upload through
        # the device cache: repeat queries serve from HBM, no re-staging.
        arrays.append(device_put_padded(arr, n_pad, sharding))
        layout.append((name.lower(), arr.dtype.str))
    lit_args = [np.asarray(v) for v in lits]

    key = (struct, tuple(layout), tuple(a.dtype.str for a in lit_args), n_pad)
    with _MASK_FN_LOCK:
        fn = _MASK_FN_CACHE.get(key)
    if fn is None:
        lowered_names = [nm for nm, _ in layout]

        def raw(cols_tuple, lits_tuple, expr=lowered):
            cols = dict(zip(lowered_names, cols_tuple))
            t, _f = _eval3(expr, cols, iter(lits_tuple))
            return jnp.broadcast_to(t, (n_pad,))

        fn = jit(raw, key="ops.filter.mask")
        with _MASK_FN_LOCK:
            _MASK_FN_CACHE[key] = fn

    mask = fn(tuple(arrays), tuple(jnp.asarray(v) for v in lit_args))
    return np.asarray(to_host(mask)).astype(bool)[:n]


def apply_filter(
    table: ColumnTable, predicate: Expr, mesh=None, venue: str = "device"
) -> ColumnTable:
    if table.num_rows == 0:
        return table
    mask = eval_predicate_mask(table, predicate, mesh=mesh, venue=venue)
    return table.filter_mask(mask)
