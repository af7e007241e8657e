"""Grouped aggregation on device.

One of the engine-side operators the reference left to Spark
(SURVEY.md §2.2 — HashAggregateExec inside WholeStageCodegen); the TPU
build owns it. Group identity is factorized on host (tiny), the
reduction runs as one jitted segment-reduce on device, and only the
K-sized per-group results come back — aggregation queries never pay the
match/row readback.

Staging (docs/architecture.md "device data path"): the device venue
stages the base columns its AggSpecs read, each once, padded to a power
of two and uploaded through DEVICE_CACHE (once per version for stable,
frozen index-cache inputs), with their validity masks and the
identity-cached group-id pad. The jitted program evaluates the
channels from them — one value channel per distinct (expression, fn),
one count channel per distinct set of masks, a shared row count for
null-free inputs and count(*) — and reduces them; only the [C, K]
result comes back. An input the program cannot evaluate (a CASE, a
date part, a string extremum) is built on the host (agg_input) and
staged as one more column.

SQL semantics: null inputs are ignored by sum/min/max/mean and count(col);
count(*) counts rows; a group whose inputs are all null yields NULL
(validity mask); null group keys form their own group.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from hyperspace_tpu import stats
from hyperspace_tpu.compat import jit, to_host
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.obs import trace as obs_trace
from hyperspace_tpu.ops.join_agg import _DENSE_MAX_SEGMENTS, _dense_reduce
from hyperspace_tpu.plan.expr import BinOp, Col, Lit, MathFn, evaluate, expr_from_json
from hyperspace_tpu.schema import Schema


def _pow2(n: int) -> int:
    return 1 << max(int(n - 1).bit_length(), 0) if n > 1 else 1


#: Row width of the [n / w, w] blocks the dense reduction walks.
_DENSE_ROWS = 4096


@functools.partial(jit, static_argnames=("num_segments", "fns"))
def _segment_reduce_many(vals, gid, num_segments: int, fns: tuple):
    """One device program reducing several (value, fn) pairs over shared
    segment ids. vals: [A, n_pad]; returns [A, num_segments]."""
    outs = []
    with jax.named_scope("agg.segment_reduce"):
        for i, fn in enumerate(fns):
            v = vals[i]
            if fn == "sum":
                outs.append(jax.ops.segment_sum(v, gid, num_segments))
            elif fn == "min":
                outs.append(jax.ops.segment_min(v, gid, num_segments))
            elif fn == "max":
                outs.append(jax.ops.segment_max(v, gid, num_segments))
            else:
                raise ValueError(fn)
        return jnp.stack(outs)


@functools.partial(jit, static_argnames=("num_segments", "fns"))
def _dense_segment_reduce(vals, gid, num_segments: int, fns: tuple):
    """:func:`_segment_reduce_many` by one dense masked reduction per
    channel, as the fused join-aggregate reduces its groups
    (ops/join_agg._dense_reduce: K compare-select-adds a row, which a TPU
    fuses into the reduction)."""
    with jax.named_scope("agg.segment_reduce"):
        w = math.gcd(gid.shape[0], _DENSE_ROWS)
        return _dense_reduce(
            tuple(v.reshape(-1, w) for v in vals), gid.reshape(-1, w),
            num_segments, tuple((fn,) for fn in fns),
        )


@functools.lru_cache(maxsize=32)
def _make_sharded_segment_reduce(mesh, axes: tuple, num_segments: int, channels: tuple):
    """Mesh-distributed :func:`_channel_reduce`: the row dimension shards
    across devices, each shard builds its channels and reduces locally,
    and ONE collective per channel (psum for sums, pmin/pmax for
    extrema) combines the [C, K] partials — the distributed
    HashAggregate the reference gets from Spark's partial + final
    aggregation (SURVEY.md §2.2), expressed as XLA collectives over ICI.

    Invariant: every channel is a commutative device reduction over
    NUMERIC lanes — string inputs never reach here (the plan validator
    rejects sum/mean over string expressions, rule
    dtype-incompatible-aggregate)."""
    from jax.sharding import PartitionSpec as P

    from hyperspace_tpu.compat import shard_map

    fns = tuple(ch.fn for ch in channels)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes)),
        out_specs=P(None, None),
        check_vma=False,
    )
    def fn(cols, masks, gid):
        vals = _channel_values(cols, masks, channels, gid.shape[0])
        local = _segment_reduce_many.__wrapped__(vals, gid, num_segments, fns)
        outs = []
        with jax.named_scope("agg.segment_reduce"):
            for i, f in enumerate(fns):
                if f == "sum":
                    outs.append(jax.lax.psum(local[i], axes))
                elif f == "min":
                    outs.append(jax.lax.pmin(local[i], axes))
                elif f == "max":
                    outs.append(jax.lax.pmax(local[i], axes))
                else:
                    raise ValueError(f)
            return jnp.stack(outs)

    return jit(fn, key="ops.aggregate.sharded_reduce")


def _dense_codes(arr: np.ndarray, valid) -> tuple[np.ndarray, int] | None:
    """O(n) factorization for integer columns whose value range is small
    relative to n (join keys, dict codes, dates): rank via a presence
    table instead of np.unique's O(n log n) argsort. Returns
    (codes [n] int64 with 0 reserved for nulls, cardinality incl. the
    null slot) in VALUE-sorted code order, or None when out of range."""
    if not np.issubdtype(arr.dtype, np.integer) or len(arr) == 0:
        return None
    vv = arr if valid is None else arr[valid]
    if len(vv) == 0:
        return np.zeros(len(arr), np.int64), 1
    lo, hi = int(vv.min()), int(vv.max())
    span = hi - lo + 1
    if span > max(4 * len(arr), 1 << 16):
        return None
    offs = arr.astype(np.int64) - lo
    if valid is not None:
        offs = np.where(valid, offs, 0)
    present = np.zeros(span, dtype=bool)
    present[offs[valid] if valid is not None else offs] = True
    ids = np.cumsum(present, dtype=np.int64)  # 1-based rank among present
    codes = ids[offs]
    if valid is not None:
        codes[~valid] = 0
    return codes, int(present.sum()) + 1


def _column_codes(table: ColumnTable, c: str) -> tuple[np.ndarray, int]:
    """(codes [n] int64 with 0 = null, cardinality) for one group column,
    codes in value-sorted order."""
    f = table.schema.field(c)
    arr = table.columns[f.name]
    if arr.ndim != 1:
        raise HyperspaceError(f"cannot group by vector column {c!r}")
    valid = table.valid_mask(c)
    dense = _dense_codes(arr, valid)
    if dense is not None:
        return dense
    _, inv = np.unique(arr, return_inverse=True)
    inv = inv.astype(np.int64) + 1
    card = int(inv.max()) + 1 if len(inv) else 1
    if valid is not None:
        inv[~valid] = 0
    return inv, card


def _compress(codes: np.ndarray) -> tuple[np.ndarray, int, np.ndarray]:
    """Combined codes → (gid [n] in [0, K), K, first_idx [K]) with gid
    order following code order."""
    dense = _dense_codes(codes, None)
    if dense is not None:
        gid = dense[0] - 1  # no nulls at this stage; drop the reserved 0
        k = dense[1] - 1
    else:
        uniq, gid = np.unique(codes, return_inverse=True)
        gid = gid.reshape(-1).astype(np.int64)
        k = len(uniq)
    # Any representative row per group works (the key values are equal);
    # a vectorized last-write gives one without a sort.
    rep = np.empty(k, dtype=np.int64)
    rep[gid] = np.arange(len(gid), dtype=np.int64)
    return gid, k, rep


def group_ids(table: ColumnTable, group_by: list[str]):
    """Host factorization of the group-key tuples. Returns
    (gid [n] int64, K, first_idx [K] — a representative row per group).
    O(n) for integer/dict/date keys of reasonable range (the common
    case: join keys, flags); np.unique fallback otherwise."""
    n = table.num_rows
    if not group_by:
        return np.zeros(n, np.int64), 1, np.zeros(1 if n else 0, np.int64)
    if len(group_by) == 1 and n:
        # Dictionary-coded string group column with no nulls: the codes
        # already ARE compact ranks in value order (the dictionary is
        # sorted) — one bincount decides whether any dictionary entry is
        # unused, and the whole multi-pass rank machinery collapses to at
        # most one small-table gather (at SF100 this was ~40% of the
        # fused join-aggregate's wall on BOTH venues).
        f = table.schema.field(group_by[0])
        if f.is_string and table.valid_mask(group_by[0]) is None:
            codes = np.asarray(table.columns[f.name])
            k_dict = len(table.dictionaries[f.name])
            if k_dict:
                cnt = np.bincount(codes, minlength=k_dict)
                used = cnt > 0
                if used.all():
                    gid = codes.astype(np.int64, copy=False)
                    k = k_dict
                else:
                    lookup = np.cumsum(used, dtype=np.int64) - 1
                    gid = lookup[codes]
                    k = int(used.sum())
                rep = np.empty(k, dtype=np.int64)
                rep[gid] = np.arange(n, dtype=np.int64)
                return gid, k, rep
    codes0, card0 = _column_codes(table, group_by[0])
    combined = codes0
    total = card0
    for c in group_by[1:]:
        codes, card = _column_codes(table, c)
        if total * card >= np.iinfo(np.int64).max:
            raise HyperspaceError(
                f"group-by key cardinalities overflow the int64 code space"
            )
        combined = combined * np.int64(card) + codes
        total *= card
    return _compress(combined)


def _case_input(table: ColumnTable, e) -> tuple[np.ndarray, np.ndarray | None]:
    """CASE WHEN inside an aggregate: conditions evaluate with FULL
    predicate semantics (string literals, 3-valued nulls — a null
    condition does not take its branch) via the filter mask machinery;
    value legs are numeric. Validity follows the branch actually taken."""
    from hyperspace_tpu.ops.filter import eval_predicate_mask

    out, valid = _expr_input(table, e.default)
    out = _full(np.asarray(out, dtype=np.float64), table.num_rows)
    for cond, val in reversed(e.branches):
        m = eval_predicate_mask(table, cond)
        v, vvalid = _expr_input(table, val)
        v = _full(np.asarray(v, dtype=np.float64), table.num_rows)
        out = np.where(m, v, out)
        if valid is not None or vvalid is not None:
            va = np.ones(table.num_rows, bool) if valid is None else valid
            vb = np.ones(table.num_rows, bool) if vvalid is None else vvalid
            valid = np.where(m, vb, va)
    return out, valid


def _full(vals: np.ndarray, n: int) -> np.ndarray:
    return np.full(n, vals) if vals.ndim == 0 else vals


def _expr_input(table: ColumnTable, e) -> tuple[np.ndarray, np.ndarray | None]:
    """Recursive (values, validity) for an aggregate expression. Case
    nodes keep their branch-following validity ANYWHERE in the tree (a
    null condition takes the ELSE leg, it does not poison the row);
    everything else ANDs the validity of what it actually reads. Values
    may be 0-d (literals) until the caller broadcasts."""
    from hyperspace_tpu.plan.expr import Case, Lit as _Lit

    if isinstance(e, Case):
        return _case_input(table, e)
    if isinstance(e, Col):
        f = table.schema.field(e.name)
        if f.is_string:
            raise HyperspaceError(f"aggregate expression over string column {f.name!r}")
        return table.columns[f.name], table.valid_mask(e.name)
    if isinstance(e, _Lit):
        return np.asarray(e.value), None
    from hyperspace_tpu.plan.expr import DatePart as _DatePart
    from hyperspace_tpu.plan.expr import eval_date_part

    if isinstance(e, _DatePart):
        vals, valid = _expr_input(table, e.child)
        return eval_date_part(e.part, _full(np.asarray(vals), table.num_rows), np), valid
    from hyperspace_tpu.plan.expr import BinOp as _BinOp

    if isinstance(e, _BinOp):
        a, av = _expr_input(table, e.left)
        b, bv = _expr_input(table, e.right)
        vals = np.asarray(
            evaluate(
                _BinOp(e.op, Col("__a__"), Col("__b__")),
                lambda name: a if name == "__a__" else b,
                np,
            )
        )
        if av is None:
            valid = bv
        elif bv is None:
            valid = av
        else:
            valid = av & bv
        return vals, valid
    from hyperspace_tpu.plan.expr import MathFn as _MathFn

    if isinstance(e, _MathFn):
        vals, valid = _expr_input(table, e.child)
        out = evaluate(
            _MathFn(e.fn, Col("__a__")), lambda name: np.asarray(vals), np
        )
        return np.asarray(out), valid
    raise HyperspaceError(f"cannot aggregate over expression {type(e).__name__}")


def _numeric_input(table: ColumnTable, e) -> tuple[np.ndarray, np.ndarray | None]:
    """Full-length numeric (values, validity) for an aggregate expression."""
    vals, valid = _expr_input(table, e)
    return _full(vals, table.num_rows), valid


def agg_input(table: ColumnTable, spec) -> tuple[np.ndarray, np.ndarray | None, bool]:
    """(values, valid mask or None, is_string_codes) for one AggSpec."""
    from hyperspace_tpu.plan.expr import Case

    if spec.expr is None:  # count(*)
        return np.ones(table.num_rows, np.int64), None, False
    if isinstance(spec.expr, Case):
        vals, valid = _case_input(table, spec.expr)
        return vals, valid, False
    if isinstance(spec.expr, Col):
        f = table.schema.field(spec.expr.name)
        valid = table.valid_mask(spec.expr.name)
        if f.is_string:
            if spec.fn not in ("min", "max", "count"):
                raise HyperspaceError(f"{spec.fn} over string column {f.name!r}")
            return table.columns[f.name], valid, True
        return table.columns[f.name], valid, False
    vals, valid = _numeric_input(table, spec.expr)
    return vals, valid, False


def aggregate_arrays_host(
    inputs: list[tuple[np.ndarray, np.ndarray | None, str]],
    gid: np.ndarray,
    num_groups: int,
):
    """Host (numpy) venue of the segment reduce: bincount sums and
    sorted-reduceat min/max in exact float64. The inputs are host-resident
    and the [A, K] result is tiny, so on slow-transfer deployments (or
    chips without native f64) this beats uploading every channel to the
    device; semantics are pinned identical to the device venue's
    (:func:`_reduce_channels`)."""
    n = len(gid)
    order = None
    group_rows = np.bincount(gid, minlength=num_groups).astype(np.int64)
    results: list[np.ndarray] = []
    counts: list[np.ndarray] = []
    for vals, valid, fn in inputs:
        v = np.asarray(vals, dtype=np.float64)
        if fn == "sum":
            if valid is not None:
                v = np.where(valid, v, 0.0)
            res = np.bincount(gid, weights=v, minlength=num_groups)
        else:
            identity = np.inf if fn == "min" else -np.inf
            if order is None:
                order = np.argsort(gid, kind="stable")
                starts = np.searchsorted(gid[order], np.arange(num_groups))
            sv = v[order]
            if valid is not None:
                sv = np.where(valid[order], sv, identity)
            nonempty = group_rows > 0
            res = np.full(num_groups, identity)
            if n and nonempty.any():
                op = np.minimum if fn == "min" else np.maximum
                # reduceat only over NON-EMPTY groups: an empty group's
                # start equals the next group's, so including it (or
                # clamping start == n) would shrink a neighbour's segment.
                # A non-empty group's segment runs to the next listed
                # start, which is exactly its true end.
                res[nonempty] = op.reduceat(sv, starts[nonempty])
        cnt = (
            group_rows.astype(np.float64)
            if valid is None
            else np.bincount(gid, weights=valid.astype(np.float64), minlength=num_groups)
        )
        results.append(res)
        counts.append(cnt)
    return np.stack(results), np.stack(counts)


@dataclasses.dataclass(frozen=True)
class _Channel:
    """One reduction channel of the device program: ``fn`` over the
    float64 values of ``expr``, each row that a validity input of
    ``mask`` marks null set to ``fn``'s identity. A None ``expr`` is a
    count: the AND of its masks, or 1 a row. ``key``, the expression's
    JSON over input positions, is what the compile key and the
    deduplication compare; ``expr`` rides along unhashed."""

    fn: str
    key: str
    mask: tuple
    expr: object = dataclasses.field(default=None, compare=False, hash=False)


_IDENTITY = {"sum": 0.0, "min": np.inf, "max": -np.inf}


def _device_expr(table: ColumnTable, e) -> bool:
    """Arithmetic (BinOp, MathFn) over int64/float64 columns and numeric
    literals: the dtypes on which numpy's and XLA's promotions agree, so
    the program computes the host's values."""
    if isinstance(e, Col):
        f = table.schema.field(e.name)
        a = table.columns[f.name]
        return not f.is_string and a.ndim == 1 and a.dtype in (np.int64, np.float64)
    if isinstance(e, Lit):
        return isinstance(e.value, (int, float)) and not isinstance(e.value, bool)
    if isinstance(e, BinOp):
        return _device_expr(table, e.left) and _device_expr(table, e.right)
    if isinstance(e, MathFn):
        return _device_expr(table, e.child)
    return False


def _device_spec(table: ColumnTable, spec) -> bool:
    """Whether the device program builds this AggSpec's channels: a count
    reads only validity; other fns a bare numeric column (any width,
    cast to float64 as the host casts it) or :func:`_device_expr`. A
    CASE, a date part or a string extremum is built on the host."""
    e = spec.expr
    if spec.fn == "count":
        return e is None or isinstance(e, Col) or _device_expr(table, e)
    if spec.fn not in _IDENTITY and spec.fn != "mean":
        return False
    if isinstance(e, Col):
        f = table.schema.field(e.name)
        a = table.columns[f.name]
        return not f.is_string and a.ndim == 1 and a.dtype.kind in "biuf"
    return _device_expr(table, e)


def _positional(d: dict, col_pos) -> dict:
    """An expression's JSON with each column named by its input position."""
    if d.get("type") == "col":
        return {"type": "col", "name": str(col_pos(d["name"]))}
    return {k: _positional(v, col_pos) if isinstance(v, dict) else v for k, v in d.items()}


def _plan_channels(table: ColumnTable, aggs: list):
    """The deduplicated channels of a device-venue aggregate.

    Returns (arrays, masks, channels, slots, n_host): the host arrays
    the program reads (each base column once, then each host-built
    channel), the validity masks, the channels, per AggSpec the
    positions of its (value, count) channels, and how many AggSpecs were
    built on the host. One value channel per distinct
    (expression, fn); one count channel per distinct set of masks, so
    every null-free input and count(*) share the group's row count."""
    import json

    arrays: list[np.ndarray] = []
    masks: list[np.ndarray] = []
    col_at: dict[str, int] = {}
    mask_at: dict[str, int] = {}
    channels: dict[_Channel, int] = {}

    def col_pos(name: str) -> int:
        f = table.schema.field(name)
        if f.name not in col_at:
            col_at[f.name] = len(arrays)
            arrays.append(table.columns[f.name])
        return col_at[f.name]

    def mask_pos(names) -> tuple:
        out = []
        for nm in sorted(names):
            f = table.schema.field(nm)
            m = table.validity.get(f.name)
            if m is not None:
                if f.name not in mask_at:
                    mask_at[f.name] = len(masks)
                    masks.append(m)
                out.append(mask_at[f.name])
        return tuple(sorted(out))

    def channel(fn: str, expr, mask: tuple) -> int:
        key = "" if expr is None else json.dumps(expr.to_json(), sort_keys=True)
        return channels.setdefault(_Channel(fn, key, mask, expr), len(channels))

    slots: list[tuple[int, int]] = []
    n_host = 0
    for spec in aggs:
        fn = "sum" if spec.fn in ("count", "mean") else spec.fn
        if _device_spec(table, spec):
            mask = mask_pos(spec.references()) if spec.expr is not None else ()
            cnt = channel("sum", None, mask)
            if spec.fn == "count":
                slots.append((cnt, cnt))
                continue
            expr = expr_from_json(_positional(spec.expr.to_json(), col_pos))
        else:
            # The host build (agg_input): a CASE, a date part, a string.
            n_host += 1
            vals, valid, fn = prepared_agg_input(table, spec)
            expr = Col(str(len(arrays)))
            arrays.append(np.asarray(vals))
            mask = ()
            if valid is not None:
                mask = (len(masks),)
                masks.append(valid)
            cnt = channel("sum", None, mask)
        slots.append((channel(fn, expr, mask), cnt))
    return arrays, masks, tuple(channels), slots, n_host


def _channel_values(cols: tuple, masks: tuple, channels: tuple, n: int) -> list:
    """Each channel's [n] float64 values, traced inside the reduction
    program from the staged inputs."""
    env = {str(i): c for i, c in enumerate(cols)}
    out = []
    with jax.named_scope("agg.channels"):
        for ch in channels:
            valid = None
            for m in ch.mask:
                valid = masks[m] if valid is None else valid & masks[m]
            if ch.expr is None:
                v = jnp.ones(n, jnp.float64) if valid is None else valid.astype(jnp.float64)
            else:
                v = jnp.asarray(evaluate(ch.expr, env.__getitem__, jnp)).astype(jnp.float64)
                v = jnp.broadcast_to(v, (n,))
                if valid is not None:
                    v = jnp.where(valid, v, _IDENTITY[ch.fn])
            out.append(v)
    return out


@functools.partial(jit, static_argnames=("num_segments", "channels", "reduce"))
def _channel_reduce(cols, masks, gid, num_segments: int, channels: tuple, reduce: str):
    """[C, num_segments]: the channels built from the staged inputs, then
    reduced densely (``reduce`` 'dense') or by segment scatter."""
    vals = _channel_values(cols, masks, channels, gid.shape[0])
    fns = tuple(ch.fn for ch in channels)
    if reduce == "dense":
        return _dense_segment_reduce.__wrapped__(vals, gid, num_segments, fns)
    return _segment_reduce_many.__wrapped__(vals, gid, num_segments, fns)


def _reduce_channels(
    arrays: list, masks: list, channels: tuple, gid: np.ndarray, num_groups: int, mesh=None
) -> np.ndarray:
    """[C, num_groups] float64: each channel reduced over the group ids.
    Every input array is written once into an n_pad-long buffer (a power
    of two, so one program serves every filtered row count) and uploaded
    through DEVICE_CACHE, once per version for a stable table; the
    channels are built inside the program. With a multi-device mesh the
    rows shard across devices (partial reduce + one collective per
    channel)."""
    from hyperspace_tpu.execution import device_cache as dcache
    from hyperspace_tpu.parallel.mesh import mesh_axes, mesh_size

    # 53-bit accumulation on the persistent x64 worker thread — the
    # process-wide flag is never touched (round 1 weakness #8).
    from hyperspace_tpu.parallel.x64 import run_x64

    d = mesh_size(mesh) if mesh is not None else 1
    n = len(gid)
    n_pad = _pow2(max(n, 1))
    if d > 1 and n_pad % d:
        n_pad = ((n_pad + d - 1) // d) * d
    k_seg = _pow2(num_groups + 1)  # +1 dead segment for pads

    def build_gid_pad() -> np.ndarray:
        g = np.full(n_pad, num_groups, np.int32)
        g[:n] = gid
        return g

    sharding = None
    if d > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P(mesh_axes(mesh)))
    upload = n_pad * (4 + sum(a.dtype.itemsize for a in (*arrays, *masks)))
    with obs_trace.span("agg.channels", channels=len(channels), upload_bytes=upload):
        if dcache.is_stable(gid):
            gid_p = dcache.derived(
                ("gidpad1", id(gid), n_pad, num_groups), (gid,), build_gid_pad
            )
        else:
            gid_p = build_gid_pad()
        staged = run_x64(
            lambda: [
                dcache.device_put_padded(a, n_pad, sharding)
                for a in (*arrays, *masks, gid_p)
            ]
        )
    cols, vmasks, gid_d = (
        tuple(staged[: len(arrays)]), tuple(staged[len(arrays) : -1]), staged[-1]
    )
    if d > 1:
        stats.increment("device.kernel.segment_reduce_sharded")
        path = "sharded"
        reduce_fn = _make_sharded_segment_reduce(mesh, mesh_axes(mesh), k_seg, channels)
    else:
        stats.increment("device.kernel.segment_reduce_lax")
        path = "lax"
        # An accelerator's float64 segment scatter costs about 110 ns
        # a row whatever K (ops/join_agg.py); the dense reduction K
        # fused compare-select-adds. XLA:CPU's scatter adds rows in
        # order, the host venue's bincount order bit for bit: the CPU
        # keeps it.
        dense = k_seg <= _DENSE_MAX_SEGMENTS and jax.default_backend() != "cpu"
        reduce_fn = functools.partial(
            _channel_reduce, num_segments=k_seg, channels=channels,
            reduce="dense" if dense else "scatter",
        )
    with obs_trace.span("agg.reduce", path=path):
        out = np.asarray(run_x64(lambda: to_host(reduce_fn(cols, vmasks, gid_d))))
    return out[:, :num_groups]


def finalize_agg_values(vals: np.ndarray, empty: np.ndarray, dtype) -> np.ndarray:
    """Per-group aggregate values → output column. Float outputs keep
    legitimately non-finite results (NaN inputs, overflowing sums —
    Spark/the reference return NaN/Infinity here); only empty (all-NULL)
    groups are zero-backed, and their validity mask marks them NULL.
    Integer outputs coerce non-finite before the cast (undefined
    otherwise; such values only arise for empty groups anyway)."""
    if np.dtype(dtype).kind == "f":
        safe = np.where(empty, 0, vals)
    else:
        safe = np.where(empty, 0, np.where(np.isfinite(vals), vals, 0))
    return safe.astype(dtype)


def _spec_identity(table: ColumnTable, spec):
    """(refs, id-parts) over every array one AggSpec reads — the
    identity key of its prepared channels. (None, None) when any input
    is unstable (per-query table: nothing to memoize against)."""
    from hyperspace_tpu.execution import device_cache as dc

    names = sorted({r.lower() for r in spec.references()}) if spec.expr is not None else []
    refs: list = []
    parts: list = []
    for nm in names:
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


def prepared_agg_input(table: ColumnTable, spec):
    """(vals, valid, fn) channels for one AggSpec — the masked value
    array, its validity and the reduce fn — memoized per (expression,
    input identity) for stable tables so repeat queries skip the channel
    prep entirely."""
    import json

    from hyperspace_tpu.execution import device_cache as dc

    def build_raw():
        vals, valid, _is_str = agg_input(table, spec)
        fn = {"count": "sum", "mean": "sum"}.get(spec.fn, spec.fn)
        if spec.fn == "count":
            vals = np.ones(table.num_rows, np.float64) if valid is None else valid.astype(np.float64)
            valid = None
        return vals, valid, fn

    refs, parts = _spec_identity(table, spec)
    if refs is None:
        return build_raw()
    if spec.expr is None:
        # count(*): the channel depends only on the row count.
        key = ("aggprep", "count_star", table.num_rows)
    else:
        key = (
            "aggprep",
            spec.fn,
            json.dumps(spec.expr.to_json(), sort_keys=True),
            table.num_rows,
            parts,
        )

    def build():
        vals, valid, fn = build_raw()
        vals = dc.freeze(np.asarray(vals))
        if valid is not None:
            valid = dc.freeze(np.asarray(valid))
        nbytes = int(vals.nbytes) + (int(valid.nbytes) if valid is not None else 0)
        return (vals, valid, fn), nbytes

    return dc.HOST_DERIVED.get_or_build(key, refs, build)


def aggregate_table(
    table: ColumnTable, group_by: list[str], aggs: list, out_schema: Schema,
    venue: str = "device",
    mesh=None,
    groups: tuple | None = None,
) -> ColumnTable:
    """Execute a grouped aggregation over a materialized table.
    `groups` optionally passes a precomputed (gid, K, first_idx)
    factorization so callers sharing one key layout across several
    aggregations (distinct expansion, grouping sets) don't re-factorize."""
    gid, k, first_idx = groups if groups is not None else group_ids(table, group_by)

    string_dicts: dict[int, np.ndarray] = {}
    for i, spec in enumerate(aggs):
        if isinstance(spec.expr, Col):
            f = table.schema.field(spec.expr.name)
            if f.is_string:
                string_dicts[i] = table.dictionaries[f.name]
    if venue == "host":
        inputs = [prepared_agg_input(table, spec) for spec in aggs]
    else:
        arrays, masks, channels, slots, n_host = _plan_channels(table, aggs)

    if k == 0:
        return ColumnTable.empty(out_schema)
    if not aggs:  # DISTINCT: group keys only, nothing to reduce
        results = counts = np.zeros((0, k))
    elif venue == "host":
        results, counts = aggregate_arrays_host(inputs, gid, k)
    else:
        if len(aggs) > n_host:
            stats.increment("device.kernel.agg_channels_device", len(aggs) - n_host)
        if n_host:
            stats.increment("device.kernel.agg_channels_host", n_host)
        out = _reduce_channels(arrays, masks, channels, gid, k, mesh)
        results = out[[v for v, _c in slots]]
        counts = out[[c for _v, c in slots]]

    cols: dict[str, np.ndarray] = {}
    dicts: dict[str, np.ndarray] = {}
    validity: dict[str, np.ndarray] = {}
    for c in group_by:
        f = table.schema.field(c)
        out_f = out_schema.field(c)
        cols[out_f.name] = table.columns[f.name][first_idx]
        if f.name in table.dictionaries:
            dicts[out_f.name] = table.dictionaries[f.name]
        gv = table.valid_mask(c)
        if gv is not None:
            validity[out_f.name] = gv[first_idx]
    for i, spec in enumerate(aggs):
        out_f = out_schema.field(spec.alias)
        res, cnt = results[i], counts[i]
        if spec.fn == "count":
            cols[out_f.name] = res.astype(np.int64)
            continue
        if spec.fn == "mean":
            with np.errstate(invalid="ignore", divide="ignore"):
                vals = res / cnt
        else:
            vals = res
        empty = cnt == 0  # all inputs null ⇒ NULL result
        if i in string_dicts:
            codes = np.where(empty, 0, vals).astype(np.int32)
            cols[out_f.name] = codes
            dicts[out_f.name] = string_dicts[i]
        else:
            cols[out_f.name] = finalize_agg_values(vals, empty, out_f.device_dtype)
        if empty.any():
            validity[out_f.name] = ~empty
    return ColumnTable(out_schema, cols, dicts, validity)
