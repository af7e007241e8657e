"""Fused join + aggregation: aggregate over an equi-join WITHOUT
materializing the joined pairs.

The expansion phase of a sort-merge join emits one (left, right) index
pair per match — for full-table TPC-H joins that is the whole output and
its readback/gather dominates. But every standard aggregate over the
join decomposes over each primary row's match RUN [st_i, en_i) in the
sorted secondary side:

    count(*)                += (en_i - st_i)                per primary row
    sum(primary expr v)     += v_i * (en_i - st_i)
    sum(secondary expr u)   += P[en_i] - P[st_i]            (P = prefix sum)

so the aggregation needs only the run bounds (two searchsorteds — the
count phase the join already runs) plus cumsum/gather/segment-sum, all
on device, and downloads K per-group scalars instead of millions of
pairs. Runs under scoped x64 (jax.enable_x64) for 53-bit accumulation;
the global flag is never touched.

Fused kernel ladder (docs/architecture.md "device data path"): with
``hyperspace.device.fusedKernels`` = auto and an eligible shape, the run
bounds come from the tiled Pallas searchsorted
(ops/sortkeys.pallas_run_bounds — the secondary row resident in VMEM,
one vectorized compare-and-count per tile) and feed the same lax
epilogue; bounds are integers, so results are byte-identical to the
all-lax path by construction. Ineligible shapes take the lax
searchsorted (`device.kernel.fused`/`device.kernel.fallbacks` count the
split); a lowering failure of an eligible shape raises.

Group reduction: each channel's per-row weights fold into [C, K] group
results in one of two ways, chosen by the padded group count K alone.
Up to ``_DENSE_MAX_SEGMENTS`` every channel reduces densely over all
primary rows at once, ``out[c, g] = sum of where(gid == g, w[c], 0)``
(the +-inf identity for extrema): K compare-select-adds a row, which
XLA fuses into the reduction on a TPU. Above it each channel keeps its
per-bucket segment scatter, whose cost on a v5e (about 110 ns a row in
float64) does not depend on K. The bound comes from timing both
programs on a v5e at the SF1 report's shape (PERF.md).
``device.kernel.dense_reduce`` / ``device.kernel.scatter_reduce`` count
the split. Extrema, and integral sums whose partial sums the float64
holds exactly, agree bit for bit on both paths; other float sums differ
only in summation order.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from hyperspace_tpu import stats
from hyperspace_tpu.compat import jit, to_host


def _seg_scan_extremum(vals, new_seg, op):
    """Segmented inclusive prefix min/max along the last axis: the scan
    restarts where `new_seg` is True. Standard associative segmented-scan
    operator — maps to one `lax.associative_scan` (log-depth on device)."""

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, op(va, vb))

    _, out = jax.lax.associative_scan(comb, (new_seg, vals), axis=-1)
    return out


#: Largest padded group count (``num_segments``) whose channels reduce
#: densely. On a v5e at the SF1 report's shape (200 x 31,104 rows, five
#: channels) the dense program beats the scatters 7.2x at K = 64, 2.5x
#: at 4,096 and only 1.07x at 16,384, so the crossover lies just above.
_DENSE_MAX_SEGMENTS = 4096
#: (group, row) pairs one step of the dense reduction compares: a bound
#: on the [K, rows] mask that XLA:CPU materializes (the TPU fuses it).
_DENSE_TILE_ELEMS = 1 << 22


def _reduce_op(kind: str) -> str:
    """The reduction a channel kind folds its per-row weights with."""
    if kind.endswith("min"):
        return "min"
    if kind.endswith("max"):
        return "max"
    return "sum"


def _bucket_weights(pkb, skb, pvb, svb, stb, enb, channels: tuple):
    """The C per-row weights [Lp] of one bucket given the run bounds
    [stb, enb): each primary row's contribution to every channel, with
    pads and rows that matched nothing at their reduction's identity (0
    for sums, +-inf for extrema). The named scopes label each stage's
    device ops in a profile."""
    bounds = functools.partial(jax.named_scope, "join_agg.bounds")
    gather = functools.partial(jax.named_scope, "join_agg.gather")
    with bounds():
        real = pkb < jnp.iinfo(pkb.dtype).max
        matched = real & (enb > stb)
        runlen = jnp.where(real, enb - stb, 0).astype(jnp.float64)
    p_prefix = None
    if svb.shape[0] and any(ch[0] == "s" for ch in channels):
        with gather():
            p_prefix = jnp.concatenate(
                [jnp.zeros((svb.shape[0], 1), svb.dtype), jnp.cumsum(svb, axis=-1)],
                axis=-1,
            )
    new_key = None
    if any(ch[0] in ("smin", "smax") for ch in channels):
        with gather():
            new_key = jnp.concatenate(
                [jnp.ones(1, bool), skb[1:] != skb[:-1]]
            )
    ws = []
    for ch in channels:
        kind = ch[0]
        if kind == "star":
            ws.append(runlen)
        elif kind == "p":
            ws.append(pvb[ch[1]] * runlen)
        elif kind == "s":
            with gather():
                pj = p_prefix[ch[1]]
                ws.append(jnp.where(real, pj[enb] - pj[stb], 0.0))
        else:
            is_min = kind.endswith("min")
            ident = jnp.inf if is_min else -jnp.inf
            with gather():
                if kind[0] == "p":
                    ws.append(jnp.where(matched, pvb[ch[1]], ident))
                else:
                    m = _seg_scan_extremum(
                        svb[ch[1]], new_key, jnp.minimum if is_min else jnp.maximum
                    )
                    ws.append(jnp.where(matched, m[jnp.maximum(enb - 1, 0)], ident))
    return tuple(ws)


#: Per reduction: (fold along axes, combine two partials, identity).
_FOLD = {
    "sum": (jnp.sum, jnp.add, 0.0),
    "min": (jnp.min, jnp.minimum, jnp.inf),
    "max": (jnp.max, jnp.maximum, -jnp.inf),
}
_SEGMENT_REDUCE = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def _dense_reduce(ws, gid, num_segments: int, channels: tuple, per_bucket: bool = False):
    """[C, K] from the channel weights ws (each [B, Lp]) and group ids
    gid [B, Lp]: ``op over rows of where(gid == g, w[c], identity)``, a
    fori_loop over blocks of whole buckets (``blk`` divides B) that keeps
    each block's mask within ``_DENSE_TILE_ELEMS`` pairs. Ids outside
    [0, K) fall in no group, as with the scatter. With `per_bucket`,
    [C, B, K]: each bucket's rows reduced into its own K segments."""
    n_b, n_rows = gid.shape
    cap = max(1, _DENSE_TILE_ELEMS // max(n_rows * num_segments, 1))
    blk = max((d for d in range(1, min(n_b, cap) + 1) if n_b % d == 0), default=1)
    seg = jnp.arange(num_segments, dtype=gid.dtype)[:, None, None]
    folds = [_FOLD[_reduce_op(ch[0])] for ch in channels]

    def step(i, acc):
        def block(a):
            return jax.lax.dynamic_slice_in_dim(a, i * blk, blk)

        hit = block(gid) == seg  # [K, blk, Lp]
        if per_bucket:
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    a, fold(jnp.where(hit, block(w), ident), axis=2).T, i * blk, 0
                )
                for a, w, (fold, _, ident) in zip(acc, ws, folds)
            )
        return tuple(
            combine(a, fold(jnp.where(hit, block(w), ident), axis=(1, 2)))
            for a, w, (fold, combine, ident) in zip(acc, ws, folds)
        )

    shape = (n_b, num_segments) if per_bucket else num_segments
    init = tuple(jnp.full(shape, ident, w.dtype) for w, (_, _, ident) in zip(ws, folds))
    return jnp.stack(jax.lax.fori_loop(0, n_b // blk, step, init))


def _scatter_reduce(ws, gid, num_segments: int, channels: tuple):
    """[C, K] by one vmapped segment scatter per channel and bucket,
    folded across buckets (a group's rows span buckets)."""
    ops = [_reduce_op(ch[0]) for ch in channels]

    def one(wb, gidb):
        return [_SEGMENT_REDUCE[op](w, gidb, num_segments) for w, op in zip(wb, ops)]

    per_bucket = jax.vmap(one)(ws, gid)
    return jnp.stack([_FOLD[op][0](p, axis=0) for p, op in zip(per_bucket, ops)])


_REDUCE = {
    "dense": _dense_reduce,
    "scatter": _scatter_reduce,
    "bucket_dense": functools.partial(_dense_reduce, per_bucket=True),
}


def _reduce(ws, gid, num_segments: int, channels: tuple, reduce: str):
    """Fold every bucket's channel weights: ``reduce`` 'dense' or
    'scatter' into [C, num_segments] global groups, 'bucket_dense' into
    [C, B, num_segments] groups local to each bucket (``gid`` then holds
    bucket-local ids)."""
    with jax.named_scope("join_agg.segment_sum"):
        return _REDUCE[reduce](ws, gid, num_segments, channels)


@functools.partial(jit, static_argnames=("num_segments", "channels", "reduce"))
def _fused_join_agg(
    pk, sk, pvals, svals, gid, num_segments: int, channels: tuple, reduce: str
):
    """pk/sk: [B, Lp]/[B, Ls] per-bucket sorted int32 codes (pads carry
    the dtype max). pvals [Ap, B, Lp] / svals [As, B, Ls]: float64
    per-row channel values (nulls and pads pre-zeroed for sum channels,
    pre-set to the ±inf identity for extremum channels). gid [B, Lp]:
    group ids (pads → num_segments-1). channels: ('star',) | ('p'|'s', j)
    sum channels | ('pmin'|'pmax'|'smin'|'smax', j) run-extremum channels
    (an equi-join match run IS one key segment of the sorted secondary,
    so its extremum is the segmented prefix scan value at the run end).
    reduce: 'dense' | 'scatter' (:func:`_reduce`). Returns
    [len(channels), num_segments] float64."""

    def one(pkb, skb, pvb, svb):
        with jax.named_scope("join_agg.bounds"):
            st = jnp.searchsorted(skb, pkb, side="left").astype(jnp.int32)
            en = jnp.searchsorted(skb, pkb, side="right").astype(jnp.int32)
        return _bucket_weights(pkb, skb, pvb, svb, st, en, channels)

    ws = jax.vmap(one)(pk, sk, pvals.transpose(1, 0, 2), svals.transpose(1, 0, 2))
    return _reduce(ws, gid, num_segments, channels, reduce)


@functools.partial(jit, static_argnames=("num_segments", "channels", "reduce"))
def _fused_join_agg_bounds(
    pk, sk, st, en, pvals, svals, gid, num_segments: int, channels: tuple, reduce: str
):
    """Same program as :func:`_fused_join_agg` with the run bounds
    precomputed (the Pallas run-bounds kernel feeds this variant)."""

    def one(pkb, skb, stb, enb, pvb, svb):
        return _bucket_weights(pkb, skb, pvb, svb, stb, enb, channels)

    ws = jax.vmap(one)(pk, sk, st, en, pvals.transpose(1, 0, 2), svals.transpose(1, 0, 2))
    return _reduce(ws, gid, num_segments, channels, reduce)


def bucket_local_ids(gid: np.ndarray, num_groups: int):
    """(local ids [B, Lp] int32, K_local, bucket [num_groups], slot
    [num_groups]) when every group's rows lie in one bucket of the
    padded group ids `gid` [B, Lp] (pads hold `num_groups`), else None.
    Each group gets the rank of its id among its bucket's groups; pads
    get the dead segment K_local - 1, past every bucket's groups."""
    n_b = gid.shape[0]
    real = gid < num_groups
    rows_bucket = np.broadcast_to(np.arange(n_b, dtype=np.int64)[:, None], gid.shape)[real]
    g = gid[real].astype(np.int64)
    bucket = np.full(num_groups, -1, np.int64)
    bucket[g] = rows_bucket
    if (bucket < 0).any() or not np.array_equal(bucket[g], rows_bucket):
        return None
    per_bucket = np.bincount(bucket, minlength=n_b)
    # Groups in (bucket, id) order are counted off from each bucket's start.
    start = np.concatenate([[0], np.cumsum(per_bucket)[:-1]])
    slot = np.empty(num_groups, np.int64)
    slot[np.argsort(bucket, kind="stable")] = np.arange(num_groups) - np.repeat(start, per_bucket)
    k_local = 1 << max(int(per_bucket.max(initial=0)).bit_length(), 1)  # >= max + 1
    lid = np.full(gid.shape, k_local - 1, np.int32)
    lid[real] = slot[g]
    return lid, k_local, bucket, slot


def fused_join_aggregate(
    pk: np.ndarray,
    sk: np.ndarray,
    pvals: np.ndarray,
    svals: np.ndarray,
    gid: np.ndarray,
    num_groups: int,
    channels: tuple,
    fused: str = "off",
    bucket_local: bool = False,
) -> np.ndarray:
    """Host wrapper: pads the group dimension (+1 dead segment for pads)
    and runs the fused device program on the persistent x64 worker thread
    (parallel/x64.py). Returns [C, num_groups] float64. `fused` = "auto"
    takes the Pallas run-bounds kernel where its shape rule admits the
    call (identical integer bounds, so identical results), and the lax
    searchsorted otherwise. `bucket_local` says the group keys hold the
    join key, so every group lies in one bucket: past the dense bound the
    channels then reduce densely into each bucket's own groups (:func:
    `bucket_local_ids`, checked on the ids) where no bucket holds more
    than the bound, instead of a [B, K] scatter per channel over every
    group."""
    from hyperspace_tpu.execution.device_cache import device_put_cached
    from hyperspace_tpu.ops.sortkeys import pallas_run_bounds
    from hyperspace_tpu.parallel.x64 import run_x64

    k_seg = 1 << max(int(num_groups).bit_length(), 1)  # >= num_groups+1
    local = None
    if k_seg > _DENSE_MAX_SEGMENTS and bucket_local:
        local = bucket_local_ids(np.asarray(gid), num_groups)
        if local is not None and local[1] > _DENSE_MAX_SEGMENTS:
            local = None
    if local is not None:
        gid, k_seg, bucket, slot = local
        reduce = "bucket_dense"
        stats.increment("device.kernel.bucket_reduce")
    elif k_seg <= _DENSE_MAX_SEGMENTS:
        reduce = "dense"
        stats.increment("device.kernel.dense_reduce")
    else:
        reduce = "scatter"
        stats.increment("device.kernel.scatter_reduce")

    def call():
        # Stable (frozen, identity-cached) inputs serve from the HBM
        # cache on repeat queries; the upload keys carry the active x64
        # scope, so the float64 channels stay float64.
        pk_dev = device_put_cached(pk)
        sk_dev = device_put_cached(sk)
        bounds = None
        if fused == "auto":
            bounds = pallas_run_bounds(pk_dev, sk_dev)
        if bounds is not None:
            out = _fused_join_agg_bounds(
                pk_dev, sk_dev, bounds[0], bounds[1],
                device_put_cached(pvals),
                device_put_cached(svals),
                device_put_cached(gid),
                k_seg,
                channels,
                reduce,
            )
        else:
            out = _fused_join_agg(
                pk_dev,
                sk_dev,
                device_put_cached(pvals),
                device_put_cached(svals),
                device_put_cached(gid),
                k_seg,
                channels,
                reduce,
            )
        return np.asarray(to_host(out))

    out = run_x64(call)
    if local is not None:
        return out[:, bucket, slot]
    return out[:, :num_groups]
