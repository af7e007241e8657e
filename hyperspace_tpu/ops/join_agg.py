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
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

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


def _one_bucket(pkb, skb, pvb, svb, gidb, stb, enb, num_segments: int, channels: tuple):
    """Per-bucket channel reduction given the run bounds [stb, enb). The
    named scopes label each stage's device ops in a profile."""
    bounds = functools.partial(jax.named_scope, "join_agg.bounds")
    gather = functools.partial(jax.named_scope, "join_agg.gather")
    segment = functools.partial(jax.named_scope, "join_agg.segment_sum")
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
    outs = []
    for ch in channels:
        kind = ch[0]
        if kind == "star":
            with segment():
                outs.append(jax.ops.segment_sum(runlen, gidb, num_segments))
        elif kind == "p":
            with segment():
                outs.append(jax.ops.segment_sum(pvb[ch[1]] * runlen, gidb, num_segments))
        elif kind == "s":
            with gather():
                pj = p_prefix[ch[1]]
                w = jnp.where(real, pj[enb] - pj[stb], 0.0)
            with segment():
                outs.append(jax.ops.segment_sum(w, gidb, num_segments))
        else:
            is_min = kind.endswith("min")
            ident = jnp.inf if is_min else -jnp.inf
            seg_red = jax.ops.segment_min if is_min else jax.ops.segment_max
            with gather():
                if kind[0] == "p":
                    w = jnp.where(matched, pvb[ch[1]], ident)
                else:
                    m = _seg_scan_extremum(
                        svb[ch[1]], new_key, jnp.minimum if is_min else jnp.maximum
                    )
                    w = jnp.where(matched, m[jnp.maximum(enb - 1, 0)], ident)
            with segment():
                outs.append(seg_red(w, gidb, num_segments))
    return jnp.stack(outs)


def _combine_buckets(per_bucket, channels: tuple):
    """Fold the vmapped [B, C, K] per-bucket partials across buckets (a
    group's rows can span buckets only via the primary side's bucketing;
    sums add, extrema fold with their own op)."""
    combined = []
    for c, ch in enumerate(channels):
        if ch[0] == "pmin" or ch[0] == "smin":
            combined.append(jnp.min(per_bucket[:, c], axis=0))
        elif ch[0] == "pmax" or ch[0] == "smax":
            combined.append(jnp.max(per_bucket[:, c], axis=0))
        else:
            combined.append(jnp.sum(per_bucket[:, c], axis=0))
    return jnp.stack(combined)  # [C, num_segments]


@functools.partial(jit, static_argnames=("num_segments", "channels"))
def _fused_join_agg(pk, sk, pvals, svals, gid, num_segments: int, channels: tuple):
    """pk/sk: [B, Lp]/[B, Ls] per-bucket sorted int32 codes (pads carry
    the dtype max). pvals [Ap, B, Lp] / svals [As, B, Ls]: float64
    per-row channel values (nulls and pads pre-zeroed for sum channels,
    pre-set to the ±inf identity for extremum channels). gid [B, Lp]:
    group ids (pads → num_segments-1). channels: ('star',) | ('p'|'s', j)
    sum channels | ('pmin'|'pmax'|'smin'|'smax', j) run-extremum channels
    (an equi-join match run IS one key segment of the sorted secondary,
    so its extremum is the segmented prefix scan value at the run end).
    Returns [len(channels), num_segments] float64."""

    def one(pkb, skb, pvb, svb, gidb):
        with jax.named_scope("join_agg.bounds"):
            st = jnp.searchsorted(skb, pkb, side="left").astype(jnp.int32)
            en = jnp.searchsorted(skb, pkb, side="right").astype(jnp.int32)
        return _one_bucket(pkb, skb, pvb, svb, gidb, st, en, num_segments, channels)

    per_bucket = jax.vmap(one)(pk, sk, pvals.transpose(1, 0, 2), svals.transpose(1, 0, 2), gid)
    with jax.named_scope("join_agg.segment_sum"):
        return _combine_buckets(per_bucket, channels)


@functools.partial(jit, static_argnames=("num_segments", "channels"))
def _fused_join_agg_bounds(
    pk, sk, st, en, pvals, svals, gid, num_segments: int, channels: tuple
):
    """Same program as :func:`_fused_join_agg` with the run bounds
    precomputed (the Pallas run-bounds kernel feeds this variant)."""

    def one(pkb, skb, stb, enb, pvb, svb, gidb):
        return _one_bucket(pkb, skb, pvb, svb, gidb, stb, enb, num_segments, channels)

    per_bucket = jax.vmap(one)(
        pk, sk, st, en, pvals.transpose(1, 0, 2), svals.transpose(1, 0, 2), gid
    )
    with jax.named_scope("join_agg.segment_sum"):
        return _combine_buckets(per_bucket, channels)


def fused_join_aggregate(
    pk: np.ndarray,
    sk: np.ndarray,
    pvals: np.ndarray,
    svals: np.ndarray,
    gid: np.ndarray,
    num_groups: int,
    channels: tuple,
    fused: str = "off",
) -> np.ndarray:
    """Host wrapper: pads the group dimension (+1 dead segment for pads)
    and runs the fused device program on the persistent x64 worker thread
    (parallel/x64.py). Returns [C, num_groups] float64. `fused` = "auto"
    takes the Pallas run-bounds kernel where its shape rule admits the
    call (identical integer bounds, so identical results), and the lax
    searchsorted otherwise."""
    from hyperspace_tpu.execution.device_cache import device_put_cached
    from hyperspace_tpu.ops.sortkeys import pallas_run_bounds
    from hyperspace_tpu.parallel.x64 import run_x64

    k_seg = 1 << max(int(num_groups).bit_length(), 1)  # >= num_groups+1

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
            )
        return np.asarray(to_host(out))

    return run_x64(call)[:, :num_groups]
