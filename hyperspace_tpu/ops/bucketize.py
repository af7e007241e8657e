"""Distributed hash-bucketize: the build-time shuffle, TPU-native.

This is the framework's equivalent of Spark's ShuffleExchangeExec + Netty
block transfer (reference hot path: `repartition(numBuckets, indexedCols)`
at actions/CreateActionBase.scala:110-112). Design per SURVEY.md §2.3:

- the mesh axis ("x") spans the devices; device d owns the contiguous
  bucket range [d*B/D, (d+1)*B/D) for B buckets over D devices;
- each device sorts its local rows by destination device, scatters them
  into a padded [D, C] send buffer, and ONE `lax.all_to_all` over ICI moves
  every row to its owner — no Netty, no host round-trip;
- a per-(src,dst) capacity C bounds the padded transfer; overflow is
  detected on device and reported back so the host can retry with a larger
  capacity factor (skew mitigation, SURVEY.md §7 step 3);
- after the exchange each device lex-sorts its received rows by
  (bucket, key columns) — giving bucket-grouped, key-sorted shards ready
  for per-bucket persistence.

Rows are carried as a stack of int32/uint32/float32-compatible columns; the
caller is responsible for representing every column as a jax-compatible
array (ColumnTable guarantees this).

Invariants (enforced statically where possible — analysis/validator.py
checks bucket specs at plan level; analysis/lint.py keeps the jax import
surface on compat.py):
- num_buckets is a positive multiple of the mesh size (checked here);
- bucket ids are a pure function of the key VALUES under the canonical
  row hash, so per-device bucket ranges partition the key space;
- invalid rows carry the 2^30 sentinel bucket and sink to shard tails.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from hyperspace_tpu.compat import jit, shard_map, to_host

AXIS = "x"


def _exchange_one_device(
    cols: list,
    bucket: jnp.ndarray,
    valid: jnp.ndarray,
    num_devices: int,
    buckets_per_device: int,
    capacity: int,
    num_key_cols: int,
    axes=(AXIS,),
):
    """Per-device body run under shard_map. `cols` are the local columns
    [R, ...] (first `num_key_cols` are sort keys, rest payloads); `bucket`
    the per-row bucket id; `valid` marks real rows. Returns
    (recv_cols, recv_bucket, recv_valid, overflowed) with received rows
    lex-sorted by (bucket, key cols) — the exchange AND the local sort run
    in one fused device program, under the named scopes ``build.bucketize``
    and ``build.sort`` that label their ops in a profile."""
    with jax.named_scope("build.bucketize"):
        r = bucket.shape[0]
        dest = jnp.where(valid, bucket // buckets_per_device, num_devices)  # invalid → sentinel D

        # Stable sort rows by dest so each destination's rows are contiguous.
        order = lax.sort((dest.astype(jnp.int32), jnp.arange(r, dtype=jnp.int32)), num_keys=1, is_stable=True)[1]
        dest_sorted = dest[order]
        bucket_sorted = bucket[order]

        # Per-destination group extents.
        counts = jnp.bincount(dest_sorted, length=num_devices + 1)
        offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        overflowed = jnp.max(counts[:num_devices]) > capacity

        # Build the [D, C] send buffer by GATHER (TPU-friendly; scatters
        # serialize): slot (d, c) reads sorted row offsets[d] + c when real.
        slot_dst = jnp.repeat(jnp.arange(num_devices, dtype=jnp.int32), capacity)
        slot_within = jnp.tile(jnp.arange(capacity, dtype=jnp.int32), num_devices)
        slot_ok = slot_within < counts[slot_dst]
        src = jnp.where(slot_ok, offsets[slot_dst] + slot_within, 0)

        def fill_slots(col_sorted, fill):
            """Gather per-ROW values into the [D, C] slot layout."""
            vals = jnp.where(slot_ok, col_sorted[src], fill)
            return vals.reshape(num_devices, capacity)

        send_valid = slot_ok.astype(jnp.int32).reshape(num_devices, capacity)
        send_bucket = fill_slots(bucket_sorted, -1)
        send_cols = [fill_slots(c[order], 0) for c in cols]

        # THE exchange: one all_to_all over the mesh axes (ICI within a
        # slice; ICI+DCN on a multi-slice mesh).
        recv_valid = lax.all_to_all(send_valid, axes, 0, 0, tiled=True)
        recv_bucket = lax.all_to_all(send_bucket, axes, 0, 0, tiled=True)
        recv_cols = [lax.all_to_all(c, axes, 0, 0, tiled=True) for c in send_cols]

    with jax.named_scope("build.sort"):
        # Flatten [D, C] → [D*C]; invalid rows get the sentinel bucket so they
        # sink to the end, then ONE stable lex-sort by (bucket, key cols).
        rv = recv_valid.reshape(-1)
        rb = jnp.where(rv > 0, recv_bucket.reshape(-1), jnp.int32(2**30))
        rc = [c.reshape(-1) for c in recv_cols]
        sorted_arrays = lax.sort((rb, *rc, rv), num_keys=1 + num_key_cols, is_stable=True)
        rb = sorted_arrays[0]
        rc = list(sorted_arrays[1:-1])
        rv = sorted_arrays[-1]
    return rc, rb, rv, overflowed


@functools.lru_cache(maxsize=64)
def make_bucketize_fn(
    mesh: Mesh,
    num_cols: int,
    num_buckets: int,
    capacity: int,
    num_key_cols: int,
):
    """Build the jitted shard_map'd exchange+sort for a fixed column layout.

    Works on a 1-D ("x") or 2-D ("dcn", "x") mesh: the exchange runs over
    the COMBINED axes, so on a multi-slice mesh XLA routes the
    within-slice portion over ICI and the cross-slice portion over DCN.
    Device order (and therefore contiguous bucket ownership) follows the
    flattened mesh order."""
    from hyperspace_tpu.parallel.mesh import mesh_axes, mesh_size

    axes = mesh_axes(mesh)
    num_devices = mesh_size(mesh)
    if num_buckets % num_devices != 0:
        raise ValueError(f"num_buckets {num_buckets} must be a multiple of mesh size {num_devices}")
    buckets_per_device = num_buckets // num_devices
    spec = P(axes)  # dim 0 sharded over the combined mesh axes

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(tuple(spec for _ in range(num_cols)), spec, spec),
        out_specs=(tuple(spec for _ in range(num_cols)), spec, spec, P()),
        check_vma=False,
    )
    def fn(cols, bucket, valid):
        rc, rb, rv, overflow = _exchange_one_device(
            list(cols), bucket, valid, num_devices, buckets_per_device, capacity,
            num_key_cols, axes,
        )
        # overflow is a per-device scalar; reduce with OR (max) across mesh.
        overflow = lax.pmax(overflow.astype(jnp.int32), axes)
        return tuple(rc), rb, rv, overflow[None] if overflow.ndim == 0 else overflow

    return jit(fn, key="ops.bucketize.exchange")


@functools.lru_cache(maxsize=64)
def make_bucketize_perm_fn(
    mesh: Mesh,
    lane_dtypes: tuple,
    num_buckets: int,
    capacity: int,
):
    """Exchange + lex-sort that returns ONLY (permutation, counts).

    The full-row variant above downloads every exchanged column, and
    over a slow link device→host readback is the build bottleneck, so
    this program keeps payloads off the device entirely:
    inputs are the key LANES (ops/sortkeys.py) + per-row bucket id, the
    global row id is generated on device (iota + axis offset), and the
    outputs are the key-sorted global row permutation [n_pad] plus
    per-device per-bucket valid-row counts [D, num_buckets]. The host
    gathers payload columns by the permutation and carves by the counts —
    one int32-per-row readback total."""
    from hyperspace_tpu.parallel.mesh import mesh_axes, mesh_size

    axes = mesh_axes(mesh)
    num_devices = mesh_size(mesh)
    if num_buckets % num_devices != 0:
        raise ValueError(f"num_buckets {num_buckets} must be a multiple of mesh size {num_devices}")
    buckets_per_device = num_buckets // num_devices
    num_lanes = len(lane_dtypes)
    spec = P(axes)
    axis_sizes = {ax: mesh.shape[ax] for ax in axes}

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(tuple(spec for _ in range(num_lanes)), spec, P()),
        out_specs=(spec, P(axes, None), P()),
        check_vma=False,
    )
    def fn(lanes, bucket, n_rows):
        r = bucket.shape[0]
        flat_idx = jnp.int32(0)
        for ax in axes:
            flat_idx = flat_idx * axis_sizes[ax] + lax.axis_index(ax)
        gid = flat_idx * r + jnp.arange(r, dtype=jnp.int32)
        valid = (gid < n_rows[0]).astype(jnp.int32)
        rc, rb, rv, overflow = _exchange_one_device(
            list(lanes) + [gid], bucket, valid, num_devices, buckets_per_device,
            capacity, num_lanes, axes,
        )
        perm = rc[-1]
        # Valid rows carry their true bucket; invalid rows carry the 2^30
        # sentinel, which bincount's bounded scatter drops.
        counts = jnp.bincount(rb, length=num_buckets).astype(jnp.int32)
        overflow = lax.pmax(overflow.astype(jnp.int32), axes)
        return perm, counts[None, :], overflow[None] if overflow.ndim == 0 else overflow

    return jit(fn, key="ops.bucketize.perm")


def bucketize_perm(
    mesh: Mesh,
    lanes: list,
    bucket,
    n: int,
    num_buckets: int,
    capacity_factor: float = 2.0,
):
    """Host wrapper for the permutation-only exchange (overflow retry as in
    `bucketize`). `lanes`/`bucket` are host arrays padded to a multiple of
    the mesh size; rows past `n` are pads. Returns (order [n] int32 global
    row ids in (bucket, key) order, bucket_rows [num_buckets])."""
    import numpy as _np

    from hyperspace_tpu.parallel.mesh import mesh_size

    num_devices = mesh_size(mesh)
    n_pad = bucket.shape[0]
    if n_pad >= 2**31:
        raise ValueError("bucketize_perm row ids exceed int32")
    per_dev = n_pad // num_devices
    lane_dtypes = tuple(str(_np.dtype(l.dtype)) for l in lanes)
    n_arr = jnp.asarray(_np.array([n], dtype=_np.int32))
    dev_lanes = tuple(jnp.asarray(l) for l in lanes)
    dev_bucket = jnp.asarray(bucket)
    while True:
        capacity = max(1, math.ceil(per_dev / num_devices * capacity_factor))
        capacity = min(capacity, per_dev)
        fn = make_bucketize_perm_fn(mesh, lane_dtypes, num_buckets, capacity)
        perm, counts, overflow = fn(dev_lanes, dev_bucket, n_arr)
        # ONE fused readback (overflow + perm + counts): every device_get
        # round-trip pays the link's latency, and overflow is rare enough
        # that optimistically downloading perm alongside it wins on
        # average.
        perm_h, counts_h, overflow_h = to_host((perm, counts, overflow))
        if not bool(_np.asarray(overflow_h).max()):
            break
        if capacity >= per_dev:
            # Typed (not assert): the invariant breaking would cross the
            # action API surface, and asserts vanish under -O.
            from hyperspace_tpu.exceptions import HyperspaceError

            raise HyperspaceError("bucketize overflow with full capacity — impossible")
        capacity_factor *= 2.0
    perm_h = _np.asarray(perm_h)
    counts_h = _np.asarray(counts_h)  # [D, num_buckets]
    # Each shard's output is its flattened [D, capacity] recv buffer
    # (valid rows sorted to the front), so the global array is [D * D*cap].
    shard_len = num_devices * capacity
    valid_per_shard = counts_h.sum(axis=1)
    parts = [
        perm_h[i * shard_len : i * shard_len + int(valid_per_shard[i])]
        for i in range(num_devices)
    ]
    order = _np.concatenate(parts) if parts else perm_h[:0]
    return order, counts_h.sum(axis=0)


def bucketize(
    mesh: Mesh,
    cols: list,
    bucket: jnp.ndarray,
    valid: jnp.ndarray,
    num_buckets: int,
    capacity_factor: float = 2.0,
    num_key_cols: int | None = None,
):
    """Host wrapper with overflow retry (doubling the capacity factor).

    Inputs are global arrays whose leading dim is a multiple of the mesh
    size (caller pads). The first `num_key_cols` of `cols` (default: all
    but the last) are sort keys after the exchange. Returns
    (cols, bucket, valid) where rows live on their owning device,
    lex-sorted by (bucket, keys) with invalid rows sunk to each shard's
    tail under the sentinel bucket."""
    from hyperspace_tpu.parallel.mesh import mesh_size

    num_devices = mesh_size(mesh)
    n = bucket.shape[0]
    per_dev = n // num_devices
    if num_key_cols is None:
        num_key_cols = max(0, len(cols) - 1)
    while True:
        capacity = max(1, math.ceil(per_dev / num_devices * capacity_factor))
        capacity = min(capacity, per_dev)  # no point exceeding local rows
        fn = make_bucketize_fn(mesh, len(cols), num_buckets, capacity, num_key_cols)
        out_cols, out_bucket, out_valid, overflow = fn(tuple(cols), bucket, valid)
        if not bool(to_host(overflow).max()):
            return list(out_cols), out_bucket, out_valid
        if capacity >= per_dev:
            # Typed (not assert): the invariant breaking would cross the
            # action API surface, and asserts vanish under -O.
            from hyperspace_tpu.exceptions import HyperspaceError

            raise HyperspaceError("bucketize overflow with full capacity — impossible")
        capacity_factor *= 2.0
