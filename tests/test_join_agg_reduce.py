"""The fused join-aggregate's group reductions (ops/join_agg.py): the
dense masked reduction over every row, the per-channel segment scatter
and, for groups keyed by the join key, the reduction into each bucket's
own groups agree with each other and with a numpy reference, and a
session's device Aggregate(Join) picks between them by the padded group
count and by whether its groups can span buckets."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import AggSpec, Hyperspace, HyperspaceSession, IndexConfig, stats
from hyperspace_tpu.config import AGG_VENUE, JOIN_VENUE
from hyperspace_tpu.ops.join_agg import (
    _DENSE_MAX_SEGMENTS,
    _fused_join_agg,
    bucket_local_ids,
    fused_join_aggregate,
)
from hyperspace_tpu.parallel.x64 import run_x64

_MAX32 = np.iinfo(np.int32).max
_B, _LP, _LS = 3, 96, 40


def _sorted_keys(rng, width, real):
    keys = np.sort(rng.integers(0, 30, (_B, width)), axis=1).astype(np.int32)
    keys[:, real:] = _MAX32
    return keys


def _inputs(rng, k_seg, kind, integral):
    """Bucket-major padded sides in the layout the executor stages:
    pads carry the key sentinel, sum-channel pads 0, extremum-channel
    pads their identity, and primary pads the dead group k_seg - 1."""
    pk = _sorted_keys(rng, _LP, _LP - 7)
    sk = _sorted_keys(rng, _LS, _LS - 5)

    def values(shape):
        if integral:
            return rng.integers(-1000, 1000, shape).astype(np.float64)
        return rng.normal(size=shape) * 100.0

    pv, sv = values((1, _B, _LP)), values((1, _B, _LS))
    fill = {"pmin": np.inf, "smax": -np.inf}.get(kind, 0.0)
    pv[:, pk == _MAX32] = fill if kind == "pmin" else 0.0
    sv[:, sk == _MAX32] = fill if kind == "smax" else 0.0
    gid = rng.integers(0, k_seg - 1, (_B, _LP)).astype(np.int32)
    gid[pk == _MAX32] = k_seg - 1
    return pk, sk, pv, sv, gid


def _reference(pk, sk, pv, sv, gid, k_seg, kind):
    """Per group: the channel folded over every matched (primary,
    secondary) pair, with the reduction's identity where none fell."""
    op = {"pmin": np.minimum, "smax": np.maximum}.get(kind, np.add)
    ident = {"pmin": np.inf, "smax": -np.inf}.get(kind, 0.0)
    out = np.full(k_seg, ident)
    for b in range(_B):
        for i in np.flatnonzero(pk[b] != _MAX32):
            for j in np.flatnonzero(sk[b] == pk[b, i]):
                v = {"star": 1.0, "p": pv[0, b, i], "pmin": pv[0, b, i]}.get(
                    kind, sv[0, b, j]
                )
                out[gid[b, i]] = op(out[gid[b, i]], v)
    return out


@pytest.mark.parametrize("kind", ["star", "p", "s", "pmin", "smax"])
@pytest.mark.parametrize(
    "k_seg", [2, 64, _DENSE_MAX_SEGMENTS, 2 * _DENSE_MAX_SEGMENTS]
)
def test_dense_and_scatter_reductions_agree(k_seg, kind):
    rng = np.random.default_rng(k_seg * 7 + len(kind))
    channels = (("star",),) if kind == "star" else (("star",), (kind, 0))
    for integral in (True, False):
        pk, sk, pv, sv, gid = _inputs(rng, k_seg, kind, integral)
        dense, scatter = (
            np.asarray(run_x64(lambda r=r: _fused_join_agg(
                pk, sk, pv, sv, gid, num_segments=k_seg, channels=channels, reduce=r
            )))
            for r in ("dense", "scatter")
        )
        assert dense.shape == scatter.shape == (len(channels), k_seg)
        assert dense.dtype == np.float64
        # Run lengths are integers and extrema order-free: bit-identical.
        np.testing.assert_array_equal(dense[0], scatter[0])
        if integral or kind in ("pmin", "smax"):
            np.testing.assert_array_equal(dense[-1], scatter[-1])
        else:
            np.testing.assert_allclose(dense[-1], scatter[-1], rtol=1e-12, atol=1e-9)
        ref = _reference(pk, sk, pv, sv, gid, k_seg, kind)
        np.testing.assert_allclose(dense[-1], ref, rtol=1e-12, atol=1e-9)
        # Pads fall in the dead segment and contribute only the identity.
        assert dense[0, k_seg - 1] == 0.0
        assert dense[-1, k_seg - 1] == ref[k_seg - 1]
        assert ref[k_seg - 1] == {"pmin": np.inf, "smax": -np.inf}.get(kind, 0.0)


def _write(root, name, frame):
    (root / name).mkdir()
    pq.write_table(pa.Table.from_pandas(frame, preserve_index=False), root / name / "p.parquet")
    return root / name


_REDUCE_COUNTERS = (
    "device.kernel.dense_reduce", "device.kernel.scatter_reduce", "device.kernel.bucket_reduce",
)


@pytest.mark.parametrize(
    "n_keys,by,counter",
    [
        pytest.param(40, "k", "device.kernel.dense_reduce", id="40-device.kernel.dense_reduce"),
        # Groups of a fact column span buckets: the global scatter.
        pytest.param(2 * _DENSE_MAX_SEGMENTS, "g", "device.kernel.scatter_reduce",
                     id=f"{2 * _DENSE_MAX_SEGMENTS}-device.kernel.scatter_reduce"),
        # Groups keyed by the join key lie in one bucket each.
        pytest.param(2 * _DENSE_MAX_SEGMENTS, "k", "device.kernel.bucket_reduce",
                     id=f"{2 * _DENSE_MAX_SEGMENTS}-device.kernel.bucket_reduce"),
    ],
)
def test_session_join_aggregate_counts_its_reduction(tmp_path, n_keys, by, counter):
    rng = np.random.default_rng(n_keys)
    fact = pd.DataFrame({
        "k": rng.integers(0, n_keys, 6 * n_keys).astype(np.int64),
        "g": rng.integers(0, n_keys, 6 * n_keys).astype(np.int64),
        "units": rng.integers(1, 9, 6 * n_keys).astype(np.int64),
    })
    dim = pd.DataFrame({
        "k": np.arange(n_keys, dtype=np.int64),
        "w": rng.integers(0, 1000, n_keys).astype(np.int64),
    })
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=4)
    session.conf.set(JOIN_VENUE, "device")
    session.conf.set(AGG_VENUE, "device")
    f = session.parquet(_write(tmp_path, "fact", fact))
    d = session.parquet(_write(tmp_path, "dim", dim))
    # Covering indexes hand the join its sides in 4 key buckets.
    hs = Hyperspace(session)
    hs.create_index(f, IndexConfig("fact_k", ["k"], ["g", "units"]))
    hs.create_index(d, IndexConfig("dim_k", ["k"], ["w"]))
    session.enable_hyperspace()
    q = f.join(d, ["k"]).aggregate(
        [by], [AggSpec.of("sum", "w", "sw"), AggSpec.of("sum", "units", "su"),
               AggSpec.of("count", None, "n")]
    )
    before = {c: stats.get(c) for c in _REDUCE_COUNTERS}
    got = session.to_pandas(q).sort_values(by).reset_index(drop=True)
    assert session.last_query_stats["agg_path"] == "fused-join-agg"
    assert session.last_query_stats["join_kernel"] == "device-run-prefix"
    assert {c: stats.get(c) - before[c] for c in _REDUCE_COUNTERS} == {
        c: int(c == counter) for c in _REDUCE_COUNTERS
    }

    j = fact.merge(dim, on="k")
    exp = (
        j.groupby(by).agg(sw=("w", "sum"), su=("units", "sum"), n=("k", "size"))
        .reset_index().sort_values(by).reset_index(drop=True)
    )
    np.testing.assert_array_equal(got[by], exp[by])
    np.testing.assert_array_equal(got["sw"], exp["sw"])
    np.testing.assert_array_equal(got["su"], exp["su"])
    np.testing.assert_array_equal(got["n"], exp["n"])


def _keyed_inputs(rng, n_b, lp, spans: bool):
    """Primary rows with distinct sorted keys per bucket, each its own
    group (a group keyed by the join key), numbered across buckets; with
    `spans`, two groups also take a row of another bucket."""
    real = lp - 9
    pk = np.full((n_b, lp), _MAX32, np.int32)
    sk = np.full((n_b, 3 * lp // 4), _MAX32, np.int32)
    for b in range(n_b):
        pk[b, :real] = np.sort(rng.choice(10 * lp, real, replace=False))
        sk[b, : sk.shape[1] - 4] = np.sort(rng.choice(pk[b, :real], sk.shape[1] - 4))
    num_groups = n_b * real
    gid = np.full((n_b, lp), num_groups, np.int32)
    gid[:, :real] = np.arange(num_groups).reshape(n_b, real)
    if spans:
        gid[1, 0], gid[0, 1] = gid[0, 0], gid[1, 1]
    pv = np.where(pk == _MAX32, 0.0, rng.normal(size=(1, n_b, lp)) * 100.0)
    sv = np.where(sk == _MAX32, 0.0, rng.integers(-1000, 1000, (1, n_b, sk.shape[1])).astype(float))
    return pk, sk, pv, sv, gid, num_groups


@pytest.mark.parametrize(
    "n_b,lp,spans,counter",
    [
        # About 1,270 groups a bucket: each bucket's groups reduce densely.
        (4, 1280, False, "device.kernel.bucket_reduce"),
        # 4,599 groups a bucket, past the dense bound: the global scatter.
        (2, 4608, False, "device.kernel.scatter_reduce"),
        # Two groups span buckets: the global scatter, as before.
        (4, 1280, True, "device.kernel.scatter_reduce"),
    ],
)
def test_bucket_local_reduction_matches_scatter_dense_and_numpy(n_b, lp, spans, counter):
    rng = np.random.default_rng(n_b * lp + spans)
    pk, sk, pv, sv, gid, num_groups = _keyed_inputs(rng, n_b, lp, spans)
    channels = (("star",), ("p", 0), ("s", 0), ("pmin", 0), ("smax", 0))
    pvals = np.concatenate([pv, np.where(pk == _MAX32, np.inf, pv)])
    svals = np.concatenate([sv, np.where(sk == _MAX32, -np.inf, sv)])
    assert (bucket_local_ids(gid, num_groups) is None) == spans
    before = {c: stats.get(c) for c in _REDUCE_COUNTERS}
    got = fused_join_aggregate(pk, sk, pvals, svals, gid, num_groups, channels, bucket_local=True)
    assert {c: stats.get(c) - before[c] for c in _REDUCE_COUNTERS} == {
        c: int(c == counter) for c in _REDUCE_COUNTERS
    }
    k_seg = 1 << int(num_groups).bit_length()
    assert k_seg > _DENSE_MAX_SEGMENTS
    dense, scatter = (
        np.asarray(run_x64(lambda r=r: _fused_join_agg(
            pk, sk, pvals, svals, gid, num_segments=k_seg, channels=channels, reduce=r
        )))[:, :num_groups]
        for r in ("dense", "scatter")
    )
    # Run lengths, integral sums and extrema are exact on every path.
    for c in (0, 2, 3, 4):
        np.testing.assert_array_equal(got[c], scatter[c])
        np.testing.assert_array_equal(got[c], dense[c])
    np.testing.assert_allclose(got[1], scatter[1], rtol=1e-12, atol=1e-9)
    # numpy: each primary row's matches in its bucket's sorted secondary.
    st = np.stack([np.searchsorted(sk[b], pk[b], "left") for b in range(n_b)])
    en = np.stack([np.searchsorted(sk[b], pk[b], "right") for b in range(n_b)])
    real = pk != _MAX32
    runs = np.where(real, en - st, 0)
    prefix = np.concatenate([np.zeros((n_b, 1)), np.cumsum(sv[0], axis=1)], axis=1)
    s_sum = np.stack([prefix[b][en[b]] - prefix[b][st[b]] for b in range(n_b)])
    g = gid[real]
    np.testing.assert_array_equal(got[0], np.bincount(g, runs[real], num_groups))
    np.testing.assert_allclose(got[1], np.bincount(g, (pv[0] * runs)[real], num_groups),
                               rtol=1e-12, atol=1e-9)
    np.testing.assert_array_equal(got[2], np.bincount(g, s_sum[real], num_groups))
