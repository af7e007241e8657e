"""The fused join-aggregate's two group reductions (ops/join_agg.py):
the dense masked reduction over every row and the per-channel segment
scatter agree with each other and with a numpy reference, and a
session's device Aggregate(Join) picks between them by the padded group
count alone."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import AggSpec, HyperspaceSession, stats
from hyperspace_tpu.config import AGG_VENUE, JOIN_VENUE
from hyperspace_tpu.ops.join_agg import _DENSE_MAX_SEGMENTS, _fused_join_agg
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


@pytest.mark.parametrize(
    "n_keys,counter",
    [(40, "device.kernel.dense_reduce"), (2 * _DENSE_MAX_SEGMENTS, "device.kernel.scatter_reduce")],
)
def test_session_join_aggregate_counts_its_reduction(tmp_path, n_keys, counter):
    rng = np.random.default_rng(n_keys)
    fact = pd.DataFrame({
        "k": rng.integers(0, n_keys, 6 * n_keys).astype(np.int64),
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
    q = f.join(d, ["k"]).aggregate(
        ["k"], [AggSpec.of("sum", "w", "sw"), AggSpec.of("sum", "units", "su"),
                AggSpec.of("count", None, "n")]
    )
    other = ({"device.kernel.dense_reduce", "device.kernel.scatter_reduce"} - {counter}).pop()
    before, before_other = stats.get(counter), stats.get(other)
    got = session.to_pandas(q).sort_values("k").reset_index(drop=True)
    assert session.last_query_stats["agg_path"] == "fused-join-agg"
    assert session.last_query_stats["join_kernel"] == "device-run-prefix"
    assert stats.get(counter) == before + 1
    assert stats.get(other) == before_other

    j = fact.merge(dim, on="k")
    exp = (
        j.groupby("k").agg(sw=("w", "sum"), su=("units", "sum"), n=("k", "size"))
        .reset_index().sort_values("k").reset_index(drop=True)
    )
    np.testing.assert_array_equal(got["k"], exp["k"])
    np.testing.assert_array_equal(got["sw"], exp["sw"])
    np.testing.assert_array_equal(got["su"], exp["su"])
    np.testing.assert_array_equal(got["n"], exp["n"])
