"""Device-plane kernel tests on the 8-device CPU mesh: hashing parity,
bucketize exchange, lex sort, merge join."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hyperspace_tpu.ops.bucketize import bucketize
from hyperspace_tpu.ops.hashing import bucket_ids, combine_hashes, hash_int_column, string_dict_hashes
from hyperspace_tpu.ops import join as join_ops
from hyperspace_tpu.parallel.mesh import make_mesh


def test_host_device_hash_parity():
    # Device lanes are 32-bit native (no x64 flag anywhere): device-side
    # hashing covers 32-bit dtypes; 64-bit hashing is host-only (builder
    # computes row hashes with numpy before upload).
    rng = np.random.default_rng(0)
    for dtype in (np.int32, np.float32):
        arr = rng.integers(-1000, 1000, 256).astype(dtype)
        h_host = hash_int_column(arr, np)
        h_dev = np.asarray(hash_int_column(jnp.asarray(arr), jnp))
        np.testing.assert_array_equal(h_host, h_dev, err_msg=str(dtype))


def test_string_hash_dictionary_independent():
    d1 = np.array(["a", "b", "c"], dtype=object)
    d2 = np.array(["b", "c", "z"], dtype=object)
    h1 = string_dict_hashes(d1)
    h2 = string_dict_hashes(d2)
    # same strings hash identically regardless of dictionary membership
    assert h1[1] == h2[0] and h1[2] == h2[1]
    assert len({h1[0], h1[1], h1[2]}) == 3


def test_combine_order_dependent():
    a = np.array([1, 2], np.uint32)
    b = np.array([3, 4], np.uint32)
    assert not np.array_equal(combine_hashes([a, b], np), combine_hashes([b, a], np))


def test_bucketize_preserves_rows_and_ownership():
    mesh = make_mesh()
    d = mesh.shape["x"]
    assert d == 8, "tests expect the 8-device CPU mesh from conftest"
    rng = np.random.default_rng(1)
    n, num_buckets = 4096, 32
    keys = rng.integers(0, 5000, n).astype(np.int32)
    vals = rng.standard_normal(n).astype(np.float32)
    bucket = bucket_ids(hash_int_column(keys, np), num_buckets, np)
    valid = np.ones(n, np.int32)
    out_cols, out_bucket, out_valid = bucketize(
        mesh, [jnp.asarray(keys), jnp.asarray(vals)], jnp.asarray(bucket), jnp.asarray(valid), num_buckets
    )
    ob = np.asarray(out_bucket)
    ov = np.asarray(out_valid)
    ok = np.asarray(out_cols[0])
    oval = np.asarray(out_cols[1])
    real = ov > 0
    assert real.sum() == n
    # Ownership: device i's segment only holds its bucket range.
    bpd = num_buckets // d
    seg = len(ob) // d
    for i in range(d):
        s = slice(i * seg, (i + 1) * seg)
        bs = ob[s][ov[s] > 0]
        assert (bs // bpd == i).all()
    # No data loss/corruption.
    assert sorted(zip(keys.tolist(), vals.tolist())) == sorted(zip(ok[real].tolist(), oval[real].tolist()))


def test_bucketize_skew_retry():
    """All rows hash to one bucket — exercises the overflow-retry path."""
    mesh = make_mesh()
    n, num_buckets = 512, 8
    keys = np.full(n, 42, np.int32)
    bucket = bucket_ids(hash_int_column(keys, np), num_buckets, np)
    out_cols, out_bucket, out_valid = bucketize(
        mesh, [jnp.asarray(keys)], jnp.asarray(bucket), jnp.asarray(np.ones(n, np.int32)), num_buckets,
        capacity_factor=0.25,
    )
    assert (np.asarray(out_valid) > 0).sum() == n


def test_merge_join_kernel():
    # bucket 0: left [1,1,2,5], right [1,2,2,7] → matches: 1x1*2, 2x2*2 = 4
    S = join_ops.SENTINEL
    lk = np.array([[1, 1, 2, 5], [10, 20, S, S]], dtype=np.int64)
    rk = np.array([[1, 2, 2, 7], [20, 20, 30, S]], dtype=np.int64)
    li, ri, totals = join_ops.merge_join(lk, rk)
    # bucket 0: (0,0),(1,0),(2,1),(2,2); bucket 1: (1,0),(1,1)
    assert totals.tolist() == [4, 2]
    got0 = sorted(zip(li[:4].tolist(), ri[:4].tolist()))
    got1 = sorted(zip(li[4:6].tolist(), ri[4:6].tolist()))
    assert got0 == [(0, 0), (1, 0), (2, 1), (2, 2)]
    assert got1 == [(1, 0), (1, 1)]


def test_merge_join_empty():
    S = join_ops.SENTINEL
    lk = np.full((2, 3), S, dtype=np.int64)
    rk = np.full((2, 4), S, dtype=np.int64)
    li, ri, totals = join_ops.merge_join(lk, rk)
    assert totals.sum() == 0 and len(li) == 0 and len(ri) == 0


def test_merge_join_wide_bucket_unpacked_path():
    """Bucket width >= 2^16 takes the non-pack16 download branch."""
    rng = np.random.default_rng(3)
    w = 70_000
    lvals = np.sort(rng.integers(0, 50_000, w)).astype(np.int32)
    rvals = np.sort(rng.integers(0, 50_000, w)).astype(np.int32)
    li, ri, totals = join_ops.merge_join(lvals[None, :], rvals[None, :])
    # verify against a host-side expansion
    import pandas as pd

    expected = pd.merge(
        pd.DataFrame({"k": lvals, "li": np.arange(w)}),
        pd.DataFrame({"k": rvals, "ri": np.arange(w)}),
        on="k",
    )
    assert totals.sum() == len(expected)
    got = set(zip(li.tolist(), ri.tolist()))
    want = set(zip(expected["li"].tolist(), expected["ri"].tolist()))
    assert got == want


def test_multi_key_join_rerank_path_equality():
    """Three key columns with cardinalities whose product exceeds int32 —
    exercises the executor's int32 re-rank of mixed-radix codes."""
    import pandas as pd

    from hyperspace_tpu.execution.executor import _factorize_keys
    from hyperspace_tpu.execution.table import ColumnTable
    from hyperspace_tpu.schema import Field, Schema

    rng = np.random.default_rng(4)
    n = 2000
    schema = Schema.of(Field("a", "int64"), Field("b", "int64"), Field("c", "int64"))

    def tbl(seed):
        r = np.random.default_rng(seed)
        return ColumnTable(
            schema,
            {
                "a": r.integers(0, 1400, n).astype(np.int64),
                "b": r.integers(0, 1400, n).astype(np.int64),
                "c": r.integers(0, 1400, n).astype(np.int64),
            },
            {},
        )

    lt, rt = tbl(1), tbl(2)
    lcodes, rcodes = _factorize_keys([lt], [rt], ["a", "b", "c"], ["a", "b", "c"])
    assert lcodes[0].dtype == np.int32 and rcodes[0].dtype == np.int32
    # code equality ⇔ full key-tuple equality
    ldf = pd.DataFrame({k: lt.columns[k] for k in ("a", "b", "c")})
    rdf = pd.DataFrame({k: rt.columns[k] for k in ("a", "b", "c")})
    merged = pd.merge(ldf.assign(lc=lcodes[0]), rdf.assign(rc=rcodes[0]), on=["a", "b", "c"])
    assert (merged["lc"] == merged["rc"]).all()
    # codes must also be order-preserving within the shared space
    order = np.argsort(lcodes[0], kind="stable")
    sorted_tuples = list(zip(*(lt.columns[k][order] for k in ("a", "b", "c"))))
    assert sorted_tuples == sorted(sorted_tuples)


def test_multislice_mesh_build_matches_single_axis():
    """(dcn, x) multi-slice mesh: the exchange over combined axes must
    produce the same per-bucket contents as the 1-D ICI mesh."""
    import tempfile
    from pathlib import Path

    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu.dataset import Dataset
    from hyperspace_tpu.execution import io as hio
    from hyperspace_tpu.execution.builder import DeviceIndexBuilder
    from hyperspace_tpu.parallel.mesh import make_mesh, make_multislice_mesh

    tmp = Path(tempfile.mkdtemp())
    data = tmp / "d"
    data.mkdir()
    rng = np.random.default_rng(0)
    n = 2048
    pq.write_table(
        pa.table(
            {
                "k": rng.integers(0, 500, n).astype(np.int64),
                "v": rng.standard_normal(n),
            }
        ),
        data / "p.parquet",
    )
    ds = Dataset.parquet(data)
    d1 = tmp / "idx1" / "v__=0"
    d2 = tmp / "idx2" / "v__=0"
    DeviceIndexBuilder(mesh=make_mesh()).write(ds.scan(), ["k", "v"], ["k"], 16, d1)
    DeviceIndexBuilder(mesh=make_multislice_mesh(2)).write(ds.scan(), ["k", "v"], ["k"], 16, d2)
    m1, m2 = hio.read_manifest(d1), hio.read_manifest(d2)
    assert m1["bucketRows"] == m2["bucketRows"]
    for b in range(16):
        t1 = hio.read_parquet([str(d1 / hio.bucket_file_name(b))])
        t2 = hio.read_parquet([str(d2 / hio.bucket_file_name(b))])
        assert np.array_equal(np.sort(t1.columns["k"]), np.sort(t2.columns["k"]))


def test_merge_join_sharded_matches_single_device():
    """The bucket-sharded distributed SMJ must emit exactly the same match
    set as the single-device kernel, for both the pack16 and wide paths."""
    from hyperspace_tpu.ops import join as join_ops
    from hyperspace_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    rng = np.random.default_rng(7)
    # Second case: 17+16 index bits > 32 forces the UNPACKED (interleaved)
    # sharded output path.
    for L, R in [(64, 96), (1 << 17, 1 << 16)]:
        B = 16
        s = join_ops.sentinel_for(np.int32)
        lk = np.full((B, L), s, np.int32)
        rk = np.full((B, R), s, np.int32)
        for b in range(B):
            nl, nr = rng.integers(1, min(L, 64)), rng.integers(1, min(R, 64))
            lk[b, :nl] = np.sort(rng.integers(0, 40, nl)).astype(np.int32)
            rk[b, :nr] = np.sort(rng.integers(0, 40, nr)).astype(np.int32)
        li1, ri1, t1 = join_ops.merge_join(lk, rk)
        li2, ri2, t2 = join_ops.merge_join_sharded(lk, rk, mesh)
        assert np.array_equal(t1, t2)
        # Match pairs per bucket must agree as sets.
        o1 = np.concatenate([[0], np.cumsum(t1)])
        for b in range(B):
            p1 = set(zip(li1[o1[b]:o1[b+1]].tolist(), ri1[o1[b]:o1[b+1]].tolist()))
            p2 = set(zip(li2[o1[b]:o1[b+1]].tolist(), ri2[o1[b]:o1[b+1]].tolist()))
            assert p1 == p2


def test_e2e_join_distributed_on_mesh(tmp_path):
    """Full query path with a session mesh: the rewritten join must run
    bucket-sharded over all 8 virtual devices and match the un-indexed
    result row-for-row (the device kernel is the subject — pinned
    explicitly)."""
    from hyperspace_tpu.config import JOIN_VENUE
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
    from hyperspace_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(3)
    n = 4000
    fact_root = tmp_path / "fact"
    fact_root.mkdir()
    pq.write_table(
        pa.table({
            "k": rng.integers(0, 200, n).astype(np.int64),
            "v": rng.standard_normal(n),
        }),
        fact_root / "f.parquet",
    )
    dim_root = tmp_path / "dim"
    dim_root.mkdir()
    pq.write_table(
        pa.table({
            "k": np.arange(200, dtype=np.int64),
            "label": pa.array([f"l{i % 5}" for i in range(200)]),
        }),
        dim_root / "d.parquet",
    )
    session = HyperspaceSession(
        system_path=str(tmp_path / "idx"), num_buckets=16, mesh=make_mesh()
    )
    session.conf.set(JOIN_VENUE, "device")
    hs = Hyperspace(session)
    fact = session.parquet(fact_root)
    dim = session.parquet(dim_root)
    hs.create_index(fact, IndexConfig("f_k", ["k"], ["v"]))
    hs.create_index(dim, IndexConfig("d_k", ["k"], ["label"]))
    q = fact.select("k", "v").join(dim.select("k", "label"), ["k"])

    session.disable_hyperspace()
    expected = session.to_pandas(q).sort_values(["k", "v"]).reset_index(drop=True)
    session.enable_hyperspace()
    got = session.to_pandas(q).sort_values(["k", "v"]).reset_index(drop=True)
    stats = session.last_query_stats
    assert stats["join_path"] == "zero-exchange-aligned"
    assert stats["join_devices"] == 8
    assert got.equals(expected[got.columns.tolist()])


def test_mesh_distributed_top_n_matches_host(tmp_path):
    """ORDER BY ... LIMIT n over an 8-device mesh: per-shard first-n
    selection + threshold mask must match the single-device result
    exactly (ties included)."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import HyperspaceSession
    from hyperspace_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(41)
    n = 200_000
    df = pd.DataFrame(
        {
            "v": np.round(rng.normal(size=n), 2),  # heavy ties
            "tag": rng.integers(0, 1000, n).astype(np.int64),
        }
    )
    root = tmp_path / "topn"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), root / "p.parquet")

    outs = {}
    for mesh in (None, make_mesh()):
        session = HyperspaceSession(
            system_path=str(tmp_path / f"idx_{mesh is None}"), num_buckets=4, mesh=mesh
        )
        if mesh is not None:
            # Pin the venue: the assertion below is about the device
            # kernel.
            from hyperspace_tpu.config import SORT_VENUE

            session.conf.set(SORT_VENUE, "device")
        ds = session.parquet(root)
        q = ds.sort([("v", False), ("tag", True)]).limit(25)
        outs[mesh is None] = session.to_pandas(q).reset_index(drop=True)
        if mesh is not None:
            plan = repr(session.last_physical_plan)
            assert "mesh-sharded-select" in plan, plan
    pd.testing.assert_frame_equal(outs[True], outs[False])
    exp = (
        df.sort_values(["v", "tag"], ascending=[False, True]).head(25).reset_index(drop=True)
    )
    np.testing.assert_allclose(outs[False]["v"], exp["v"])
    np.testing.assert_array_equal(outs[False]["tag"], exp["tag"])
