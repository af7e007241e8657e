"""Aggregate / Sort / Limit: device kernels vs pandas ground truth, the
fused Aggregate(Join) path vs the materialized join, and rewrite rules
firing underneath aggregation (the engine-side operators the TPU build
owns, SURVEY.md §2.2)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import AggSpec, Hyperspace, HyperspaceSession, IndexConfig, col
from hyperspace_tpu.parallel.mesh import make_mesh


@pytest.fixture
def sales(tmp_path):
    rng = np.random.default_rng(21)
    n = 5_000
    nulls = rng.random(n) < 0.1
    t = pa.table(
        {
            "store": pa.array([f"s{int(i) % 7}" for i in rng.integers(0, 7, n)]),
            "item": rng.integers(0, 50, n).astype(np.int64),
            "qty": pa.array(rng.integers(1, 20, n).astype(np.int64), mask=nulls),
            "price": rng.random(n) * 100,
        }
    )
    root = tmp_path / "sales"
    root.mkdir()
    pq.write_table(t, root / "part-0.parquet")
    return root


def _session(tmp_path, **kw):
    return HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=8, **kw)


def test_grouped_aggregation_matches_pandas(tmp_path, sales):
    session = _session(tmp_path)
    df = session.parquet(sales)
    q = df.aggregate(
        ["store"],
        [
            AggSpec.of("sum", "qty", "total_qty"),
            AggSpec.of("count", None, "rows"),
            AggSpec.of("count", "qty", "qty_rows"),
            AggSpec.of("mean", "price", "avg_price"),
            AggSpec.of("min", "price", "min_price"),
            AggSpec.of("max", "item", "max_item"),
            AggSpec.of("sum", col("qty") * col("price"), "revenue"),
        ],
    )
    got = session.to_pandas(q).sort_values("store").reset_index(drop=True)

    pdf = pq.read_table(sales).to_pandas()
    exp = (
        pdf.groupby("store")
        .agg(
            total_qty=("qty", "sum"),
            rows=("store", "size"),
            qty_rows=("qty", "count"),
            avg_price=("price", "mean"),
            min_price=("price", "min"),
            max_item=("item", "max"),
        )
        .reset_index()
        .sort_values("store")
        .reset_index(drop=True)
    )
    exp["revenue"] = (
        (pdf["qty"] * pdf["price"]).groupby(pdf["store"]).sum().sort_index().values
    )
    assert list(got["store"]) == list(exp["store"])
    np.testing.assert_allclose(got["total_qty"].astype(float), exp["total_qty"].astype(float))
    np.testing.assert_array_equal(got["rows"], exp["rows"])
    np.testing.assert_array_equal(got["qty_rows"], exp["qty_rows"])
    np.testing.assert_allclose(got["avg_price"], exp["avg_price"])
    np.testing.assert_allclose(got["min_price"], exp["min_price"])
    np.testing.assert_array_equal(got["max_item"], exp["max_item"])
    np.testing.assert_allclose(got["revenue"], exp["revenue"])


def test_global_aggregate_and_string_minmax(tmp_path, sales):
    session = _session(tmp_path)
    df = session.parquet(sales)
    q = df.aggregate(
        [],
        [
            AggSpec.of("count", None, "n"),
            AggSpec.of("sum", "price", "sum_price"),
            AggSpec.of("min", "store", "min_store"),
            AggSpec.of("max", "store", "max_store"),
        ],
    )
    got = session.to_pandas(q)
    pdf = pq.read_table(sales).to_pandas()
    assert got["n"][0] == len(pdf)
    np.testing.assert_allclose(got["sum_price"][0], pdf["price"].sum())
    assert got["min_store"][0] == pdf["store"].min()
    assert got["max_store"][0] == pdf["store"].max()


@pytest.mark.parametrize("venue", ["device", "host"])
def test_null_group_key_and_all_null_group(tmp_path, venue):
    from hyperspace_tpu.config import AGG_VENUE

    t = pa.table(
        {
            "k": pa.array([1, 1, None, None, 2], type=pa.int64()),
            "v": pa.array([10.0, None, 5.0, 7.0, None]),
        }
    )
    root = tmp_path / "nulls"
    root.mkdir()
    pq.write_table(t, root / "p.parquet")
    session = _session(tmp_path)
    session.conf.set(AGG_VENUE, venue)
    df = session.parquet(root)
    q = df.aggregate(["k"], [AggSpec.of("sum", "v", "sv"), AggSpec.of("count", "v", "cv")])
    got = session.to_pandas(q)
    by_k = {row["k"]: row for _, row in got.iterrows()}
    assert by_k[1]["sv"] == 10.0 and by_k[1]["cv"] == 1
    # null key forms its own group
    null_rows = got[got["k"].isna()]
    assert len(null_rows) == 1 and null_rows["sv"].iloc[0] == 12.0
    # group 2 has only null inputs -> NULL sum, count 0
    g2 = got[got["k"] == 2]
    assert g2["cv"].iloc[0] == 0 and pd.isna(g2["sv"].iloc[0])


def test_sort_and_limit(tmp_path, sales):
    session = _session(tmp_path)
    df = session.parquet(sales)
    q = df.select("store", "item", "price").sort([("store", True), ("price", False)]).limit(100)
    got = session.to_pandas(q)
    pdf = pq.read_table(sales).to_pandas()
    exp = (
        pdf[["store", "item", "price"]]
        .sort_values(["store", "price"], ascending=[True, False], kind="stable")
        .head(100)
        .reset_index(drop=True)
    )
    np.testing.assert_array_equal(got["store"], exp["store"])
    np.testing.assert_allclose(got["price"], exp["price"])


def test_sort_desc_nulls_last(tmp_path):
    t = pa.table({"v": pa.array([3.0, None, 1.0, 2.0, None])})
    root = tmp_path / "sn"
    root.mkdir()
    pq.write_table(t, root / "p.parquet")
    session = _session(tmp_path)
    got = session.to_pandas(session.parquet(root).sort([("v", False)]))
    vals = list(got["v"])
    assert vals[:3] == [3.0, 2.0, 1.0]
    assert all(pd.isna(v) for v in vals[3:])


@pytest.fixture
def join_tables(tmp_path):
    rng = np.random.default_rng(5)
    n = 8_000
    fact_root = tmp_path / "fact"
    fact_root.mkdir()
    pq.write_table(
        pa.table(
            {
                "k": rng.integers(0, 300, n).astype(np.int64),
                "amount": rng.random(n) * 50,
                "units": rng.integers(1, 9, n).astype(np.int64),
            }
        ),
        fact_root / "f.parquet",
    )
    dim_root = tmp_path / "dim"
    dim_root.mkdir()
    pq.write_table(
        pa.table(
            {
                "k": np.arange(250, dtype=np.int64),  # keys 250..299 unmatched
                "cat": pa.array([f"c{i % 6}" for i in range(250)]),
                "weight": np.round(np.random.default_rng(6).random(250), 3),
            }
        ),
        dim_root / "d.parquet",
    )
    return fact_root, dim_root


def _expected_join_agg(fact_root, dim_root, group, aggs):
    f = pq.read_table(fact_root).to_pandas()
    d = pq.read_table(dim_root).to_pandas()
    j = f.merge(d, on="k")
    g = j.groupby(group) if group else None
    return j, g


@pytest.mark.parametrize("with_index", [False, True])
def test_fused_join_aggregate_matches_pandas(tmp_path, join_tables, with_index):
    fact_root, dim_root = join_tables
    session = _session(tmp_path, mesh=make_mesh())
    hs = Hyperspace(session)
    fact = session.parquet(fact_root)
    dim = session.parquet(dim_root)
    if with_index:
        hs.create_index(fact, IndexConfig("f_k", ["k"], ["amount", "units"]))
        hs.create_index(dim, IndexConfig("d_k", ["k"], ["cat", "weight"]))
        session.enable_hyperspace()
    q = fact.join(dim, ["k"]).aggregate(
        ["cat"],
        [
            AggSpec.of("sum", "amount", "sum_amount"),  # left measure
            AggSpec.of("sum", "weight", "sum_weight"),  # right measure
            AggSpec.of("count", None, "pairs"),
            AggSpec.of("mean", "amount", "avg_amount"),
            AggSpec.of("sum", col("amount") * col("units"), "revenue"),
        ],
    )
    got = session.to_pandas(q).sort_values("cat").reset_index(drop=True)
    assert session.last_query_stats["agg_path"] == "fused-join-agg"
    if with_index:
        assert session.last_query_stats["join_path"] == "zero-exchange-aligned"

    f = pq.read_table(fact_root).to_pandas()
    d = pq.read_table(dim_root).to_pandas()
    j = f.merge(d, on="k")
    exp = (
        j.groupby("cat")
        .agg(
            sum_amount=("amount", "sum"),
            sum_weight=("weight", "sum"),
            pairs=("cat", "size"),
            avg_amount=("amount", "mean"),
        )
        .reset_index()
        .sort_values("cat")
        .reset_index(drop=True)
    )
    exp["revenue"] = (j["amount"] * j["units"]).groupby(j["cat"]).sum().sort_index().values
    assert list(got["cat"]) == list(exp["cat"])
    np.testing.assert_allclose(got["sum_amount"], exp["sum_amount"])
    np.testing.assert_allclose(got["sum_weight"], exp["sum_weight"])
    np.testing.assert_array_equal(got["pairs"], exp["pairs"])
    np.testing.assert_allclose(got["avg_amount"], exp["avg_amount"])
    np.testing.assert_allclose(got["revenue"], exp["revenue"])


def test_fused_join_agg_group_by_left_side(tmp_path, join_tables):
    fact_root, dim_root = join_tables
    session = _session(tmp_path)
    fact = session.parquet(fact_root)
    dim = session.parquet(dim_root)
    q = fact.join(dim, ["k"]).aggregate(
        ["k"], [AggSpec.of("sum", "weight", "w"), AggSpec.of("count", None, "n")]
    )
    got = session.to_pandas(q).sort_values("k").reset_index(drop=True)
    f = pq.read_table(fact_root).to_pandas()
    d = pq.read_table(dim_root).to_pandas()
    j = f.merge(d, on="k")
    exp = (
        j.groupby("k").agg(w=("weight", "sum"), n=("k", "size")).reset_index()
    ).sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(got["k"], exp["k"])
    np.testing.assert_allclose(got["w"], exp["w"])
    np.testing.assert_array_equal(got["n"], exp["n"])


def test_join_agg_minmax(tmp_path, join_tables):
    """min/max over a join fuse on BOTH venues: the host C++ pass walks
    per-key runs; the device kernel's run-extremum channels take the
    segmented prefix scan at each run end. Results identical either way,
    covering secondary-side (amount), primary-side (weight), and mixed
    sibling aggregates."""
    from hyperspace_tpu import native
    from hyperspace_tpu.config import JOIN_VENUE

    fact_root, dim_root = join_tables
    f = pq.read_table(fact_root).to_pandas()
    d = pq.read_table(dim_root).to_pandas()
    j = f.merge(d, on="k")
    exp = (
        j.groupby("cat")
        .agg(mx=("amount", "max"), mn=("amount", "min"), wmx=("weight", "max"),
             sa=("amount", "sum"), n=("cat", "size"))
        .reset_index()
        .sort_values("cat")
        .reset_index(drop=True)
    )
    outs = {}
    for venue in ("host", "device"):
        if venue == "host" and not native.available():
            continue
        session = _session(tmp_path)
        session.conf.set(JOIN_VENUE, venue)
        fact = session.parquet(fact_root)
        dim = session.parquet(dim_root)
        q = fact.join(dim, ["k"]).aggregate(
            ["cat"],
            [
                AggSpec.of("max", "amount", "mx"),
                AggSpec.of("min", "amount", "mn"),
                AggSpec.of("max", "weight", "wmx"),
                AggSpec.of("sum", "amount", "sa"),
                AggSpec.of("count", None, "n"),
            ],
        )
        got = session.to_pandas(q).sort_values("cat").reset_index(drop=True)
        assert session.last_query_stats["agg_path"] == "fused-join-agg"
        expected_kernel = (
            "host-native-merge-accumulate" if venue == "host" else "device-run-prefix"
        )
        assert session.last_query_stats["join_kernel"] == expected_kernel
        outs[venue] = got
        assert list(got["cat"]) == list(exp["cat"])
        for c in ("mx", "mn", "wmx", "sa"):
            np.testing.assert_allclose(got[c], exp[c], rtol=1e-9, err_msg=f"{venue}.{c}")
        np.testing.assert_array_equal(got["n"], exp["n"])
    if len(outs) == 2:
        pd.testing.assert_frame_equal(outs["host"], outs["device"])


@pytest.mark.parametrize("venue", ["host", "device"])
def test_fused_minmax_with_nulls_and_unmatched(tmp_path, venue):
    """Fused min/max null semantics on BOTH venues (the device venue
    runs the segmented-prefix-scan run-extremum channels): null measure
    values are ignored, a group whose matched rows are all-null yields
    NULL, multiplicity does not skew extrema (duplicate keys), results
    equal the materialized join."""
    from hyperspace_tpu import native
    from hyperspace_tpu.config import JOIN_VENUE

    if venue == "host" and not native.available():
        pytest.skip("native library not built")
    rng = np.random.default_rng(51)
    n = 4_000
    amount = rng.random(n) * 100
    nulls = rng.random(n) < 0.2
    fact = pa.table(
        {
            "k": rng.integers(0, 80, n).astype(np.int64),
            "amount": pa.array(np.where(nulls, 0.0, amount), mask=nulls),
        }
    )
    dim = pa.table(
        {
            "k": np.arange(60, dtype=np.int64),  # keys 60..79 unmatched
            "cat": pa.array([f"c{i % 5}" for i in range(60)]),
        }
    )
    (tmp_path / "f").mkdir()
    (tmp_path / "d").mkdir()
    pq.write_table(fact, tmp_path / "f" / "p.parquet")
    pq.write_table(dim, tmp_path / "d" / "p.parquet")
    session = _session(tmp_path)
    session.conf.set(JOIN_VENUE, venue)
    fs, ds = session.parquet(tmp_path / "f"), session.parquet(tmp_path / "d")
    q = fs.join(ds, ["k"]).aggregate(
        ["cat"],
        [
            AggSpec.of("min", "amount", "mn"),
            AggSpec.of("max", "amount", "mx"),
            AggSpec.of("sum", "amount", "sm"),
        ],
    )
    got = session.to_pandas(q).sort_values("cat").reset_index(drop=True)
    assert session.last_query_stats["agg_path"] == "fused-join-agg"
    expected_kernel = (
        "host-native-merge-accumulate" if venue == "host" else "device-run-prefix"
    )
    assert session.last_query_stats["join_kernel"] == expected_kernel
    fpd = fact.to_pandas()
    jm = fpd.merge(dim.to_pandas(), on="k")
    exp = (
        jm.groupby("cat")
        .agg(mn=("amount", "min"), mx=("amount", "max"), sm=("amount", "sum"))
        .reset_index()
    )
    np.testing.assert_allclose(got["mn"].astype(float), exp["mn"].astype(float), rtol=1e-9)
    np.testing.assert_allclose(got["mx"].astype(float), exp["mx"].astype(float), rtol=1e-9)
    np.testing.assert_allclose(got["sm"].astype(float), exp["sm"].astype(float), rtol=1e-9)


def test_aggregate_over_index_rewrite_and_explain(tmp_path, sales):
    """Rules must fire underneath an Aggregate, and explain must render
    the new nodes."""
    session = _session(tmp_path)
    hs = Hyperspace(session)
    df = session.parquet(sales)
    hs.create_index(df, IndexConfig("sidx", ["item"], ["qty", "price"]))
    session.enable_hyperspace()
    q = df.filter(col("item") == 7).aggregate([], [AggSpec.of("sum", "qty", "sq")])
    opt = session.optimized_plan(q)
    assert any(s.bucket_spec is not None for s in opt.leaves()), "rewrite under Aggregate missed"
    got = session.to_pandas(q)
    session.disable_hyperspace()
    exp = session.to_pandas(q)
    assert got["sq"][0] == exp["sq"][0]
    text = hs.explain(q)
    assert "Aggregate" in text


def test_aggregate_plan_roundtrips_json(tmp_path, sales):
    from hyperspace_tpu.plan.nodes import plan_from_json

    session = _session(tmp_path)
    df = session.parquet(sales)
    q = df.aggregate(["store"], [AggSpec.of("sum", col("qty") * col("price"), "rev")]).sort(
        [("rev", False)]
    ).limit(3)
    rt = plan_from_json(q.to_json())
    assert rt.to_json() == q.to_json()
    got = session.to_pandas(q)
    got2 = session.to_pandas(rt)
    pd.testing.assert_frame_equal(got, got2)


def test_count_star_only_prunes_to_one_column_not_zero(tmp_path, sales):
    """count(*) with no group_by references no columns; pruning must keep
    at least one scan column or num_rows collapses to 0."""
    session = _session(tmp_path)
    df = session.parquet(sales)
    got = session.to_pandas(df.aggregate([], [AggSpec.of("count", None, "n")]))
    assert got["n"][0] == pq.read_table(sales).num_rows


def test_fused_join_agg_empty_primary_side(tmp_path, join_tables):
    """Global aggregate over a join whose primary (left) side is empty:
    one row with count 0 and NULL sum, not an IndexError."""
    _, dim_root = join_tables
    empty_root = tmp_path / "empty_fact"
    empty_root.mkdir()
    pq.write_table(
        pa.table(
            {
                "k": np.zeros(0, np.int64),
                "amount": np.zeros(0, np.float64),
            }
        ),
        empty_root / "f.parquet",
    )
    session = _session(tmp_path)
    fact = session.parquet(empty_root)
    dim = session.parquet(dim_root)
    q = fact.join(dim, ["k"]).aggregate(
        [], [AggSpec.of("count", None, "n"), AggSpec.of("sum", "amount", "s")]
    )
    got = session.to_pandas(q)
    assert session.last_query_stats["agg_path"] == "fused-join-agg"
    assert len(got) == 1
    assert got["n"][0] == 0
    assert pd.isna(got["s"][0])

    # Grouped variant: no groups at all.
    q2 = fact.join(dim, ["k"]).aggregate(["k"], [AggSpec.of("count", None, "n")])
    assert len(session.to_pandas(q2)) == 0


def test_count_star_over_projected_table(tmp_path, sales):
    """Pruning must not collapse a Project to zero columns either."""
    session = _session(tmp_path)
    df = session.parquet(sales).select("price")
    got = session.to_pandas(df.aggregate([], [AggSpec.of("count", None, "n")]))
    assert got["n"][0] == pq.read_table(sales).num_rows


def test_sum_of_constant_expression(tmp_path, join_tables):
    """sum(lit(2)) == 2 * count(*): constant expressions broadcast instead
    of crashing, on both the plain and the join paths."""
    from hyperspace_tpu.plan.expr import lit

    fact_root, dim_root = join_tables
    session = _session(tmp_path)
    fact = session.parquet(fact_root)
    dim = session.parquet(dim_root)

    got = session.to_pandas(
        fact.aggregate([], [AggSpec.of("sum", lit(2), "s"), AggSpec.of("count", None, "n")])
    )
    assert got["s"][0] == 2 * got["n"][0] == 2 * pq.read_table(fact_root).num_rows

    got2 = session.to_pandas(
        fact.join(dim, ["k"]).aggregate(
            [], [AggSpec.of("sum", lit(2), "s"), AggSpec.of("count", None, "n")]
        )
    )
    f = pq.read_table(fact_root).to_pandas()
    d = pq.read_table(dim_root).to_pandas()
    pairs = len(f.merge(d, on="k"))
    assert got2["n"][0] == pairs and got2["s"][0] == 2 * pairs


def test_agg_host_venue_matches_device(tmp_path, sales):
    """The numpy host reduce must match the device segment-reduce on all
    fns incl. null inputs and string (dict-code) min/max."""
    from hyperspace_tpu.config import AGG_VENUE

    q_args = (
        ["item"],
        [
            AggSpec.of("sum", "qty", "s"),
            AggSpec.of("count", None, "n"),
            AggSpec.of("count", "qty", "nq"),
            AggSpec.of("mean", "price", "m"),
            AggSpec.of("min", "qty", "mn"),
            AggSpec.of("max", "price", "mx"),
            AggSpec.of("min", "store", "smn"),
            AggSpec.of("max", "store", "smx"),
        ],
    )
    outs = {}
    for venue in ("device", "host"):
        session = _session(tmp_path, **{})
        session.conf.set(AGG_VENUE, venue)
        df = session.parquet(sales)
        outs[venue] = (
            session.to_pandas(df.aggregate(*q_args)).sort_values("item").reset_index(drop=True)
        )
        assert session.last_query_stats["agg_path"] == f"segment-reduce-{venue}"
    d, h = outs["device"], outs["host"]
    assert list(d["item"]) == list(h["item"])
    for c in ("s", "n", "nq", "m", "mn", "mx"):
        np.testing.assert_allclose(d[c].astype(float), h[c].astype(float), rtol=1e-12)
    assert list(d["smn"]) == list(h["smn"])
    assert list(d["smx"]) == list(h["smx"])


def test_sort_host_venue_matches_device(tmp_path, sales):
    from hyperspace_tpu.config import SORT_VENUE

    outs = {}
    for venue in ("device", "host"):
        session = _session(tmp_path)
        session.conf.set(SORT_VENUE, venue)
        df = session.parquet(sales)
        q = df.select("store", "item", "price").sort([("store", True), ("price", False)]).limit(50)
        outs[venue] = session.to_pandas(q)
    pd.testing.assert_frame_equal(outs["device"], outs["host"])


def test_sort_requires_keys():
    from hyperspace_tpu.plan.nodes import Scan, Sort
    from hyperspace_tpu.schema import Field, Schema

    scan = Scan("/x", "parquet", Schema.of(Field("a", "int64")))
    with pytest.raises(ValueError, match="at least one"):
        Sort(scan, [])
    with pytest.raises(ValueError, match="at least one"):
        scan.sort([])


def test_mesh_sharded_aggregation_matches_single_device(tmp_path, sales):
    """With a multi-device mesh, the device segment-reduce shards the row
    dimension and combines [A, K] partials with one collective per
    channel; results must equal the single-device reduce."""
    from hyperspace_tpu.config import AGG_VENUE

    q_args = (
        ["item"],
        [
            AggSpec.of("sum", "qty", "s"),
            AggSpec.of("count", None, "n"),
            AggSpec.of("mean", "price", "m"),
            AggSpec.of("min", "price", "mn"),
            AggSpec.of("max", "qty", "mx"),
        ],
    )
    outs = {}
    for name, mesh in (("single", None), ("mesh", make_mesh())):
        session = _session(tmp_path, mesh=mesh)
        session.conf.set(AGG_VENUE, "device")
        df = session.parquet(sales)
        outs[name] = (
            session.to_pandas(df.aggregate(*q_args)).sort_values("item").reset_index(drop=True)
        )
        if name == "mesh":
            assert session.last_query_stats.get("agg_devices", 1) > 1
    pd.testing.assert_frame_equal(outs["single"], outs["mesh"])


@pytest.mark.parametrize("venue", ["device", "host"])
def test_case_when_conditional_aggregate(tmp_path, sales, venue):
    """SQL CASE WHEN inside aggregates (the TPC-H Q12/Q14 shape):
    string-literal conditions with 3-valued nulls, numeric value legs,
    identical across venues and vs pandas."""
    from hyperspace_tpu import when
    from hyperspace_tpu.config import AGG_VENUE
    from hyperspace_tpu.plan.expr import lit as L

    session = _session(tmp_path)
    session.conf.set(AGG_VENUE, venue)
    df = session.parquet(sales)
    is_s1 = (col("store") == L("s1")) | (col("store") == L("s2"))
    expr = when(is_s1, col("price")).otherwise(0.0)
    flag = when(col("qty") > L(10), 1.0).otherwise(0.0)  # qty has nulls
    q = df.aggregate(
        ["item"],
        [
            AggSpec.of("sum", expr, "s12_price"),
            AggSpec.of("sum", flag, "big_qty"),
        ],
    ).sort(["item"])
    got = session.to_pandas(q)

    pdf = pq.read_table(sales).to_pandas()
    exp_price = np.where(pdf.store.isin(["s1", "s2"]), pdf.price, 0.0)
    # null qty: condition is NULL -> branch not taken -> 0.0 (default leg)
    exp_flag = np.where(pdf.qty.fillna(-1) > 10, 1.0, 0.0)
    exp = (
        pd.DataFrame({"item": pdf.item, "p": exp_price, "f": exp_flag})
        .groupby("item")
        .sum()
        .reset_index()
        .sort_values("item")
        .reset_index(drop=True)
    )
    np.testing.assert_array_equal(got["item"], exp["item"])
    np.testing.assert_allclose(got["s12_price"], exp["p"])
    np.testing.assert_allclose(got["big_qty"], exp["f"])


def test_case_when_json_roundtrip():
    from hyperspace_tpu import when
    from hyperspace_tpu.plan.expr import expr_from_json, lit as L

    e = when(col("a") > L(1), col("b") * L(2.0)).when(col("a") < L(0), 0.0).otherwise(col("b"))
    j = e.to_json()
    e2 = expr_from_json(j)
    assert e2.to_json() == j
    assert e.references() == {"a", "b"}


def test_nested_case_in_arithmetic_aggregate(tmp_path, sales):
    """Case nested inside arithmetic keeps branch-following validity: a
    null condition takes the ELSE leg instead of poisoning the row, and
    string-literal conditions work at any depth."""
    from hyperspace_tpu import when
    from hyperspace_tpu.plan.expr import lit as L

    session = _session(tmp_path)
    df = session.parquet(sales)
    expr = when(col("qty") > L(10), 1.0).otherwise(2.0) * col("price")
    sexpr = when(col("store") == L("s1"), 1.0).otherwise(0.0) * col("price")
    got = session.to_pandas(
        df.aggregate([], [AggSpec.of("sum", expr, "s"), AggSpec.of("sum", sexpr, "sp")])
    )
    pdf = pq.read_table(sales).to_pandas()
    exp = (np.where(pdf.qty.fillna(-1) > 10, 1.0, 2.0) * pdf.price).sum()
    exp_sp = np.where(pdf.store == "s1", pdf.price, 0.0).sum()
    np.testing.assert_allclose(got["s"][0], exp)
    np.testing.assert_allclose(got["sp"][0], exp_sp)


def test_case_aggregate_takes_fused_join_path(tmp_path, join_tables):
    """A Case spec with string-literal conditions stays eligible for the
    fused Aggregate(Join) kernel (the TPC-H Q12 shape)."""
    from hyperspace_tpu import when
    from hyperspace_tpu.config import AGG_VENUE
    from hyperspace_tpu.plan.expr import lit as L

    fact_root, dim_root = join_tables
    session = _session(tmp_path)
    session.conf.set(AGG_VENUE, "device")
    fact = session.parquet(fact_root)
    dim = session.parquet(dim_root)
    # The condition reads the FACT side so the partial-agg pushdown
    # (which owns the dim-condition shape) stays out of the way.
    q = fact.join(dim, ["k"]).aggregate(
        [], [AggSpec.of("sum", when(col("units") >= L(5), 1.0).otherwise(0.0), "big")]
    )
    got = session.to_pandas(q)
    assert session.last_query_stats["agg_path"] == "fused-join-agg"
    f = pq.read_table(fact_root).to_pandas()
    d = pq.read_table(dim_root).to_pandas()
    j = f.merge(d, on="k")
    np.testing.assert_allclose(got["big"][0], float((j.units >= 5).sum()))


def test_partial_agg_pushdown_dim_case_matches_pandas(tmp_path, join_tables):
    """The q43/q59 shape — SUM(CASE WHEN <dim attr> THEN <fact measure>
    ELSE 0) grouped by dim attributes — pre-aggregates the fact side by
    the join key and re-folds (PartialAggPushdown), matching pandas."""
    from hyperspace_tpu import when
    from hyperspace_tpu.plan.expr import lit as L

    fact_root, dim_root = join_tables
    session = _session(tmp_path)
    fact = session.parquet(fact_root)
    dim = session.parquet(dim_root)
    q = fact.join(dim, ["k"]).aggregate(
        ["cat"],
        [
            AggSpec.of("sum", when(col("weight") > L(0.5), col("amount")).otherwise(0.0), "hv"),
            AggSpec.of("sum", "amount", "tot"),
            AggSpec.of("count", None, "n"),
            AggSpec.of("mean", "units", "mu"),
            AggSpec.of("min", "amount", "lo"),
        ],
    )
    got = session.to_pandas(q).sort_values("cat").reset_index(drop=True)
    assert "PartialAggPushdown" in repr(session.last_physical_plan)
    f = pq.read_table(fact_root).to_pandas()
    d = pq.read_table(dim_root).to_pandas()
    j = f.merge(d, on="k")
    j["hv"] = np.where(j.weight > 0.5, j.amount, 0.0)
    exp = (
        j.groupby("cat")
        .agg(hv=("hv", "sum"), tot=("amount", "sum"), n=("amount", "size"),
             mu=("units", "mean"), lo=("amount", "min"))
        .reset_index()
        .sort_values("cat")
        .reset_index(drop=True)
    )
    np.testing.assert_allclose(got.hv.to_numpy(), exp.hv.to_numpy(), rtol=1e-9)
    np.testing.assert_allclose(got.tot.to_numpy(), exp.tot.to_numpy(), rtol=1e-9)
    np.testing.assert_array_equal(got.n.to_numpy(), exp.n.to_numpy())
    np.testing.assert_allclose(got.mu.to_numpy(), exp.mu.to_numpy(), rtol=1e-12)
    np.testing.assert_allclose(got.lo.to_numpy(), exp.lo.to_numpy(), rtol=1e-12)


@pytest.mark.parametrize("venue,kernel", [
    (None, "device-select"),
    ("host", "host-partition-select"),
    ("device", "device-select"),
])
def test_top_n_matches_full_sort(tmp_path, venue, kernel):
    """ORDER BY + LIMIT takes the venue's select path and must equal the
    full sort exactly, incl. duplicate first keys and DESC order. The
    default venue (device, no mesh) selects on the one device; the host
    venue keeps the partition select."""
    rng = np.random.default_rng(8)
    n = 60_000
    df_ = pd.DataFrame(
        {
            "r": np.round(rng.random(n), 3),  # many exact duplicates
            "id": rng.permutation(n).astype(np.int64),
        }
    )
    root = tmp_path / "top"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df_, preserve_index=False), root / "p.parquet")
    session = _session(tmp_path)
    if venue is not None:
        session.conf.set("hyperspace.sort.venue", venue)
    scan = session.parquet(root)
    got = session.to_pandas(scan.sort([("r", False), ("id", True)]).limit(25))
    node = next(n_ for n_ in session.last_physical_plan.walk() if n_.op == "TopN")
    assert node.detail["kernel"].startswith(kernel)
    exp = df_.sort_values(["r", "id"], ascending=[False, True]).head(25).reset_index(drop=True)
    np.testing.assert_allclose(got["r"], exp["r"])
    np.testing.assert_array_equal(got["id"], exp["id"])
    # limit 0 edge
    assert len(session.to_pandas(scan.sort(["r"]).limit(0))) == 0


@pytest.mark.parametrize("venue", ["device", "host"])
def test_distinct(tmp_path, venue):
    from hyperspace_tpu.config import AGG_VENUE

    df_ = pd.DataFrame(
        {
            "a": [1, 1, 2, 2, 2, None],
            "b": ["x", "x", "y", "y", "z", None],
        }
    )
    root = tmp_path / "d"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df_, preserve_index=False), root / "p.parquet")
    session = _session(tmp_path)
    session.conf.set(AGG_VENUE, venue)
    got = session.to_pandas(session.parquet(root).distinct())
    assert len(got) == 4
    tuples = {(None if pd.isna(a) else int(a), None if (b is None or (isinstance(b, float) and pd.isna(b))) else b)
              for a, b in zip(got["a"], got["b"])}
    assert tuples == {(1, "x"), (2, "y"), (2, "z"), (None, None)}


@pytest.mark.parametrize("with_index", [False, True])
def test_host_fused_join_aggregate_matches_device(tmp_path, join_tables, with_index):
    """The host C++ merge+accumulate fused path must match the device
    run-prefix kernel and pandas, with and without aligned indexes
    (covering both the sorted and permuted code layouts)."""
    from hyperspace_tpu import native
    from hyperspace_tpu.config import JOIN_VENUE

    if not native.available():
        pytest.skip("native library not built")
    fact_root, dim_root = join_tables
    outs = {}
    for venue in ("device", "host"):
        session = _session(tmp_path / venue)
        session.conf.set(JOIN_VENUE, venue)
        hs = Hyperspace(session)
        fact = session.parquet(fact_root)
        dim = session.parquet(dim_root)
        if with_index:
            hs.create_index(fact, IndexConfig("f_k", ["k"], ["amount", "units"]))
            hs.create_index(dim, IndexConfig("d_k", ["k"], ["cat", "weight"]))
            session.enable_hyperspace()
        q = fact.join(dim, ["k"]).aggregate(
            ["cat"],
            [
                AggSpec.of("sum", "amount", "sa"),     # secondary-side measure
                AggSpec.of("sum", "weight", "sw"),     # primary(group)-side measure
                AggSpec.of("count", None, "n"),
                AggSpec.of("mean", "amount", "ma"),
            ],
        )
        outs[venue] = session.to_pandas(q).sort_values("cat").reset_index(drop=True)
        assert session.last_query_stats["agg_path"] == "fused-join-agg"
        if venue == "host":
            assert session.last_query_stats["join_kernel"] == "host-native-merge-accumulate"
    d, h = outs["device"], outs["host"]
    assert list(d["cat"]) == list(h["cat"])
    for c in ("sa", "sw", "n", "ma"):
        np.testing.assert_allclose(d[c].astype(float), h[c].astype(float), rtol=1e-9)

    f = pq.read_table(fact_root).to_pandas()
    dd = pq.read_table(dim_root).to_pandas()
    j = f.merge(dd, on="k")
    exp = (
        j.groupby("cat")
        .agg(sa=("amount", "sum"), sw=("weight", "sum"), n=("cat", "size"), ma=("amount", "mean"))
        .reset_index().sort_values("cat").reset_index(drop=True)
    )
    np.testing.assert_allclose(h["sa"], exp["sa"])
    np.testing.assert_allclose(h["sw"], exp["sw"])
    np.testing.assert_array_equal(h["n"], exp["n"])
    np.testing.assert_allclose(h["ma"], exp["ma"])


@pytest.mark.parametrize("venue", ["device", "host"])
def test_non_finite_float_aggregates_pass_through(tmp_path, venue):
    """sum/min/max results that are legitimately NaN or inf (NaN/inf VALUES
    in a float column) come back as NaN/inf with the row still valid —
    not silently zeroed (round-2 advisor, medium). Matches Spark/numpy."""
    from hyperspace_tpu.config import AGG_VENUE

    t = pa.table(
        {
            "g": pa.array([0, 0, 1, 1, 2, 3], type=pa.int64()),
            "x": pa.array([1.0, np.nan, np.inf, 2.0, 3.0, -np.inf]),
        }
    )
    root = tmp_path / f"nf_{venue}"
    root.mkdir()
    pq.write_table(t, root / "p.parquet")
    session = _session(tmp_path)
    session.conf.set(AGG_VENUE, venue)
    df = session.parquet(root)
    q = df.aggregate(
        ["g"],
        [
            AggSpec.of("sum", "x", "s"),
            AggSpec.of("min", "x", "mn"),
            AggSpec.of("max", "x", "mx"),
        ],
    )
    got = session.to_pandas(q).sort_values("g").reset_index(drop=True)
    exp = (
        t.to_pandas()
        .groupby("g")
        .agg(s=("x", "sum"), mn=("x", "min"), mx=("x", "max"))
        .reset_index()
    )
    # pandas .sum skips NaN; SQL SUM over a NaN VALUE is NaN — pin SQL/
    # numpy semantics explicitly per group.
    assert np.isnan(got.loc[0, "s"]) and np.isnan(got.loc[0, "mn"]) and np.isnan(got.loc[0, "mx"])
    assert got.loc[1, "s"] == np.inf and got.loc[1, "mn"] == 2.0 and got.loc[1, "mx"] == np.inf
    assert got.loc[2, "s"] == 3.0
    assert got.loc[3, "s"] == -np.inf and got.loc[3, "mn"] == -np.inf
    assert not got[["s", "mn", "mx"]].isna().drop(index=0).any().any()
    np.testing.assert_array_equal(got["g"], exp["g"])


def test_host_reduceat_with_trailing_empty_groups():
    """aggregate_arrays_host called with num_groups > max(gid)+1: trailing
    empty groups must not corrupt the LAST non-empty group's min/max
    (round-2 advisor: clamped reduceat starts shrank the prior segment)."""
    from hyperspace_tpu.ops.aggregate import aggregate_arrays_host

    vals = np.array([5.0, 1.0, 9.0])
    gid = np.array([0, 0, 1])
    res, cnt = aggregate_arrays_host(
        [(vals, None, "min"), (vals, None, "max")], gid, num_groups=4
    )
    np.testing.assert_array_equal(res[0][:2], [1.0, 9.0])  # min includes sv[n-1]
    np.testing.assert_array_equal(res[1][:2], [5.0, 9.0])
    assert np.isinf(res[0][2]) and np.isinf(res[0][3])  # empty -> identity
    np.testing.assert_array_equal(cnt[0], [2, 1, 0, 0])


@pytest.mark.parametrize("venue", ["host", "device"])
def test_count_distinct(tmp_path, venue):
    """count(distinct col): two-phase re-aggregation, nulls excluded,
    combinable with plain aggregates (TPC-H Q16's shape)."""
    from hyperspace_tpu.config import AGG_VENUE

    rng = np.random.default_rng(29)
    n = 8_000
    nulls = rng.random(n) < 0.1
    df = pd.DataFrame(
        {
            "g": rng.integers(0, 12, n).astype(np.int64),
            "supp": pd.array(np.where(nulls, 0, rng.integers(0, 300, n)), dtype="Int64"),
            "qty": rng.integers(1, 50, n).astype(np.int64),
        }
    )
    df.loc[nulls, "supp"] = pd.NA
    root = tmp_path / f"cd_{venue}"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), root / "p.parquet")
    session = _session(tmp_path)
    session.conf.set(AGG_VENUE, venue)
    ds = session.parquet(root)

    q = ds.aggregate(
        ["g"],
        [
            AggSpec.of("count_distinct", "supp", "nsupp"),
            AggSpec.of("sum", "qty", "sq"),
            AggSpec.of("count", None, "rows"),
            AggSpec.of("min", "qty", "mn"),
        ],
    )
    got = session.to_pandas(q).sort_values("g").reset_index(drop=True)
    assert "CountDistinctReaggregate" in repr(session.last_physical_plan)
    exp = (
        df.groupby("g")
        .agg(
            nsupp=("supp", "nunique"),
            sq=("qty", "sum"),
            rows=("g", "size"),
            mn=("qty", "min"),
        )
        .reset_index()
    )
    np.testing.assert_array_equal(got["g"], exp["g"])
    np.testing.assert_array_equal(got["nsupp"], exp["nsupp"])
    np.testing.assert_array_equal(got["sq"], exp["sq"])
    np.testing.assert_array_equal(got["rows"], exp["rows"])
    np.testing.assert_array_equal(got["mn"], exp["mn"])

    # Global (no group) variant.
    got = session.to_pandas(ds.aggregate([], [AggSpec.of("count_distinct", "supp", "ns")]))
    assert int(got.loc[0, "ns"]) == int(df.supp.nunique())


def test_multi_distinct_and_mean_share_aggregate(tmp_path):
    """TPC-DS q38/q87 shapes: several distinct columns AND mean in ONE
    aggregate, via the distinct-expansion path (Spark's Expand analog):
    one child execution, one group factorization, pair-factorized
    distinct counts — no join, no re-execution."""
    rng = np.random.default_rng(31)
    n = 6_000
    null_a = rng.random(n) < 0.08
    df = pd.DataFrame(
        {
            "g": rng.integers(0, 9, n).astype(np.int64),
            "a": pd.array(np.where(null_a, 0, rng.integers(0, 40, n)), dtype="Int64"),
            "b": rng.integers(0, 25, n).astype(np.int64),
            "v": np.round(rng.normal(size=n) * 10, 3),
        }
    )
    df.loc[null_a, "a"] = pd.NA
    root = tmp_path / "md"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), root / "p.parquet")
    session = _session(tmp_path)
    ds = session.parquet(root)
    q = ds.aggregate(
        ["g"],
        [
            AggSpec.of("count_distinct", "a", "na"),
            AggSpec.of("count_distinct", "b", "nb"),
            AggSpec.of("mean", "v", "mv"),
            AggSpec.of("sum", "v", "sv"),
            AggSpec.of("count", None, "rows"),
        ],
    )
    got = session.to_pandas(q).sort_values("g").reset_index(drop=True)
    assert "DistinctExpandAggregate" in repr(session.last_physical_plan)
    exp = (
        df.groupby("g")
        .agg(
            na=("a", "nunique"),
            nb=("b", "nunique"),
            mv=("v", "mean"),
            sv=("v", "sum"),
            rows=("g", "size"),
        )
        .reset_index()
    )
    np.testing.assert_array_equal(got["g"], exp["g"])
    np.testing.assert_array_equal(got["na"], exp["na"])
    np.testing.assert_array_equal(got["nb"], exp["nb"])
    np.testing.assert_allclose(got["mv"], exp["mv"], rtol=1e-12)
    np.testing.assert_allclose(got["sv"], exp["sv"], rtol=1e-12)
    np.testing.assert_array_equal(got["rows"], exp["rows"])

    # Global multi-distinct (no groups).
    got = session.to_pandas(
        ds.aggregate(
            [],
            [
                AggSpec.of("count_distinct", "a", "na"),
                AggSpec.of("mean", "b", "mb"),
            ],
        )
    )
    assert int(got.loc[0, "na"]) == int(df.a.nunique())
    assert np.isclose(got.loc[0, "mb"], df.b.mean())


def test_count_distinct_empty_input_counts_are_zero(tmp_path):
    """count(*) / count(col) siblings of count_distinct stay 0 (never
    NULL) over empty input — SQL count is never NULL."""
    t = pa.table({"g": pa.array([], type=pa.int64()), "a": pa.array([], type=pa.int64())})
    root = tmp_path / "cde"
    root.mkdir()
    pq.write_table(t, root / "p.parquet")
    session = _session(tmp_path)
    ds = session.parquet(root)
    got = session.to_pandas(ds.aggregate([], [
        AggSpec.of("count_distinct", "a", "na"),
        AggSpec.of("count", None, "rows"),
    ]))
    assert int(got.loc[0, "na"]) == 0
    assert got.loc[0, "rows"] is not None and int(got.loc[0, "rows"]) == 0


@pytest.mark.parametrize(
    "n,num_groups",
    [(1, 1), (5_000, 7), (3 * 4096, 300), (40_000, 4095)],
)
def test_dense_segment_reduce_matches_scatter_and_numpy(n, num_groups):
    """The accelerators' dense grouped reduction (sums, extrema, pads in
    the dead segment) against the segment scatter and numpy."""
    from hyperspace_tpu.ops.aggregate import _dense_segment_reduce, _pow2, _segment_reduce_many
    from hyperspace_tpu.parallel.x64 import run_x64

    rng = np.random.default_rng(n + num_groups)
    n_pad, k_seg = _pow2(n), _pow2(num_groups + 1)
    gid = np.full(n_pad, num_groups, np.int32)
    gid[:n] = rng.integers(0, num_groups, n)
    v = rng.normal(size=n) * 1e3
    fns = ("sum", "sum", "min", "max")
    vals = np.stack([
        np.pad(v, (0, n_pad - n)),
        np.pad(np.round(v), (0, n_pad - n)),
        np.pad(v, (0, n_pad - n), constant_values=np.inf),
        np.pad(v, (0, n_pad - n), constant_values=-np.inf),
    ])
    dense, scatter = (
        np.asarray(run_x64(lambda f=f: f(vals, gid, num_segments=k_seg, fns=fns)))
        for f in (_dense_segment_reduce, _segment_reduce_many)
    )
    g = gid[:n]
    # Integral sums and extrema agree bit for bit; float sums by order.
    np.testing.assert_array_equal(dense[1:], scatter[1:])
    np.testing.assert_allclose(dense[0], scatter[0], rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(dense[0, :num_groups], np.bincount(g, v, num_groups),
                               rtol=1e-12, atol=1e-9)
    np.testing.assert_array_equal(dense[1, :num_groups], np.bincount(g, np.round(v), num_groups))
    mins = np.full(num_groups, np.inf)
    np.minimum.at(mins, g, v)
    np.testing.assert_array_equal(dense[2, :num_groups], mins)


# -- channels built on the device from the staged base columns -----------------

def _lineitem(n, seed=7, nulls=False):
    """A TPC-H lineitem-shaped table: two flags and the four decimals
    Q1 reads (optionally with nulls in quantity and discount)."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n).astype(np.float64)
    mask = (rng.random(n) < 0.2) if nulls else None
    return pa.table({
        "l_returnflag": pa.array(rng.choice(["A", "N", "R"], n).tolist(), pa.string()),
        "l_linestatus": pa.array(rng.choice(["F", "O"], n).tolist(), pa.string()),
        "l_quantity": pa.array(qty, mask=mask),
        "l_extendedprice": np.round(qty * (900 + rng.random(n) * 1e5) / 100, 2),
        "l_discount": pa.array(np.round(rng.integers(0, 11, n) / 100, 2),
                               mask=None if mask is None else np.roll(mask, 1)),
        "l_tax": np.round(rng.integers(0, 9, n) / 100, 2),
        "l_shipdate": rng.integers(8000, 10500, n).astype(np.int64),
    })


def _q1_specs():
    from hyperspace_tpu import lit

    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return [
        AggSpec.of("sum", "l_quantity", "sum_qty"),
        AggSpec.of("sum", "l_extendedprice", "sum_base_price"),
        AggSpec.of("sum", disc_price, "sum_disc_price"),
        AggSpec.of("sum", disc_price * (lit(1.0) + col("l_tax")), "sum_charge"),
        AggSpec.of("mean", "l_quantity", "avg_qty"),
        AggSpec.of("mean", "l_extendedprice", "avg_price"),
        AggSpec.of("mean", "l_discount", "avg_disc"),
        AggSpec.of("count", None, "count_order"),
    ]


def _agg_both_venues(ct, group_by, aggs):
    """aggregate_table on the device and the host venue, with the
    (device, host) channel counters' moves of the device call."""
    from hyperspace_tpu import stats
    from hyperspace_tpu.execution.exec_common import _TableLeaf
    from hyperspace_tpu.ops.aggregate import aggregate_table
    from hyperspace_tpu.plan.nodes import Aggregate

    schema = Aggregate(_TableLeaf(ct), group_by, aggs).schema
    names = ("device.kernel.agg_channels_device", "device.kernel.agg_channels_host")
    before = [stats.get(c) for c in names]
    dev = aggregate_table(ct, group_by, aggs, schema, venue="device")
    moved = tuple(stats.get(c) - b for c, b in zip(names, before))
    return dev, aggregate_table(ct, group_by, aggs, schema, venue="host"), moved


def _case_table(case):
    from hyperspace_tpu import lit
    from hyperspace_tpu.execution.table import ColumnTable
    from hyperspace_tpu.plan.expr import CaseBuilder

    if case == "q1":
        return ColumnTable.from_arrow(_lineitem(3_000)), ["l_returnflag", "l_linestatus"], _q1_specs()
    if case == "nulls":
        specs = [
            AggSpec.of(fn, e, f"{fn}_{i}")
            for i, e in enumerate(("l_quantity", "l_discount", col("l_quantity") * col("l_discount")))
            for fn in ("sum", "mean", "count", "min", "max")
        ]
        return ColumnTable.from_arrow(_lineitem(2_000, nulls=True)), ["l_linestatus"], specs
    if case == "null_groups":
        t = pa.table({
            "g": pa.array([1, None, 2, None, 1, 3], pa.int64()),
            "x": pa.array([1.5, 2.0, None, 4.0, None, 7.25]),
        })
        specs = [AggSpec.of(fn, "x", fn) for fn in ("sum", "mean", "count", "min", "max")]
        return ColumnTable.from_arrow(t), ["g"], [*specs, AggSpec.of("count", None, "rows")]
    if case in ("n_odd", "n_one"):
        n = 777 if case == "n_odd" else 1
        return ColumnTable.from_arrow(_lineitem(n)), ["l_returnflag"], _q1_specs()
    if case == "empty":
        return ColumnTable.from_arrow(_lineitem(0)), [], _q1_specs()
    assert case == "case"
    big = CaseBuilder([(col("l_returnflag") == lit("R"), col("l_quantity"))]).otherwise(lit(0.0))
    return (
        ColumnTable.from_arrow(_lineitem(1_500)),
        ["l_linestatus"],
        [AggSpec.of("sum", big, "returned_qty"), AggSpec.of("sum", "l_tax", "tax")],
    )


#: Per case, the AggSpecs whose channels the device program builds and
#: those the host builds.
_CHANNEL_PATHS = {
    "q1": (8, 0), "nulls": (15, 0), "null_groups": (6, 0), "n_odd": (8, 0), "n_one": (8, 0),
    "empty": (8, 0), "case": (1, 1),
}


@pytest.mark.parametrize("case", list(_CHANNEL_PATHS))
def test_device_channels_match_host_venue(case):
    """Channels the reduction program builds from the staged base
    columns give the host venue's results bit for bit (XLA:CPU's
    scatter adds rows in the host's order), and each AggSpec counts on
    the path that built its channels: a CASE takes the host build."""
    ct, group_by, aggs = _case_table(case)
    dev, host, got_moved = _agg_both_venues(ct, group_by, aggs)
    assert got_moved == _CHANNEL_PATHS[case]
    assert dev.num_rows == host.num_rows and dev.num_rows > 0
    assert set(dev.columns) == set(host.columns)
    for name in host.columns:
        np.testing.assert_array_equal(dev.columns[name], host.columns[name], err_msg=name)
        assert (dev.validity.get(name) is None) == (host.validity.get(name) is None), name
        if host.validity.get(name) is not None:
            np.testing.assert_array_equal(dev.validity[name], host.validity[name], err_msg=name)


def test_device_channels_are_shape_stable():
    """A stable table uploads each base column once: a second
    aggregation over it hits the device cache for every input and adds
    no entry. Two filtered tables of different row counts under one
    n_pad compile the channel program once."""
    from hyperspace_tpu.execution import device_cache as dcache
    from hyperspace_tpu.execution.table import ColumnTable
    from hyperspace_tpu.obs import runtime as obs_runtime
    from hyperspace_tpu.ops.aggregate import group_ids

    ct = ColumnTable.from_arrow(_lineitem(2_500))
    for a in ct.columns.values():
        dcache.freeze(a)
    group_by = ["l_returnflag", "l_linestatus"]
    gid, k, rep = group_ids(ct, group_by)
    groups = (dcache.freeze(gid), k, rep)

    def run(table, grp=None):
        from hyperspace_tpu.execution.exec_common import _TableLeaf
        from hyperspace_tpu.ops.aggregate import aggregate_table
        from hyperspace_tpu.plan.nodes import Aggregate

        aggs = _q1_specs()
        schema = Aggregate(_TableLeaf(table), group_by, aggs).schema
        return aggregate_table(table, group_by, aggs, schema, venue="device", groups=grp)

    run(ct, groups)
    s1 = dcache.DEVICE_CACHE.stats()
    run(ct, groups)
    s2 = dcache.DEVICE_CACHE.stats()
    # Four base columns and the group-id pad, each served from HBM.
    assert (s2["entries"], s2["misses"], s2["hits"] - s1["hits"]) == (s1["entries"], s1["misses"], 5)

    key = "hyperspace_tpu.ops.aggregate._channel_reduce"
    compiles = lambda: obs_runtime.jit_report().get(key, {}).get("compiles", 0)  # noqa: E731
    small = ColumnTable.from_arrow(_lineitem(600, seed=1))
    run(small)
    after_first = compiles()
    run(ColumnTable.from_arrow(_lineitem(1_000, seed=2)))  # n_pad 1024 for both
    assert compiles() == after_first


def test_q1_channels_deduplicate(tmp_path):
    """Q1's eight AggSpecs reduce six channels (four sums, the discount,
    the shared row count): the span says so, and each mean is its sum
    over the row count on every group."""
    root = tmp_path / "lineitem"
    root.mkdir()
    pq.write_table(_lineitem(4_000), root / "p.parquet")
    session = _session(tmp_path)
    got = session.run(session.parquet(root).aggregate(["l_returnflag", "l_linestatus"], _q1_specs()))

    stack, attrs = [session.last_profile().trace], []
    while stack:
        node = stack.pop()
        if node["name"] == "agg.channels":
            attrs.append(node["attrs"])
        stack.extend(node.get("children", ()))
    assert [a["channels"] for a in attrs] == [6]
    assert attrs[0]["upload_bytes"] == 4096 * (4 * 8 + 4)
    cnt = got.column("count_order").astype(np.float64)
    for avg, total in (("avg_qty", "sum_qty"), ("avg_price", "sum_base_price")):
        np.testing.assert_array_equal(got.column(avg), got.column(total) / cnt)
