"""Device data path: Arrow→device zero-copy staging + fused-kernel
venue parity (docs/architecture.md "device data path").

The contract this suite pins: the THREE execution configurations —
host venues, device venues with staged uploads, and device venues with
the fused Pallas kernels engaged — produce byte-identical results for
every query class (filter / join / group_agg / join_agg) over nullable,
dict-coded, zero-row, and offset-view inputs; the staging layer keeps
eligible columns as zero-copy buffer views (counted) and degrades to
the copied path for everything else; and the byte-budgeted caches
account dict-coded columns at their (codes + dictionary) footprint.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import AggSpec, Hyperspace, HyperspaceSession, IndexConfig, col, lit
from hyperspace_tpu import stats
from hyperspace_tpu.config import (
    AGG_VENUE,
    DEVICE_FUSED_KERNELS,
    DEVICE_STAGING_ENABLED,
    FILTER_VENUE,
    JOIN_VENUE,
    SORT_VENUE,
)
from hyperspace_tpu.execution import device_cache as dc
from hyperspace_tpu.execution import io as hio
from hyperspace_tpu.execution import staging
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.schema import Schema

N = 4_000


@pytest.fixture(autouse=True)
def _staging_on():
    staging.set_enabled(True)
    yield
    staging.set_enabled(True)


@pytest.fixture
def dataset(tmp_path):
    """Fact/dim pair exercising every staging class: null-free ints
    (zero-copy eligible), a nullable int column, dict-coded strings, and
    an INTEGER-VALUED float column (so fused sums are provably exact and
    must engage the Pallas path)."""
    rng = np.random.default_rng(7)
    fact = pa.table(
        {
            "k": rng.integers(0, 200, N).astype(np.int32),
            "q": rng.integers(0, 1000, N).astype(np.float64),  # integral floats
            "n": pa.array(
                [None if i % 7 == 0 else int(i % 97) for i in range(N)],
                type=pa.int64(),
            ),
            "s": pa.array([f"cat_{i % 13:02d}" for i in range(N)]),
        }
    )
    dim = pa.table(
        {
            "k": np.arange(180, dtype=np.int32),
            "w": rng.integers(0, 50, 180).astype(np.float64),
            "t": pa.array([f"tag_{i % 5}" for i in range(180)]),
        }
    )
    (tmp_path / "fact").mkdir()
    (tmp_path / "dim").mkdir()
    pq.write_table(fact, tmp_path / "fact" / "p.parquet")
    pq.write_table(dim, tmp_path / "dim" / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=8)
    hs = Hyperspace(session)
    fs = session.parquet(tmp_path / "fact")
    ds = session.parquet(tmp_path / "dim")
    hs.create_index(fs, IndexConfig("pf_k", ["k"], ["q", "n", "s"]))
    hs.create_index(ds, IndexConfig("pd_k", ["k"], ["w", "t"]))
    session.enable_hyperspace()
    return session, fs, ds


def _canon(table: ColumnTable):
    """Decoded columns in a deterministic row order, for EXACT (bitwise
    for floats — no tolerance) cross-venue comparison."""
    dec = table.decode()
    names = sorted(dec)
    if not names or table.num_rows == 0:
        return {k: np.asarray(v) for k, v in dec.items()}
    keys = [np.asarray(dec[n], dtype="U32") if dec[n].dtype == object else dec[n] for n in reversed(names)]
    order = np.lexsort(tuple(np.nan_to_num(k.astype(np.float64), nan=-1e300) if k.dtype.kind == "f" else k for k in keys))
    return {k: np.asarray(v)[order] for k, v in dec.items()}


def _assert_identical(a: ColumnTable, b: ColumnTable, label: str):
    ca, cb = _canon(a), _canon(b)
    assert set(ca) == set(cb), label
    for name in ca:
        va, vb = ca[name], cb[name]
        assert len(va) == len(vb), (label, name)
        if va.dtype.kind == "f" and vb.dtype.kind == "f":
            # Bitwise: the venues must agree to the last ulp.
            ints = f"i{va.dtype.itemsize}"
            assert np.array_equal(va.view(ints), vb.view(ints)), (label, name)
        else:
            assert np.array_equal(va, vb), (label, name)


_CONFIGS = {
    "host": {"venue": "host", "fused": "off"},
    "device-staged": {"venue": "device", "fused": "off"},
    "pallas-fused": {"venue": "device", "fused": "auto"},
}


def _run_all(session, plan):
    outs = {}
    for name, cfg in _CONFIGS.items():
        for key in (FILTER_VENUE, JOIN_VENUE, AGG_VENUE, SORT_VENUE):
            session.conf.set(key, cfg["venue"])
        session.conf.set(DEVICE_FUSED_KERNELS, cfg["fused"])
        outs[name] = session.run(plan)
    return outs


def _queries(fs, ds):
    return {
        "filter": fs.filter(((col("k") % 3) == 0) & (col("q") > 500.0)),
        "filter_null": fs.filter(col("n") > lit(40)),
        "group_agg": fs.aggregate(
            ["s"],
            [
                AggSpec.of("sum", "q", "sq"),
                AggSpec.of("count", None, "cnt"),
                AggSpec.of("min", "q", "mn"),
                AggSpec.of("max", "n", "mx"),
            ],
        ),
        "join": fs.join(ds, ["k"]),
        "join_agg": fs.join(ds, ["k"]).aggregate(
            ["s"], [AggSpec.of("sum", "w", "sw"), AggSpec.of("count", None, "cnt")]
        ),
        "zero_row": fs.filter(col("q") > 1e9),
        "zero_row_agg": fs.filter(col("q") > 1e9).aggregate(
            ["s"], [AggSpec.of("sum", "q", "sq")]
        ),
    }


@pytest.mark.parametrize("qname", [
    "filter", "filter_null", "group_agg", "join", "join_agg", "zero_row", "zero_row_agg",
])
def test_venue_parity_byte_identical(dataset, qname):
    session, fs, ds = dataset
    plan = _queries(fs, ds)[qname]
    outs = _run_all(session, plan)
    _assert_identical(outs["host"], outs["device-staged"], f"{qname}: host vs staged")
    _assert_identical(outs["host"], outs["pallas-fused"], f"{qname}: host vs pallas")


def test_non_integral_device_sum_matches_host_bitwise(dataset):
    session, fs, ds = dataset
    # q/3 is not integral: the device's float64 lax reduce must still
    # give the host venue's bits.
    plan = fs.aggregate([], [AggSpec.of("sum", col("q") / lit(3.0), "x")])
    for key in (FILTER_VENUE, JOIN_VENUE, AGG_VENUE, SORT_VENUE):
        session.conf.set(key, "device")
    session.conf.set(DEVICE_FUSED_KERNELS, "auto")
    out = session.run(plan)
    for key in (FILTER_VENUE, JOIN_VENUE, AGG_VENUE, SORT_VENUE):
        session.conf.set(key, "host")
    _assert_identical(out, session.run(plan), "device lax sum")


# -- staging unit surface -----------------------------------------------------

def test_zero_copy_counters_and_views(tmp_path):
    t = pa.table(
        {
            "a": np.arange(10_000, dtype=np.int64),
            "b": np.arange(10_000, dtype=np.float32),
            "c": pa.array([None if i % 9 == 0 else i for i in range(10_000)], type=pa.int32()),
        }
    )
    pq.write_table(t, tmp_path / "p.parquet")
    before_zc = stats.get("device.stage.bytes_zero_copy")
    before_cp = stats.get("device.stage.bytes_copied")
    ct = hio.read_parquet_cached([str(tmp_path / "p.parquet")])
    zc = stats.get("device.stage.bytes_zero_copy") - before_zc
    cp = stats.get("device.stage.bytes_copied") - before_cp
    # a (80k) + b (40k) are views; c (nullable) copies.
    assert zc == 10_000 * (8 + 4)
    assert cp >= 10_000 * 4
    assert not ct.columns["a"].flags.writeable
    np.testing.assert_array_equal(ct.columns["a"], np.arange(10_000))


def test_staging_disabled_copies_everything(tmp_path, tmp_system_path):
    t = pa.table({"a": np.arange(1000, dtype=np.int64)})
    pq.write_table(t, tmp_path / "p.parquet")
    session = HyperspaceSession(system_path=tmp_system_path)
    session.conf.set(DEVICE_STAGING_ENABLED, False)
    try:
        assert session.conf.get(DEVICE_STAGING_ENABLED) is False
        before = stats.get("device.stage.bytes_zero_copy")
        ct = hio.read_parquet_cached([str(tmp_path / "p.parquet")])
        assert stats.get("device.stage.bytes_zero_copy") == before
        assert stats.get("device.stage.bytes_copied") >= 8000
        np.testing.assert_array_equal(ct.columns["a"], np.arange(1000))
    finally:
        session.conf.set(DEVICE_STAGING_ENABLED, True)


def test_offset_view_slices_stage_correctly():
    base = pa.table(
        {
            "a": np.arange(1000, dtype=np.int64),
            "s": pa.array([f"v{i % 3}" for i in range(1000)]),
        }
    )
    sliced = base.slice(17, 400)  # offset view: non-zero arr.offset
    ct = ColumnTable.from_arrow(sliced, zero_copy_ok=True)
    np.testing.assert_array_equal(ct.columns["a"], np.arange(17, 417))
    got = ct.dictionaries["s"][ct.columns["s"]]
    np.testing.assert_array_equal(got.astype(str), np.array([f"v{i % 3}" for i in range(17, 417)]))


def test_uncached_read_is_downgraded_writable(tmp_path):
    """A table too large for the io cache must come back with OWNED
    writable arrays (read-only would masquerade as identity-stable)."""
    t = pa.table({"a": np.arange(50_000, dtype=np.int64)})
    pq.write_table(t, tmp_path / "p.parquet")
    old = hio._CACHE_BUDGET
    hio.set_table_cache_budget(1024)  # nothing fits
    try:
        ct = hio.read_parquet_cached([str(tmp_path / "p.parquet")])
        assert ct.columns["a"].flags.writeable
        np.testing.assert_array_equal(ct.columns["a"], np.arange(50_000))
    finally:
        hio.set_table_cache_budget(old)


def test_bool_and_multichunk_columns_take_copy_path():
    t1 = pa.table({"b": pa.array([True, False] * 50)})
    ct1 = ColumnTable.from_arrow(t1, zero_copy_ok=True)
    assert ct1.columns["b"].dtype == np.bool_
    np.testing.assert_array_equal(ct1.columns["b"], np.array([True, False] * 50))
    chunked = pa.table(
        {"a": pa.chunked_array([np.arange(5, dtype=np.int64), np.arange(5, 10, dtype=np.int64)])}
    )
    ct2 = ColumnTable.from_arrow(chunked, zero_copy_ok=True)
    np.testing.assert_array_equal(ct2.columns["a"], np.arange(10))


# -- staged-view immutability (the HSL025 runtime mirror) ---------------------
#
# The static rule (analysis/tracedomain.py HSL025) proves no code path
# mutates or donates a writeable=False staged view; these tests pin the
# runtime half of the same contract: the views really are read-only (a
# mutation attempt raises rather than corrupting the Arrow buffer), and
# own_arrays() is the one sanctioned way to writable arrays.

def test_mutating_zero_copy_staged_view_raises():
    t = pa.table({"a": np.arange(1000, dtype=np.int64)})
    ct = ColumnTable.from_arrow(t, zero_copy_ok=True)
    assert not ct.columns["a"].flags.writeable
    with pytest.raises(ValueError):
        ct.columns["a"][0] = -1
    # the Arrow buffer is untouched
    assert t.column("a")[0].as_py() == 0


def test_date32_and_timestamp_views_are_read_only():
    # These stage through Arrow's zero-copy .view() reinterpretation
    # (date32→int32 days, timestamp[us]→int64 micros) — the re-viewed
    # arrays must carry the same read-only contract as direct views.
    t = pa.table(
        {
            "d": pa.array([0, 1, 20000], type=pa.date32()),
            "ts": pa.array([0, 1_000_000, 2_000_000], type=pa.timestamp("us")),
        }
    )
    ct = ColumnTable.from_arrow(t, zero_copy_ok=True)
    assert ct.columns["d"].dtype == np.int32
    assert ct.columns["ts"].dtype == np.int64
    np.testing.assert_array_equal(ct.columns["d"], [0, 1, 20000])
    np.testing.assert_array_equal(ct.columns["ts"], [0, 1_000_000, 2_000_000])
    for name in ("d", "ts"):
        assert not ct.columns[name].flags.writeable, name
        with pytest.raises(ValueError):
            ct.columns[name][0] = 7


def test_every_zero_copy_column_is_read_only():
    """Whatever the staging layer kept as a view (counted in
    bytes_zero_copy) must be non-writeable — a writable view would let
    query code corrupt the shared Arrow buffer silently."""
    t = pa.table(
        {
            "i64": np.arange(500, dtype=np.int64),
            "f32": np.arange(500, dtype=np.float32),
            "i32": np.arange(500, dtype=np.int32),
            "d": pa.array(list(range(500)), type=pa.date32()),
            "ts": pa.array([i * 1000 for i in range(500)], type=pa.timestamp("us")),
            "nullable": pa.array(
                [None if i % 5 == 0 else i for i in range(500)], type=pa.int64()
            ),
        }
    )
    before = stats.get("device.stage.bytes_zero_copy")
    ct = ColumnTable.from_arrow(t, zero_copy_ok=True)
    staged = stats.get("device.stage.bytes_zero_copy") - before
    assert staged == 500 * (8 + 4 + 4 + 4 + 8)  # every eligible column viewed
    for name in ("i64", "f32", "i32", "d", "ts"):
        assert not ct.columns[name].flags.writeable, name
    # the nullable column took the copy path and stays writable
    assert ct.columns["nullable"].flags.writeable


def test_own_arrays_is_the_writable_gateway():
    t = pa.table({"a": np.arange(1000, dtype=np.int64)})
    ct = ColumnTable.from_arrow(t, zero_copy_ok=True)
    view = ct.columns["a"]
    assert not view.flags.writeable
    before_cp = stats.get("device.stage.bytes_copied")
    ct.own_arrays()
    # downgraded to an owned writable copy, accounted to the counters
    assert ct.columns["a"].flags.writeable
    assert ct.columns["a"] is not view
    assert stats.get("device.stage.bytes_copied") - before_cp == view.nbytes
    ct.columns["a"][0] = -1  # now legal
    assert ct.columns["a"][0] == -1
    # the original staged view and its Arrow buffer are untouched
    assert view[0] == 0 and t.column("a")[0].as_py() == 0


# -- dict-coded footprint accounting (RefCache satellite) --------------------

def test_dict_footprint_counts_codes_plus_dictionary():
    n = 50_000
    strings = [f"{'x' * 60}_{i % 4}" for i in range(n)]  # 4 long distinct values
    ct = ColumnTable.from_arrow(pa.table({"s": pa.array(strings)}))
    fp = dc.table_footprint_bytes(ct)
    codes_bytes = n * 4
    payload = sum(len(s) for s in set(strings)) + 8 * 4
    assert fp == codes_bytes + payload
    # NOT the inflated per-row string size (n * 62 chars).
    assert fp < n * 62 // 4


def test_refcache_admits_dict_column_under_true_footprint():
    """The over-count regression: a dict-coded side table whose TRUE
    footprint fits budget/4 must be admitted (the inflated per-row
    string size would have rejected it and evicted dict columns
    eagerly)."""
    n = 20_000
    ct = ColumnTable.from_arrow(
        pa.table({"s": pa.array([f"{'y' * 100}_{i % 3}" for i in range(n)])})
    )
    for a in (*ct.columns.values(), *ct.dictionaries.values()):
        dc.freeze(a)
    fp = dc.table_footprint_bytes(ct)
    inflated = n * 103
    budget = (fp + 1024) * 4  # true footprint fits; inflated would not
    assert inflated > budget // 4
    cache = dc.RefCache(budget, name="ref_cache")
    got = cache.get_or_build(("t", id(ct)), (ct,), lambda: (ct, fp))
    assert got is ct
    assert cache.stats()["entries"] == 1, "dict column must be admitted at its true footprint"


def test_result_cache_accounting_matches_canonical():
    from hyperspace_tpu.serve.result_cache import table_nbytes

    ct = ColumnTable.from_arrow(
        pa.table({"s": pa.array(["aa", "bb", "aa"]), "v": np.arange(3, dtype=np.int64)})
    )
    assert table_nbytes(ct) == dc.table_footprint_bytes(ct)


def test_to_arrow_keeps_strings_dictionary_coded():
    ct = ColumnTable.from_arrow(pa.table({"s": pa.array(["b", "a", "b", None])}))
    back = ct.to_arrow()
    assert pa.types.is_dictionary(back.column("s").type)
    assert back.column("s").to_pylist() == ["b", "a", "b", None]
    # Round trip: codes + dictionary survive without inflating.
    again = ColumnTable.from_arrow(back)
    assert list(again.dictionaries["s"]) == list(ct.dictionaries["s"])
    np.testing.assert_array_equal(again.columns["s"], ct.columns["s"])
