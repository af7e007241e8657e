"""Range (min/max) pruning over sorted index buckets.

The analog of FileSourceScanExec's parquet min/max pruning, which the
reference inherits from Spark (SURVEY.md §2.2): the index manifest
persists per-bucket key stats, range predicates skip non-overlapping
bucket files, and surviving files are searchsorted-sliced on the sorted
key instead of full-scan masked.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col, lit
from hyperspace_tpu.execution import io as hio

NB = 8


@pytest.fixture
def indexed(tmp_path):
    """Parquet source + covering index on an int64 key, returning
    (session, scan, source pandas)."""
    rng = np.random.default_rng(11)
    n = 50_000
    df = pd.DataFrame(
        {
            "k": rng.integers(0, 100_000, n).astype(np.int64),
            "v": rng.normal(size=n),
            "tag": rng.choice(["x", "y", "z"], n),
        }
    )
    root = tmp_path / "src"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), root / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=NB)
    hs = Hyperspace(session)
    scan = session.parquet(root)
    hs.create_index(scan, IndexConfig("r_k", ["k"], ["v", "tag"]))
    session.enable_hyperspace()
    return session, scan, df


def test_manifest_has_key_stats(indexed, tmp_path):
    vdir = tmp_path / "idx" / "r_k" / "v__=0"
    m = hio.read_manifest(vdir)
    assert m is not None and "keyStats" in m
    ks = m["keyStats"]
    assert len(ks) == NB
    # Stats must bound the actual file contents.
    for b, s in enumerate(ks):
        t = pq.read_table(vdir / hio.bucket_file_name(b)).to_pandas()
        if len(t) == 0:
            assert s is None
        else:
            assert s[0] == t["k"].min() and s[1] == t["k"].max()


def test_between_query_prunes_and_matches(indexed):
    session, scan, df = indexed
    lo, hi = 40_000, 40_500
    q = scan.filter((col("k") >= lit(lo)) & (col("k") <= lit(hi)))
    got = (
        session.to_pandas(q)
        .sort_values(["k", "v"])
        .reset_index(drop=True)
    )
    exp = (
        df[(df.k >= lo) & (df.k <= hi)]
        .sort_values(["k", "v"])
        .reset_index(drop=True)
    )
    np.testing.assert_array_equal(got["k"], exp["k"])
    np.testing.assert_allclose(got["v"], exp["v"])
    assert list(got["tag"]) == list(exp["tag"])
    # The narrow range must not read every row: slicing kicked in.
    assert session.last_query_stats["rows_pruned"] > 0


def test_open_range_prunes_files(indexed):
    session, scan, df = indexed
    # Keys are hash-bucketed, so every bucket spans ~the full key range;
    # a threshold beyond every file's max prunes ALL files.
    q = scan.filter(col("k") > lit(100_000))
    got = session.to_pandas(q)
    assert len(got) == 0
    stats = session.last_query_stats
    assert stats["files_pruned"] == NB
    assert stats["files_read"] == 0


def test_strict_vs_inclusive_bounds(indexed):
    session, scan, df = indexed
    kmax = int(df.k.max())
    inc = session.to_pandas(scan.filter(col("k") >= lit(kmax)))
    strict = session.to_pandas(scan.filter(col("k") > lit(kmax)))
    assert len(inc) == int((df.k == kmax).sum())
    assert len(strict) == 0


def test_range_with_null_keys_falls_back_correctly(tmp_path):
    t = pa.table(
        {
            "k": pa.array([1, 5, None, 9, None, 3], type=pa.int64()),
            "v": np.arange(6, dtype=np.float64),
        }
    )
    root = tmp_path / "nsrc"
    root.mkdir()
    pq.write_table(t, root / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=2)
    hs = Hyperspace(session)
    scan = session.parquet(root)
    hs.create_index(scan, IndexConfig("n_k", ["k"], ["v"]))
    session.enable_hyperspace()
    got = session.to_pandas(scan.filter(col("k") >= lit(4)))
    assert sorted(got["k"]) == [5, 9]  # nulls fail the comparison


def test_string_key_file_level_pruning(tmp_path):
    df = pd.DataFrame(
        {
            "s": [f"key{i:04d}" for i in range(2_000)],
            "v": np.arange(2_000, dtype=np.float64),
        }
    )
    root = tmp_path / "ssrc"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), root / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=4)
    hs = Hyperspace(session)
    scan = session.parquet(root)
    hs.create_index(scan, IndexConfig("s_k", ["s"], ["v"]))
    session.enable_hyperspace()
    got = session.to_pandas(scan.filter(col("s") < lit("key0010")))
    exp = df[df.s < "key0010"]
    assert sorted(got["s"]) == sorted(exp["s"])
    # Beyond-max range prunes every file via string stats.
    empty = session.to_pandas(scan.filter(col("s") > lit("zzz")))
    assert len(empty) == 0 and session.last_query_stats["files_read"] == 0


def test_range_pruning_survives_incremental_refresh(tmp_path):
    rng = np.random.default_rng(3)
    root = tmp_path / "isrc"
    root.mkdir()
    d1 = pd.DataFrame({"k": rng.integers(0, 1000, 3000).astype(np.int64), "v": rng.normal(size=3000)})
    pq.write_table(pa.Table.from_pandas(d1, preserve_index=False), root / "a.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=4)
    hs = Hyperspace(session)
    scan = session.parquet(root)
    hs.create_index(scan, IndexConfig("i_k", ["k"], ["v"]))
    d2 = pd.DataFrame({"k": rng.integers(0, 1000, 1000).astype(np.int64), "v": rng.normal(size=1000)})
    pq.write_table(pa.Table.from_pandas(d2, preserve_index=False), root / "b.parquet")
    hs.refresh_index("i_k", mode="incremental")
    session.enable_hyperspace()
    both = pd.concat([d1, d2], ignore_index=True)
    lo, hi = 200, 260
    got = session.to_pandas(scan.filter((col("k") >= lit(lo)) & (col("k") < lit(hi))))
    exp = both[(both.k >= lo) & (both.k < hi)]
    assert sorted(got["k"]) == sorted(exp["k"])
    np.testing.assert_allclose(sorted(got["v"]), sorted(exp["v"]))
    assert session.last_query_stats["rows_pruned"] > 0


def test_float32_key_weak_literal_not_overpruned(tmp_path):
    """Pruning must compare in the filter's own domain: a python-float
    literal against a float32 key compares IN float32 (NEP 50), so the
    literal rounds. Comparing raw float64 instead would prune files/rows
    the mask keeps."""
    v = np.float32(0.1)  # 0.10000000149... as float64
    df = pd.DataFrame(
        {
            "k": np.full(300, v, dtype=np.float32),
            "p": np.arange(300, dtype=np.float64),
        }
    )
    root = tmp_path / "f32"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), root / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=2)
    hs = Hyperspace(session)
    scan = session.parquet(root)
    hs.create_index(scan, IndexConfig("f_k", ["k"], ["p"]))

    q = scan.filter(col("k") <= lit(0.1))
    session.disable_hyperspace()
    raw = session.to_pandas(q)
    session.enable_hyperspace()
    idx = session.to_pandas(q)
    assert len(raw) == 300  # float32(0.1) <= float32(0.1)
    assert len(idx) == len(raw)


def test_range_pruning_in_hybrid_scan(tmp_path):
    """After an append WITHOUT refresh, the rewritten plan is a hybrid
    Union(index, delta); range pruning must still skip index files."""
    rng = np.random.default_rng(9)
    root = tmp_path / "hsrc"
    root.mkdir()
    d1 = pd.DataFrame({"k": rng.integers(0, 1000, 4000).astype(np.int64), "v": rng.normal(size=4000)})
    pq.write_table(pa.Table.from_pandas(d1, preserve_index=False), root / "a.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=4)
    hs = Hyperspace(session)
    scan = session.parquet(root)
    hs.create_index(scan, IndexConfig("h_k", ["k"], ["v"]))
    d2 = pd.DataFrame({"k": rng.integers(0, 1000, 500).astype(np.int64), "v": rng.normal(size=500)})
    pq.write_table(pa.Table.from_pandas(d2, preserve_index=False), root / "b.parquet")
    from hyperspace_tpu.config import INDEX_HYBRID_SCAN_ENABLED, INDEX_HYBRID_SCAN_MAX_APPENDED_RATIO

    session.conf.set(INDEX_HYBRID_SCAN_ENABLED, True)
    session.conf.set(INDEX_HYBRID_SCAN_MAX_APPENDED_RATIO, 10.0)
    session.enable_hyperspace()
    both = pd.concat([d1, d2], ignore_index=True)
    # Above every key: index files all pruned; delta still scanned.
    got = session.to_pandas(scan.filter(col("k") > lit(10_000)))
    assert len(got) == 0
    assert session.last_query_stats["files_pruned"] == 4
    lo, hi = 100, 150
    got2 = session.to_pandas(scan.filter((col("k") >= lit(lo)) & (col("k") < lit(hi))))
    exp2 = both[(both.k >= lo) & (both.k < hi)]
    assert sorted(got2["k"]) == sorted(exp2["k"])
    np.testing.assert_allclose(sorted(got2["v"]), sorted(exp2["v"]))


def test_exact_slice_skips_residual_mask(indexed):
    """A predicate made ONLY of key bounds is fully implemented by the
    slice — the physical plan records the skipped mask and results stay
    identical to the raw scan."""
    session, scan, df = indexed
    lo, hi = 30_000, 31_000
    q = scan.filter((col("k") >= lit(lo)) & (col("k") < lit(hi)))
    got = session.to_pandas(q)
    phys = session.last_physical_plan
    node = next(n for n in phys.walk() if n.op == "IndexRangeScan")
    assert "mask skipped" in node.detail["kernel"]
    exp = df[(df.k >= lo) & (df.k < hi)]
    assert len(got) == len(exp)
    np.testing.assert_allclose(sorted(got["v"]), sorted(exp["v"]))

    # A residual conjunct on another column keeps the mask.
    q2 = scan.filter((col("k") >= lit(lo)) & (col("k") < lit(hi)) & (col("v") > lit(0.0)))
    got2 = session.to_pandas(q2)
    node2 = next(n for n in session.last_physical_plan.walk() if n.op == "IndexRangeScan")
    assert "-mask" in node2.detail["kernel"]  # mask ran (either venue)
    exp2 = exp[exp.v > 0.0]
    assert len(got2) == len(exp2)


def test_nan_bound_returns_no_rows(indexed):
    """NaN comparisons are False for every row; the range path must not
    treat NaN as an orderable bound (searchsorted sorts NaN last, which
    would return EVERY row as an 'exact' slice)."""
    session, scan, df = indexed
    q = scan.filter(col("k") <= lit(float("nan")))
    session.disable_hyperspace()
    assert len(session.to_pandas(q)) == 0
    session.enable_hyperspace()
    assert len(session.to_pandas(q)) == 0


def test_float_key_with_nan_values_not_overincluded(tmp_path):
    """A float key column holding NaN VALUES: a lower-bound-only slice
    includes the trailing NaN run, so the mask must still run (exactness
    is never claimed for float keys)."""
    df = pd.DataFrame(
        {
            "k": np.array([1.0, 2.0, 3.0, np.nan, np.nan], dtype=np.float64),
            "v": np.arange(5, dtype=np.float64),
        }
    )
    root = tmp_path / "nan_src"
    root.mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), root / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=1)
    hs = Hyperspace(session)
    scan = session.parquet(root)
    hs.create_index(scan, IndexConfig("nk", ["k"], ["v"]))
    session.enable_hyperspace()
    got = session.to_pandas(scan.filter(col("k") >= lit(2.0)))
    assert sorted(got["k"]) == [2.0, 3.0]  # NaN rows dropped by the mask


@pytest.mark.parametrize(
    "lo,lo_strict,hi,hi_strict",
    [
        (100, False, 150, True),
        (100, True, 150, False),
        (None, False, 120, False),
        (120, True, None, False),
        (-(2**40), False, 2**40, False),  # both beyond the key's int32 range
        (2**40, False, None, False),  # above every key: empty runs
        (None, False, -(2**40), True),  # below every key: empty runs
    ],
)
def test_device_slice_bounds_match_searchsorted(lo, lo_strict, hi, hi_strict):
    """The device venue's slice bounds equal the host's np.searchsorted
    runs for every file, pads and out-of-range literals included."""
    from hyperspace_tpu.execution.exec_common import KeyBounds
    from hyperspace_tpu.ops.sortkeys import device_slice_bounds

    rng = np.random.default_rng(5)
    keys = [np.sort(rng.integers(50, 200, n)).astype(np.int32) for n in (1, 37, 200, 64)]
    runs = device_slice_bounds(keys, KeyBounds(lo, lo_strict, hi, hi_strict))
    for k, (st, en) in zip(keys, runs):
        want_st = 0 if lo is None else np.searchsorted(k, lo, side="right" if lo_strict else "left")
        want_en = len(k) if hi is None else np.searchsorted(k, hi, side="left" if hi_strict else "right")
        if want_en <= want_st:  # no row in range: any empty run will do
            assert en <= st
        else:
            assert (st, en) == (want_st, want_en)


def test_exact_slice_on_the_device_venue_finds_its_runs_on_the_device(indexed):
    session, scan, df = indexed
    session.conf.set("hyperspace.filter.venue", "device")
    lo, hi = 30_000, 31_000
    got = session.to_pandas(scan.filter((col("k") >= lit(lo)) & (col("k") < lit(hi))))
    node = next(n for n in session.last_physical_plan.walk() if n.op == "IndexRangeScan")
    assert node.detail["kernel"] == (
        "minmax-prune + device-searchsorted-slice (exact, mask skipped)"
    )
    exp = df[(df.k >= lo) & (df.k < hi)]
    assert sorted(got["k"]) == sorted(exp["k"])
