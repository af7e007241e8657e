"""Host-native join venue: the C++ bucket-parallel merge join must be
result-identical to the device kernel, and the venue must obey the
config: `device` by default, `host` when set; both venues share every
other stage."""

import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col, lit
from hyperspace_tpu.config import JOIN_VENUE, VENUE_KEYS
from hyperspace_tpu import native


@pytest.fixture
def joined(tmp_path):
    rng = np.random.default_rng(0)
    f = pd.DataFrame(
        {
            "k": rng.integers(0, 500, 20_000).astype(np.int64),
            "a": rng.normal(size=20_000),
        }
    )
    d = pd.DataFrame({"k": np.arange(400, dtype=np.int64), "b": rng.normal(size=400)})
    (tmp_path / "f").mkdir()
    (tmp_path / "d").mkdir()
    pq.write_table(pa.Table.from_pandas(f, preserve_index=False), tmp_path / "f" / "p.parquet")
    pq.write_table(pa.Table.from_pandas(d, preserve_index=False), tmp_path / "d" / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=8)
    hs = Hyperspace(session)
    fs, ds = session.parquet(tmp_path / "f"), session.parquet(tmp_path / "d")
    hs.create_index(fs, IndexConfig("fk", ["k"], ["a"]))
    hs.create_index(ds, IndexConfig("dk", ["k"], ["b"]))
    session.enable_hyperspace()
    return session, fs, ds, f, d


needs_native = pytest.mark.skipif(not native.available(), reason="native library not built")


@needs_native
def test_host_venue_matches_device_venue(joined):
    session, fs, ds, f, d = joined
    q = fs.join(ds, ["k"])
    session.conf.set(JOIN_VENUE, "device")
    r_dev = session.to_pandas(q).sort_values(["k", "a"]).reset_index(drop=True)
    assert session.last_query_stats["join_kernel"] == "device-searchsorted"
    session.conf.set(JOIN_VENUE, "host")
    r_host = session.to_pandas(q).sort_values(["k", "a"]).reset_index(drop=True)
    assert session.last_query_stats["join_kernel"] == "host-native-merge"
    assert session.last_query_stats["join_path"] == "zero-exchange-aligned"
    pd.testing.assert_frame_equal(r_dev, r_host)
    exp = f.merge(d, on="k").sort_values(["k", "a"]).reset_index(drop=True)
    np.testing.assert_allclose(r_host["a"], exp["a"])
    np.testing.assert_allclose(r_host["b"], exp["b"])


@needs_native
def test_host_venue_null_keys_do_not_join(tmp_path):
    t1 = pa.table(
        {
            "k": pa.array([1, None, 2, None, 3], type=pa.int64()),
            "a": np.arange(5, dtype=np.float64),
        }
    )
    t2 = pa.table(
        {
            "k": pa.array([1, 2, None], type=pa.int64()),
            "b": np.arange(3, dtype=np.float64),
        }
    )
    (tmp_path / "l").mkdir()
    (tmp_path / "r").mkdir()
    pq.write_table(t1, tmp_path / "l" / "p.parquet")
    pq.write_table(t2, tmp_path / "r" / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=2)
    session.conf.set(JOIN_VENUE, "host")
    ls, rs = session.parquet(tmp_path / "l"), session.parquet(tmp_path / "r")
    got = session.to_pandas(ls.join(rs, ["k"]))
    assert sorted(got["k"]) == [1, 2]  # SQL: NULL = NULL is not true


@needs_native
def test_host_venue_multi_key_and_strings(tmp_path):
    rng = np.random.default_rng(2)
    n = 3000
    f = pd.DataFrame(
        {
            "g": rng.choice(["x", "y", "z"], n),
            "k": rng.integers(0, 50, n).astype(np.int64),
            "a": rng.normal(size=n),
        }
    )
    d = pd.DataFrame(
        {
            "g": np.repeat(["x", "y", "z"], 50),
            "k": np.tile(np.arange(50, dtype=np.int64), 3),
            "b": rng.normal(size=150),
        }
    )
    (tmp_path / "f").mkdir()
    (tmp_path / "d").mkdir()
    pq.write_table(pa.Table.from_pandas(f, preserve_index=False), tmp_path / "f" / "p.parquet")
    pq.write_table(pa.Table.from_pandas(d, preserve_index=False), tmp_path / "d" / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=4)
    session.conf.set(JOIN_VENUE, "host")
    fs, ds = session.parquet(tmp_path / "f"), session.parquet(tmp_path / "d")
    got = (
        session.to_pandas(fs.join(ds, ["g", "k"]))
        .sort_values(["g", "k", "a"])
        .reset_index(drop=True)
    )
    exp = (
        f.merge(d, on=["g", "k"])
        .sort_values(["g", "k", "a"])
        .reset_index(drop=True)
    )
    assert len(got) == len(exp)
    np.testing.assert_allclose(got["a"], exp["a"])
    np.testing.assert_allclose(got["b"], exp["b"])


@needs_native
def test_native_merge_join_kernel_direct():
    """Kernel-level: matches numpy reference on adversarial runs
    (duplicates straddling bucket edges, empty buckets, all-equal runs)."""
    rng = np.random.default_rng(7)
    for trial in range(5):
        nb = 6
        lparts = [np.sort(rng.integers(0, 12, rng.integers(0, 40))).astype(np.int32) for _ in range(nb)]
        rparts = [np.sort(rng.integers(0, 12, rng.integers(0, 40))).astype(np.int32) for _ in range(nb)]
        lk = np.concatenate(lparts) if lparts else np.zeros(0, np.int32)
        rk = np.concatenate(rparts) if rparts else np.zeros(0, np.int32)
        lofs = np.concatenate([[0], np.cumsum([len(p) for p in lparts])]).astype(np.int64)
        rofs = np.concatenate([[0], np.cumsum([len(p) for p in rparts])]).astype(np.int64)
        li, ri, totals = native.merge_join_sorted(lk, lofs, rk, rofs)
        # Reference: per-bucket nested equality.
        exp_pairs = []
        for b in range(nb):
            for i in range(lofs[b], lofs[b + 1]):
                for j in range(rofs[b], rofs[b + 1]):
                    if lk[i] == rk[j]:
                        exp_pairs.append((i, j))
        got_pairs = sorted(zip(li.tolist(), ri.tolist()))
        assert got_pairs == sorted(exp_pairs), f"trial {trial}"
        assert int(totals.sum()) == len(exp_pairs)


@pytest.mark.parametrize(
    "key, value",
    [(key, "auto") for key in VENUE_KEYS] + [(JOIN_VENUE, "hsot")],
)
def test_unknown_venue_raises(key, value):
    """Every venue key takes device|host only, and says which key was
    wrong at set(); a rejected value leaves the default in force."""
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.exceptions import HyperspaceError

    conf = HyperspaceConf(system_path="unused")
    with pytest.raises(HyperspaceError, match=re.escape(key)):
        conf.set(key, value)
    assert conf.get(key) == "device"


@needs_native
def test_forced_host_venue_wins_over_mesh(joined):
    from hyperspace_tpu.parallel.mesh import make_mesh

    session, fs, ds, f, d = joined
    session.mesh = make_mesh()
    session.conf.set(JOIN_VENUE, "host")
    got = session.to_pandas(fs.join(ds, ["k"]))
    assert session.last_query_stats["join_kernel"] == "host-native-merge"
    assert len(got) == len(f.merge(d, on="k"))


@needs_native
def test_build_venue_host_produces_identical_index(tmp_path):
    """Host and device build venues must write byte-identical bucket
    files and manifests (null/string/float32/int64 keys covered)."""
    import json

    from hyperspace_tpu.config import BUILD_VENUE

    rng = np.random.default_rng(0)
    n = 20_000
    df = pd.DataFrame(
        {
            "k": rng.integers(0, 5_000, n).astype(np.int64),
            "s": rng.choice(["aa", "bb", None, "cc"], n),
            "v": rng.normal(size=n).astype(np.float32),
            "d": rng.normal(size=n),
        }
    )
    (tmp_path / "src").mkdir()
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), tmp_path / "src" / "p.parquet")

    dirs = {}
    for venue in ("device", "host"):
        session = HyperspaceSession(system_path=str(tmp_path / f"idx_{venue}"), num_buckets=8)
        session.conf.set(BUILD_VENUE, venue)
        hs = Hyperspace(session)
        scan = session.parquet(tmp_path / "src")
        hs.create_index(scan, IndexConfig("ix", ["k", "s"], ["v", "d"]))
        dirs[venue] = tmp_path / f"idx_{venue}" / "ix" / "v__=0"
    for b in range(8):
        f = f"bucket-{b:05d}.parquet"
        pd.testing.assert_frame_equal(
            pq.read_table(dirs["device"] / f).to_pandas(),
            pq.read_table(dirs["host"] / f).to_pandas(),
        )
    m1 = json.loads((dirs["device"] / "_index_manifest.json").read_text())
    m2 = json.loads((dirs["host"] / "_index_manifest.json").read_text())
    assert m1 == m2


@pytest.mark.parametrize("venue", ["device", "host"])
def test_filtered_sides_keep_zero_exchange_join(joined, venue):
    """JoinIndexRule keeps linear sides with filters; the executor must
    apply side-local predicates per bucket and STILL take the
    bucket-aligned zero-exchange path (round-1 weak #7: such shapes
    silently fell back to the single-partition join)."""
    if venue == "host" and not native.available():
        pytest.skip("native library not built")
    session, fs, ds, f, d = joined
    session.conf.set(JOIN_VENUE, venue)
    q = fs.filter(col("a") > lit(0.0)).join(ds.filter(col("b") < lit(0.5)), ["k"])
    got = session.to_pandas(q).sort_values(["k", "a"]).reset_index(drop=True)
    assert session.last_query_stats["join_path"] == "zero-exchange-aligned"
    exp = (
        f[f.a > 0.0]
        .merge(d[d.b < 0.5], on="k")
        .sort_values(["k", "a"])
        .reset_index(drop=True)
    )
    assert len(got) == len(exp)
    np.testing.assert_allclose(got["a"], exp["a"])
    np.testing.assert_allclose(got["b"], exp["b"])
