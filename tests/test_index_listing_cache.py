"""The rewrite's cache of index version-directory listings
(``execution/io.list_version_dir``).

A committed version directory never changes, so ``index_scan_for`` and
the plan-time prefetch take its files from a cache checked by one stat
of the directory. These tests count the filesystem calls and hold every
listing the cache returns to a fresh ``list_data_files``: after direct
edits of a directory, after every action that writes index data, inside
the racy window, for layouts it must not cache, and under concurrent
rewrites.
"""

import os
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from pathlib import Path

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col, stats
from hyperspace_tpu.dataset import list_data_files
from hyperspace_tpu.exceptions import IndexCorruptionError
from hyperspace_tpu.execution import io as hio
from hyperspace_tpu.execution import prefetch
from hyperspace_tpu.obs import metrics
from hyperspace_tpu.rules.base import index_scan_for
from hyperspace_tpu.serve.plan_cache import PlanCache

NAME = "lc"


@pytest.fixture
def built(tmp_path, sample_parquet):
    session = HyperspaceSession(system_path=str(tmp_path / "indexes"), num_buckets=4)
    hs = Hyperspace(session)
    df = session.parquet(sample_parquet)
    hs.create_index(df, IndexConfig(NAME, ["key"], ["value"]))
    session.enable_hyperspace()
    return session, hs, df


def _entry(session):
    (entry,) = [e for e in session.manager.get_indexes() if e.name == NAME]
    return entry


def _vdirs(session) -> list[Path]:
    entry = _entry(session)
    return [Path(entry.content.root) / d for d in entry.content.directories]


def _age(*dirs):
    """Move the directories' mtimes out of the racy window, as the time
    between an index's commit and its queries does."""
    old = time.time_ns() - 10_000_000_000
    for d in dirs:
        os.utime(d, ns=(old, old))


def _index_files(plan) -> list[str]:
    (scan,) = [s for s in plan.leaves() if s.bucket_spec is not None]
    return list(scan.files)


def _fresh(dirs) -> list[str]:
    return sorted(fi.path for d in dirs for fi in list_data_files(d))


def _lookup(df, key=5):
    return df.filter(col("key") == key).select("key", "value")


def _frames_equal(a: pd.DataFrame, b: pd.DataFrame):
    cols = sorted(a.columns)
    pd.testing.assert_frame_equal(
        a[cols].sort_values(cols).reset_index(drop=True),
        b[cols].sort_values(cols).reset_index(drop=True),
        check_dtype=False,
    )


def _answers_from_source(session, q):
    got = session.to_pandas(q)
    session.disable_hyperspace()
    try:
        _frames_equal(got, session.to_pandas(q))
    finally:
        session.enable_hyperspace()


def _counter(name: str) -> int:
    return metrics.REGISTRY.get(name).value


@contextmanager
def _counting(monkeypatch):
    """Paths passed to os.stat and os.scandir by this thread (pathlib's
    stat goes through os.stat)."""
    calls = {"stat": [], "scandir": []}
    me = threading.get_ident()
    real_stat, real_scandir = os.stat, os.scandir

    def stat(p, *a, **kw):
        if threading.get_ident() == me:
            calls["stat"].append(os.fspath(p))
        return real_stat(p, *a, **kw)

    def scandir(p=".", *a, **kw):
        if threading.get_ident() == me:
            calls["scandir"].append(os.fspath(p))
        return real_scandir(p, *a, **kw)

    monkeypatch.setattr(os, "stat", stat)
    monkeypatch.setattr(os, "scandir", scandir)
    try:
        yield calls
    finally:
        monkeypatch.setattr(os, "stat", real_stat)
        monkeypatch.setattr(os, "scandir", real_scandir)


def _bucket_stats(calls, root) -> list[str]:
    return [p for p in calls["stat"] if p.startswith(str(root)) and p.endswith(".parquet")]


def test_second_rewrite_stats_no_bucket_file(built, monkeypatch):
    session, hs, df = built
    (d,) = _vdirs(session)
    _age(d)
    session.optimized_plan(_lookup(df))
    with _counting(monkeypatch) as calls:
        plan = session.optimized_plan(_lookup(df))
    assert _index_files(plan) == _fresh([d])
    assert _bucket_stats(calls, d) == []
    assert not any(p.startswith(str(d)) for p in calls["scandir"])
    assert str(d) in calls["stat"]  # the one directory stat


MUTATIONS = {
    "add": lambda d: shutil.copy(d / "bucket-00000.parquet", d / "bucket-00009.parquet"),
    "remove": lambda d: os.remove(d / "bucket-00001.parquet"),
    "rename": lambda d: os.rename(d / "bucket-00002.parquet", d / "bucket-00007.parquet"),
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_edit_of_a_cached_directory_is_seen_on_the_next_rewrite(built, mutation):
    session, hs, df = built
    (d,) = _vdirs(session)
    _age(d)
    before = _index_files(session.optimized_plan(_lookup(df)))
    assert hio.list_version_dir(d)[1]  # cached
    MUTATIONS[mutation](d)
    after = _index_files(session.optimized_plan(_lookup(df)))
    assert after != before
    assert after == _fresh([d])
    assert hio.list_version_dir(d)[0] == list_data_files(d)


def _append_source(root, fname):
    rng = np.random.default_rng(len(fname))
    n = 300
    pq.write_table(
        pa.table({
            "id": pa.array(np.arange(10_000, 10_000 + n, dtype=np.int64)),
            "key": pa.array(rng.integers(0, 100, size=n, dtype=np.int64)),
            "value": pa.array(rng.standard_normal(n)),
            "name": pa.array([f"name_{i % 37}" for i in range(n)]),
        }),
        Path(root) / fname,
    )


def _recreate(hs, session, root):
    hs.delete_index(NAME)
    hs.vacuum_index(NAME)
    hs.create_index(session.parquet(root), IndexConfig(NAME, ["key"], ["value"]))


# action -> (whether source files are appended first, the action)
ACTIONS = {
    "refresh_full": (True, lambda hs, session, root: hs.refresh_index(NAME, mode="full")),
    "refresh_incremental": (True, lambda hs, session, root: hs.refresh_index(NAME, mode="incremental")),
    "optimize": (False, lambda hs, session, root: hs.optimize_index(NAME)),
    "delete_vacuum_recreate": (True, _recreate),
}


@pytest.mark.parametrize("action", sorted(ACTIONS))
def test_actions_give_the_new_file_list(built, sample_parquet, action):
    session, hs, df = built
    q = _lookup(df)
    old_dirs = _vdirs(session)
    _age(*old_dirs)
    session.optimized_plan(q)
    assert all(hio.list_version_dir(d)[1] for d in old_dirs)
    append, act = ACTIONS[action]
    if append:
        _append_source(sample_parquet, "part-appended.parquet")
    act(hs, session, sample_parquet)
    new_dirs = _vdirs(session)
    if action == "delete_vacuum_recreate":
        assert new_dirs == old_dirs  # the same path, written anew
    else:
        assert new_dirs != old_dirs
    assert _index_files(session.optimized_plan(q)) == _fresh(new_dirs)
    _answers_from_source(session, q)
    # Once out of the racy window the new listing is cached, and stays exact.
    _age(*new_dirs)
    session.optimized_plan(q)
    hits = _counter("plan.index_files.cache_hits")
    assert _index_files(session.optimized_plan(q)) == _fresh(new_dirs)
    assert _counter("plan.index_files.cache_hits") == hits + len(new_dirs)


def test_directory_modified_within_the_racy_window_is_relisted(built, monkeypatch):
    session, hs, df = built
    (d,) = _vdirs(session)
    # Modified a fifth of a second before the listing, or stamped ahead
    # of the clock: each listing reads the directory again.
    for offset_ns in (-200_000_000, 60_000_000_000):
        stamp = time.time_ns() + offset_ns
        os.utime(d, ns=(stamp, stamp))
        with _counting(monkeypatch) as calls:
            assert [hio.list_version_dir(d)[1] for _ in range(2)] == [False, False]
        assert calls["scandir"] == [str(d), str(d)]
    _age(d)
    assert [hio.list_version_dir(d)[1] for _ in range(2)] == [False, True]
    assert hio.list_version_dir(d)[0] == list_data_files(d)


def _subdirectory(d: Path) -> Path:
    (d / "nested").mkdir()
    shutil.copy(d / "bucket-00000.parquet", d / "nested" / "bucket-00000.parquet")
    return d


def _missing(d: Path) -> Path:
    return d.parent / "v__=99"


def _a_file(d: Path) -> Path:
    return d / "bucket-00000.parquet"


@pytest.mark.parametrize("layout", [_subdirectory, _missing, _a_file], ids=lambda f: f.__name__[1:])
def test_other_layouts_are_listed_as_today_and_never_cached(built, layout):
    session, hs, df = built
    (d,) = _vdirs(session)
    target = layout(d)
    _age(d)
    for _ in range(3):
        listing, hit = hio.list_version_dir(target)
        assert not hit
        assert listing == list_data_files(target)


def test_garbage_manifest_still_raises_and_the_query_falls_back(built):
    session, hs, df = built
    (d,) = _vdirs(session)
    _age(d)
    q = _lookup(df)
    session.optimized_plan(q)  # listing and manifest cached
    (d / hio.MANIFEST_NAME).write_text('{"numBuckets": 4, "bucketRo')
    assert hio.list_version_dir(d)[1]  # the listing still hits
    with pytest.raises(IndexCorruptionError):
        index_scan_for(_entry(session))
    plan = session.optimized_plan(q)
    assert all(s.bucket_spec is None for s in plan.leaves())
    _answers_from_source(session, q)


@pytest.mark.parametrize("fallback", [True, False], ids=["fallback", "no_fallback"])
def test_bucket_deleted_after_caching_surfaces_through_the_read_path(built, fallback):
    session, hs, df = built
    (d,) = _vdirs(session)
    _age(d)
    q = _lookup(df)
    cache = PlanCache()
    session.run_query(q, plan_cache=cache)  # plan and listing cached
    for f in d.glob("bucket-*.parquet"):
        f.unlink()
    hio.clear_table_cache()
    session.conf.set("hyperspace.fallback.enabled", fallback)
    if not fallback:
        with pytest.raises(IndexCorruptionError):
            session.run_query(q, plan_cache=cache)
        return
    before = stats.get("fallback.queries")
    out = session.run_query(q, plan_cache=cache)
    assert stats.get("fallback.queries") == before + 1
    session.disable_hyperspace()
    _frames_equal(pd.DataFrame(out.result.decode()), session.to_pandas(q))


@pytest.mark.parametrize("aged", [True, False], ids=["validated", "racy"])
def test_prefetch_stats_no_bucket_file_on_a_hit(built, monkeypatch, aged):
    session, hs, df = built
    (d,) = _vdirs(session)
    if aged:
        _age(d)
    else:
        ahead = time.time_ns() + 60_000_000_000
        os.utime(d, ns=(ahead, ahead))
    q = df.filter(col("key") >= 0).select("key", "value")  # keeps every bucket
    session.optimized_plan(q)
    plan = session.optimized_plan(q)
    prefetch.reset()
    with _counting(monkeypatch) as calls:
        submitted = prefetch.prefetch_plan(plan)
    prefetch.drain()
    assert submitted == len(_fresh([d])) == 4
    assert len(_bucket_stats(calls, d)) == (0 if aged else 4)
    assert prefetch.prefetch_plan(plan) == 0  # unchanged files are not issued again


def test_counters_and_span_attribute_engage(built):
    session, hs, df = built
    (d,) = _vdirs(session)
    _age(d)

    def cached_attr():
        stack = [session.last_profile().trace]
        while stack:
            node = stack.pop()
            if node["name"] == "plan.index_files":
                return node["attrs"]["cached"]
            stack.extend(node.get("children", ()))

    session.run(_lookup(df))
    assert cached_attr() is False
    assert (_counter("plan.index_files.cache_hits"), _counter("plan.index_files.cache_misses")) == (0, 1)
    session.run(_lookup(df, 7))
    assert cached_attr() is True
    assert (_counter("plan.index_files.cache_hits"), _counter("plan.index_files.cache_misses")) == (1, 1)


def test_concurrent_rewrites_agree_with_a_fresh_listing(built):
    session, hs, df = built
    (d,) = _vdirs(session)
    entry = _entry(session)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for rnd in range(3):
            shutil.copy(d / "bucket-00000.parquet", d / f"bucket-0001{rnd}.parquet")
            _age(d)
            expected = _fresh([d])
            with ThreadPoolExecutor(max_workers=8) as ex:
                got = list(ex.map(lambda _: index_scan_for(entry).files, range(64), timeout=120))
            assert all(files == expected for files in got)
            assert hio.list_version_dir(d) == (list_data_files(d), True)
    finally:
        sys.setswitchinterval(interval)


def test_another_spelling_of_a_cached_directory_lists_in_its_own_form(built, monkeypatch):
    session, hs, df = built
    (d,) = _vdirs(session)
    _age(d)
    hio.list_version_dir(d)
    assert hio.list_version_dir(d)[1]
    monkeypatch.chdir(d.parent)
    listing, hit = hio.list_version_dir(Path(d.name))
    assert not hit
    assert listing == list_data_files(Path(d.name))
