"""The tracer's profiler bridge and the spans that split planning and
the host's waits on the device (docs/observability.md "Tracer").

A recorded span enters a ``jax.profiler.TraceAnnotation`` of its bare
name while a profiler session records, so the span tree lands in the
``.xplane.pb`` on the device's clock; with no session recording, none
is built. The rewrite is split into ``plan.index_files``,
``plan.fingerprint`` and ``plan.prefetch``; every blocking device→host
fetch is a ``device.sync`` span.
"""

from pathlib import Path

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import AggSpec, Hyperspace, HyperspaceSession, IndexConfig, col, stats
from hyperspace_tpu.config import (
    AGG_VENUE,
    DEVICE_FUSED_KERNELS,
    FILTER_VENUE,
    JOIN_VENUE,
    SORT_VENUE,
)
from hyperspace_tpu.obs import trace

PLAN_SPANS = ("plan.index_files", "plan.fingerprint", "plan.prefetch")


@pytest.fixture
def tpch(tmp_path):
    """A small orders/lineitem pair with covering indexes on the order
    key, as the benchmark's lookup and join-aggregate cells use them."""
    rng = np.random.default_rng(5)
    n_orders = 300
    lines = rng.integers(1, 8, n_orders)
    orders = pa.table({
        "o_orderkey": np.arange(n_orders, dtype=np.int64),
        "o_custkey": rng.integers(0, 50, n_orders).astype(np.int64),
    })
    lineitem = pa.table({
        "l_orderkey": np.repeat(np.arange(n_orders, dtype=np.int64), lines),
        "l_partkey": rng.integers(0, 1000, int(lines.sum())).astype(np.int64),
        "l_quantity": rng.integers(1, 6, int(lines.sum())).astype(np.int64),
    })
    for name, t in (("orders", orders), ("lineitem", lineitem)):
        (tmp_path / name).mkdir()
        pq.write_table(t, tmp_path / name / "p.parquet")
    session = HyperspaceSession(system_path=str(tmp_path / "idx"), num_buckets=8)
    hs = Hyperspace(session)
    o = session.parquet(tmp_path / "orders")
    li = session.parquet(tmp_path / "lineitem")
    hs.create_index(li, IndexConfig("li_orderkey", ["l_orderkey"], ["l_partkey", "l_quantity"]))
    hs.create_index(o, IndexConfig("o_orderkey", ["o_orderkey"], ["o_custkey"]))
    session.enable_hyperspace()
    return session, o, li


def _lookup(li, key: int = 17):
    return li.filter(col("l_orderkey") == key).select("l_orderkey", "l_partkey")


def _names(span_json):
    stack, out = [span_json], []
    while stack:
        node = stack.pop()
        out.append(node["name"])
        stack.extend(node.get("children", ()))
    return out


def _host_events(log_dir: Path, names) -> dict:
    """Intervals of the host-plane events called one of `names`, by name."""
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    out: dict = {n: [] for n in names}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in out:
                        out[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_lookup_spans_nest_on_the_profiler_clock(tpch, tmp_path):
    session, o, li = tpch
    session.run(_lookup(li))  # compiles outside the profile
    chain = ("query", "plan.optimize", "rule.FilterIndexRule", "plan.index_files")
    with jax.profiler.trace(str(tmp_path / "prof")):
        session.run(_lookup(li, 23))
    ev = _host_events(tmp_path / "prof", chain)
    assert all(ev[n] for n in chain), ev
    for inner_name, outer_name in zip(chain[1:], chain[:-1]):
        for s, e in ev[inner_name]:
            assert any(os_ <= s and e <= oe for os_, oe in ev[outer_name]), (inner_name, outer_name)


def test_no_annotation_is_built_without_a_profiler_session(tpch, monkeypatch):
    session, o, li = tpch

    class Counting(jax.profiler.TraceAnnotation):
        built = 0

        def __init__(self, name, **kw):
            type(self).built += 1
            super().__init__(name, **kw)

    monkeypatch.setattr(trace, "_annotation", Counting)
    session.run(_lookup(li))
    assert "plan.optimize" in _names(session.last_profile().trace)
    assert Counting.built == 0


def test_profiler_session_builds_one_annotation_per_span(monkeypatch, tmp_path):
    built = []

    class Recording(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(trace, "_annotation", Recording)
    with jax.profiler.trace(str(tmp_path / "prof")):
        with trace.trace("root", k=1):
            with trace.span("child", rows=3):
                pass
    assert built == ["root", "child"]  # bare names: attrs stay on the span


def test_disabled_tracing_returns_noop_under_a_profiler_session(tmp_path):
    trace.set_enabled(False)
    with jax.profiler.trace(str(tmp_path / "prof")):
        assert trace.span("x") is trace.NOOP
        with trace.trace("t") as root:
            assert root is trace.NOOP
            assert trace.span("y") is trace.NOOP


def test_lookup_profile_holds_the_plan_spans(tpch):
    session, o, li = tpch
    session.run(_lookup(li))
    prof = session.last_profile().trace
    names = _names(prof)
    for name in PLAN_SPANS:
        assert name in names, (name, names)
    (opt,) = [c for c in prof["children"] if c["name"] == "plan.optimize"]
    inside = _names(opt)
    assert all(name in inside for name in PLAN_SPANS)
    assert "device.sync" in names  # the filter mask's fetch


def test_plan_spans_keep_their_names_when_the_listing_cache_hits(tpch):
    """A committed version directory's listing is served from the cache
    once out of the racy window; the rewrite's spans stay as named, and
    ``plan.index_files`` says it hit."""
    import os
    import time

    session, o, li = tpch
    old = time.time_ns() - 10_000_000_000
    for entry in session.manager.get_indexes():
        for d in entry.content.directories:
            os.utime(Path(entry.content.root) / d, ns=(old, old))
    session.run(_lookup(li))
    session.run(_lookup(li, 23))
    prof = session.last_profile().trace
    (opt,) = [c for c in prof["children"] if c["name"] == "plan.optimize"]
    inside = _names(opt)
    assert all(name in inside for name in PLAN_SPANS)
    stack, cached = [opt], []
    while stack:
        node = stack.pop()
        if node["name"] == "plan.index_files":
            cached.append(node["attrs"]["cached"])
        stack.extend(node.get("children", ()))
    assert cached == [True]


def test_fused_join_aggregate_waits_in_device_sync(tpch, tmp_path):
    session, o, li = tpch
    for key in (FILTER_VENUE, JOIN_VENUE, AGG_VENUE, SORT_VENUE):
        session.conf.set(key, "device")
    session.conf.set(DEVICE_FUSED_KERNELS, "auto")
    plan = o.select("o_orderkey", "o_custkey").join(
        li.select("l_orderkey", "l_partkey", "l_quantity"), ["o_orderkey"], ["l_orderkey"],
    ).aggregate(["l_quantity"], [
        AggSpec.of("count", None, "n"),
        AggSpec.of("sum", "o_custkey", "s_cust"),
        AggSpec.of("sum", "l_partkey", "s_part"),
    ])
    before = stats.get("device.kernel.fused")
    with jax.profiler.trace(str(tmp_path / "prof")):
        session.run(plan)
    assert stats.get("device.kernel.fused") > before  # the run-bounds kernel ran
    names = _names(session.last_profile().trace)
    assert "device.sync" in names
    assert not any(n == "device.kernel" for n in names)
    ev = _host_events(tmp_path / "prof", ("device.sync", "device.kernel"))
    assert ev["device.sync"] and not ev["device.kernel"]
