"""The readers of the program's spans: device idle under span groups on
the profiler clock, by hand on synthetic intervals and on a small trace
recorded on a v5e chip (``fixtures/``), and per-query span walls."""

import types
from pathlib import Path

import pytest

from perfbench import program_spans, xplane

FIXTURES = Path(__file__).resolve().parent / "fixtures"
MS = 1e6  # ns


def _events(window, plan=(), io=(), devices=((),)):
    return {"window": [window], "spans": {"plan": list(plan), "io": list(io)},
            "devices": [[("op", s, e) for s, e in dev] for dev in devices]}


def test_plan_comes_before_io_where_they_overlap():
    # plan 10-30; io 20-50 (10 ms inside plan); device busy 40-45
    ev = _events((0, 100 * MS), plan=[(10 * MS, 30 * MS)], io=[(20 * MS, 50 * MS)],
                 devices=[[(40 * MS, 45 * MS)]])
    r = program_spans.idle_by_group(ev)
    assert r["plan"] == pytest.approx(20.0)
    assert r["io"] == pytest.approx(15.0)  # 30-50 less 5 ms busy


def test_union_over_threads_counts_an_instant_once():
    # two threads in plan at once (10-30 and 20-40), device busy 25-35
    ev = _events((0, 100 * MS), plan=[(10 * MS, 30 * MS), (20 * MS, 40 * MS)],
                 devices=[[(25 * MS, 35 * MS)]])
    r = program_spans.idle_by_group(ev)
    assert r["plan"] == pytest.approx(20.0)  # 30 ms covered, 10 busy
    assert r["io"] == 0.0


def test_spans_are_clipped_to_the_window():
    ev = _events((10 * MS, 60 * MS), plan=[(0, 20 * MS)], io=[(50 * MS, 90 * MS)])
    r = program_spans.idle_by_group(ev)
    assert r["plan"] == pytest.approx(20.0)  # 10 of 50 ms
    assert r["io"] == pytest.approx(20.0)


def test_busy_is_averaged_over_devices():
    ev = _events((0, 100 * MS), plan=[(0, 50 * MS)], devices=[[(0, 50 * MS)], []])
    assert program_spans.idle_by_group(ev)["plan"] == pytest.approx(25.0)


def test_a_program_without_the_spans_gives_none():
    assert program_spans.idle_by_group(_events((0, 100 * MS))) is None


def test_subtract():
    assert program_spans._subtract([(0, 10), (20, 30)], [[5, 8], [9, 22], [25, 26]]) == [
        (0, 5), (8, 9), (22, 25), (26, 30)]
    assert program_spans._subtract([(0, 10)], []) == [(0, 10)]


def test_mean_span_ms_over_queries():
    def query(*walls):
        kids = [{"name": "plan.index_files", "wall_s": w} for w in walls]
        return types.SimpleNamespace(kind="query", evidence={"profile": {
            "trace": {"name": "query", "wall_s": 1.0, "children": kids}}})

    run = types.SimpleNamespace(ops=[query(0.001, 0.002), query()])
    assert program_spans.mean_span_ms(run, "plan.index_files") == pytest.approx(1.5)
    assert program_spans.mean_span_ms(run, "plan.prefetch") is None


def test_without_a_trace_the_idle_readers_give_none():
    run = types.SimpleNamespace(trace=None)
    assert program_spans.idle_pct(run, "plan") is None


def test_recorded_v5e_trace_with_the_program_spans():
    """A 3 s window of sf1_lookup (50 queries) traced on one TPU v5e chip with the
    program's spans bridged onto the profiler clock."""
    path = FIXTURES / "sf1_lookup_spans_v5e.xplane.pb"
    events = program_spans.read_groups(path)
    shares = program_spans.idle_by_group(events)
    whole = xplane.reduce(xplane.read(path))
    idle_query = (1 - whole["busy_s"] / whole["window_s"]) * 100
    assert shares["plan"] > 0
    assert shares["plan"] + shares["io"] <= idle_query
    # The device is idle all through planning, and planning runs on one
    # thread: the plan share is the summed planning time.
    (w0, w1), = events["window"]
    planning = sum(min(e, w1) - max(s, w0) for s, e in events["spans"]["plan"] if e > w0 and s < w1)
    assert shares["plan"] / 100 * (w1 - w0) == pytest.approx(planning, rel=0.01)
    assert len(whole["ops"]) == len(events["spans"]["plan"])
