"""Durable telemetry journal (obs/journal.py, docs/observability.md
"telemetry journal"): segment rotation through the atomic tmp+replace
publish, byte-budgeted eviction, the advisory IO contract, the
event/span/SLO taps, the fleet merge reader — and the crash-safety
story proven with a REAL ``kill -9``: a journaling child killed
mid-segment leaves sealed segments that merge cleanly, a torn
``.tmp-seg-*`` tail that merge skips and ``sweep()`` removes."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from hyperspace_tpu import faults, stats
from hyperspace_tpu.analysis.duradomain import TORN_WINDOWS
from hyperspace_tpu.faults import CrashPoint
from hyperspace_tpu.obs import events, journal, metrics, slo, trace
from hyperspace_tpu.obs import export as obs_export


def _enable(tmp_path, **kw):
    # Big enough that only an explicit seal() publishes (the first
    # record also carries an opportunistic full-registry metrics
    # snapshot, which alone overflows a tiny segment budget).
    kw.setdefault("segment_bytes", 1 << 20)
    journal.configure(enabled=True, root=str(tmp_path / "_obs"), **kw)
    return tmp_path / "_obs"


def _my_dir(root):
    return root / str(os.getpid())


# -- write path / rotation ---------------------------------------------------


def test_record_seal_merge_roundtrip(tmp_path):
    root = _enable(tmp_path)
    journal.record("event", event={"name": "x", "seq": 1})
    journal.record("span", trace={"name": "query", "trace_id": "1-1"})
    # Nothing is visible until the active segment is sealed: readers
    # only ever see whole segments.
    assert journal.segment_paths(_my_dir(root)) == []
    journal.seal()
    (seg,) = journal.segment_paths(_my_dir(root))
    kinds = [r["kind"] for r in journal.read_segment(seg)]
    assert "event" in kinds and "span" in kinds
    merged = journal.merge_dir(root)
    assert all(r["pid"] == os.getpid() for r in merged)
    assert [r.get("ts") for r in merged] == sorted(r.get("ts") for r in merged)
    assert stats.get("obs.journal.records") >= 2
    assert stats.get("obs.journal.segments_sealed") == 1


def test_segment_rotation_is_atomic_and_ordered(tmp_path):
    root = _enable(tmp_path, segment_bytes=1024)
    for i in range(200):
        journal.record("event", event={"name": "fill", "seq": i, "pad": "p" * 64})
    journal.seal()
    segs = journal.segment_paths(_my_dir(root))
    assert len(segs) >= 2  # rotated at the byte budget
    numbers = [int(p.name[len("segment-"):-len(".jsonl")]) for p in segs]
    assert numbers == sorted(numbers)
    # Every published segment is whole: each line parses.
    for seg in segs:
        with open(seg, encoding="utf-8") as f:
            for line in f:
                json.loads(line)
    # Replay preserves the emission order within this process.
    seqs = [r["event"]["seq"] for r in journal.merge_dir(root)
            if r["kind"] == "event" and r["event"].get("name") == "fill"]
    assert seqs == sorted(seqs)


def test_eviction_holds_byte_budget_keeping_newest(tmp_path):
    root = _enable(tmp_path, segment_bytes=1024, max_bytes=4096)
    for i in range(400):
        journal.record("event", event={"name": "fill", "seq": i, "pad": "p" * 64})
    journal.seal()
    segs = journal.segment_paths(_my_dir(root))
    assert stats.get("obs.journal.evictions") > 0
    total = sum(p.stat().st_size for p in segs)
    assert total <= 4096 + 2048  # budget + at most the newest overshoot
    # The newest records survived eviction; the oldest were dropped.
    seqs = [r["event"]["seq"] for r in journal.merge_dir(root)
            if r["kind"] == "event"]
    assert 399 in seqs and 0 not in seqs


def test_metrics_snapshots_ride_the_write_path(tmp_path):
    root = _enable(tmp_path, snapshot_s=0.1)
    metrics.counter("serve.completed").inc(7)
    journal.record("event", event={"name": "tick", "seq": 1})
    journal.seal()
    snaps = [r for r in journal.merge_dir(root) if r["kind"] == "metrics"]
    assert snaps and snaps[0]["metrics"]["serve.completed"] == 7


def test_disabled_journal_is_a_noop(tmp_path):
    journal.configure(enabled=False, root=str(tmp_path / "_obs"))
    journal.record("event", event={"name": "x"})
    journal.seal()
    assert not (tmp_path / "_obs").exists()
    assert stats.get("obs.journal.records") == 0


def test_io_failures_are_advisory_counted_not_raised(tmp_path):
    # Point the journal root AT A FILE: every open fails, nothing raises.
    blocker = tmp_path / "blocked"
    blocker.write_text("not a directory")
    journal.configure(enabled=True, root=str(blocker))
    journal.record("event", event={"name": "x"})
    assert stats.get("obs.journal.errors") >= 1
    assert stats.get("obs.journal.records") == 0


# -- taps ---------------------------------------------------------------------


def test_event_span_and_slo_taps_feed_the_journal(tmp_path):
    root = _enable(tmp_path)
    evt = events.declare("advisor.routing.demoted")  # any declared event
    evt.emit(detail="hello")
    with trace.trace("q"):
        pass
    # Walk the SLO sampler into a page: baseline traffic, then a hard
    # failure burst (the controller tests' _drive_page shape).
    completed = metrics.counter("serve.completed")
    failed = metrics.counter("serve.failed")
    metrics.counter("serve.timeouts")
    metrics.counter("serve.cancelled")
    metrics.histogram("serve.latency.seconds")
    completed.inc(10_000)
    slo.sample(0.0)
    slo.evaluate(0.0)
    slo.sample(4000.0)
    slo.evaluate(4000.0)
    failed.inc(3_000)
    slo.sample(4030.0)
    slo.evaluate(4030.0)
    journal.seal()
    merged = journal.merge_dir(root)
    tapped = [r["event"]["name"] for r in merged if r["kind"] == "event"]
    assert "advisor.routing.demoted" in tapped
    span_names = [r["trace"]["name"] for r in merged if r["kind"] == "span"]
    assert "q" in span_names
    transitions = [(r["objective"], r["previous"], r["verdict"])
                   for r in merged if r["kind"] == "slo"]
    assert ("serve.availability", "ok", "page") in transitions


def test_worker_state_shipping_roundtrip(tmp_path):
    _enable(tmp_path, segment_bytes=2048)
    state = journal.export_state()
    assert state["enabled"] and state["parent_pid"] == os.getpid()
    # install_state in THIS process is what a worker would run: it
    # reconfigures and stamps a process record.
    journal.install_state(dict(state, worker_id=3))
    journal.seal()
    merged = journal.merge_dir(journal.root())
    procs = [r for r in merged if r["kind"] == "process"]
    assert procs and procs[-1]["worker_id"] == 3
    assert procs[-1]["parent_pid"] == os.getpid()


# -- reader tolerance ---------------------------------------------------------


def test_merge_skips_torn_and_alien_lines(tmp_path):
    root = _enable(tmp_path)
    journal.record("event", event={"name": "good", "seq": 1})
    journal.seal()
    (seg,) = journal.segment_paths(_my_dir(root))
    with open(seg, "a", encoding="utf-8") as f:
        f.write('{"torn": tr')  # a torn JSON tail
    # An alien (non-journal) pid dir entry and a foreign file.
    (root / "notes.txt").write_text("not a pid dir")
    docs = journal.read_segment(seg)
    assert [d["event"]["seq"] for d in docs if d.get("kind") == "event"] == [1]
    assert journal.merge_dir(root)  # does not raise on the alien file


def test_sweep_removes_torn_tmp_but_not_the_live_tail(tmp_path):
    root = _enable(tmp_path)
    # A dead writer's torn tail in another pid's dir.
    dead = root / "99999"
    dead.mkdir(parents=True)
    torn = dead / ".tmp-seg-abc"
    torn.write_text('{"ts": 1.0, "kind": "event"')
    # Our own live active segment.
    journal.record("event", event={"name": "live", "seq": 1})
    live_tmp = [p for p in _my_dir(root).iterdir()
                if p.name.startswith(".tmp-seg-")]
    assert live_tmp
    removed = journal.sweep(root)
    assert str(torn) in removed and not torn.exists()
    assert all(p.exists() for p in live_tmp)  # the live tail is ours


# -- crash safety: a REAL kill -9 mid-rotation --------------------------------

_CHILD = r"""
import sys
from hyperspace_tpu.obs import journal
journal.configure(enabled=True, root=sys.argv[1], segment_bytes=1024)
i = 0
while True:  # journals forever, until killed
    journal.record("event", event={"name": "child", "seq": i, "pad": "p" * 64})
    i += 1
"""


def test_sigkill_mid_rotation_leaves_mergeable_segments(tmp_path):
    root = tmp_path / "_obs"
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(root)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        child_dir = root / str(proc.pid)
        deadline = time.monotonic() + 60.0
        # Wait until the child has sealed at least two segments AND has
        # an active tmp tail — then SIGKILL it mid-segment. The child is
        # stopped while its directory is read, so the tail seen is the
        # tail the kill tears.
        while time.monotonic() < deadline:
            proc.send_signal(signal.SIGSTOP)
            sealed = journal.segment_paths(child_dir)
            tmps = (
                [p for p in child_dir.iterdir()
                 if p.name.startswith(".tmp-seg-")]
                if child_dir.is_dir() else []
            )
            if len(sealed) >= 2 and tmps:
                break
            proc.send_signal(signal.SIGCONT)
            time.sleep(0.05)
        else:
            raise AssertionError("child never sealed two segments")
    finally:
        proc.kill()  # SIGKILL: no cleanup handlers run
        proc.wait(timeout=30.0)
    assert proc.returncode == -signal.SIGKILL
    # The torn tail is invisible to readers and the sealed history
    # replays in order with no gaps.
    merged = journal.merge_dir(root)
    seqs = [r["event"]["seq"] for r in merged if r["kind"] == "event"]
    assert seqs == list(range(len(seqs))) and len(seqs) > 0
    # sweep() reaps the torn tmp tail the kill left behind.
    leftover = [p for p in (root / str(proc.pid)).iterdir()
                if p.name.startswith(".tmp-seg-")]
    assert leftover  # the kill really did tear an active segment
    journal.sweep(root)
    assert not [p for p in (root / str(proc.pid)).iterdir()
                if p.name.startswith(".tmp-seg-")]
    assert journal.merge_dir(root) == merged  # sweep changed no history


# -- fleet chrome export ------------------------------------------------------


def _write_member_journal(root, pid, spans):
    d = root / str(pid)
    d.mkdir(parents=True)
    with open(d / "segment-00000000.jsonl", "w", encoding="utf-8") as f:
        for i, sp in enumerate(spans):
            f.write(json.dumps(
                {"ts": float(i), "pid": pid, "kind": "span", "trace": sp}
            ) + "\n")


def test_fleet_chrome_lanes_are_pid_qualified(tmp_path):
    """Two members whose OS thread ids collide (tid=1 in both — every
    member's main thread) must land on separate per-pid track groups,
    not interleave on one lane."""
    root = tmp_path / "_obs"
    _write_member_journal(root, 101, [
        {"name": "qa", "trace_id": "101-1", "tid": 1, "t0_s": 0.0, "wall_s": 1.0}
    ])
    _write_member_journal(root, 202, [
        {"name": "qb", "trace_id": "202-1", "tid": 1, "t0_s": 0.5, "wall_s": 1.0}
    ])
    roots = obs_export.roots_from_fleet(str(root))
    assert {r["pid"] for r in roots} == {101, 202}
    doc = obs_export.chrome_trace(roots)
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {(e["pid"], e["name"]) for e in slices} == {(101, "qa"), (202, "qb")}
    # Same raw tid, different pids => distinct (pid, lane) tracks with
    # per-pid alias numbering starting at 1 in each group.
    assert {(e["pid"], e["tid"]) for e in slices} == {(101, 1), (202, 1)}
    names = [e for e in doc["traceEvents"] if e["ph"] == "M"
             and e["name"] == "process_name"]
    assert {m["args"]["name"] for m in names} == {
        "member pid 101", "member pid 202"
    }


# -- torn-window sweep, driven BY NAME from the static registry --------------


def _drive_seal_before_index(tmp_path, point):
    """Kill between the segment publish (replace + dir fsync) and the
    eviction/bookkeeping index: the sealed segment must be whole, the
    bookkeeping must be untouched, and a restarted journaler re-scans
    the directory and indexes PAST the orphan instead of over it."""
    root = _enable(tmp_path)
    journal.record("event", event={"name": "torn", "seq": 0})
    sealed_before = stats.get("obs.journal.segments_sealed")
    faults.inject(point, crash=True, at_call=1)
    try:
        with pytest.raises(CrashPoint):
            journal.seal()
    finally:
        faults.reset()
    # First half of the window held: the segment published whole …
    (seg,) = journal.segment_paths(_my_dir(root))
    seqs = [r["event"]["seq"] for r in journal.read_segment(seg)
            if r["kind"] == "event" and r["event"].get("name") == "torn"]
    assert seqs == [0]
    # … and the second half never ran: no seal counted, no eviction.
    assert stats.get("obs.journal.segments_sealed") == sealed_before
    # A real kill takes the process; model the restart with the
    # journal's own reset (fresh segment cursor -> directory re-scan).
    journal.reset()
    _enable(tmp_path)
    journal.record("event", event={"name": "torn", "seq": 1})
    journal.seal()
    segs = journal.segment_paths(_my_dir(root))
    assert len(segs) == 2  # the orphan was indexed past, not overwritten
    merged = [r["event"]["seq"] for r in journal.merge_dir(root)
              if r["kind"] == "event" and r["event"].get("name") == "torn"]
    assert merged == [0, 1]
    assert journal.sweep(root) == []  # sealed segments are never swept


_TORN_WINDOW_DRIVERS = {
    "journal.seal_before_index": _drive_seal_before_index,
}


@pytest.mark.parametrize(
    "window", sorted(k for k in TORN_WINDOWS if k.startswith("journal."))
)
def test_kill_inside_window_converges(window, tmp_path):
    """A journal window added to `analysis.duradomain.TORN_WINDOWS`
    without a driver here fails with a KeyError — the crash sweep can
    never silently drift from the statically proven protocol set."""
    _fn, _first, _second, point, why = TORN_WINDOWS[window]
    assert point in faults.KNOWN_POINTS, why
    _TORN_WINDOW_DRIVERS[window](tmp_path, point)
