"""The trace reduction: by hand on synthetic events, and on a small
trace recorded on a v5e chip (``fixtures/``)."""

from pathlib import Path

import pytest

from perfbench import xplane

FIXTURES = Path(__file__).resolve().parent / "fixtures"
MS = 1e6  # ns


def test_reduce_by_hand():
    host = [
        ("window", 0, 100 * MS),
        ("op:q#0", 10 * MS, 50 * MS), ("run:q#0", 10 * MS, 40 * MS), ("decode:q#0", 40 * MS, 50 * MS),
        ("op:q#1", 60 * MS, 90 * MS), ("run:q#1", 60 * MS, 90 * MS),
    ]
    # device busy 20-30 (inside q#0's run), 25-35 overlapping, and 70-80
    # plus 95-120 (clipped to the window's end at 100)
    dev = [("a", 20 * MS, 30 * MS), ("b", 25 * MS, 35 * MS), ("a", 70 * MS, 80 * MS),
           ("c", 95 * MS, 120 * MS)]
    r = xplane.reduce({"host": host, "devices": [dev]})
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030)  # 15 + 10 + 5 ms
    ops = {(o["op"], o["index"]): o for o in r["ops"]}
    assert ops[("q", 0)]["device_s"] == pytest.approx(0.015)
    assert ops[("q", 1)]["device_s"] == pytest.approx(0.010)
    idle = dict(r["idle_gaps"])
    assert idle["q.run"] == pytest.approx((30 - 15) / 1e3 + (30 - 10) / 1e3)
    assert idle["q.decode"] == pytest.approx(0.010)
    # outside the ops: 0-10, 50-60, 90-100 = 30 ms, of which 5 ms busy
    assert idle["between ops"] == pytest.approx(0.025)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert dict(r["device_ops"])["a"] == pytest.approx(0.020)
    assert dict(r["device_ops"])["c"] == pytest.approx(0.005)


def test_busy_is_averaged_over_devices():
    host = [("window", 0, 10 * MS)]
    r = xplane.reduce({"host": host, "devices": [[("a", 0, 10 * MS)], []]})
    assert r["busy_s"] == pytest.approx(0.005)


def test_no_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"host": [], "devices": [[]]})


def test_recorded_v5e_trace():
    """A 2.5 s window of sf1_lookup traced on one TPU v5e chip: 40
    queries (36 lookups, 4 scans), one mask program each."""
    events = xplane.read(FIXTURES / "sf1_lookup_v5e.xplane.pb")
    r = xplane.reduce(events)
    assert r["devices"] == 1
    assert len(r["ops"]) == 40
    assert {o["op"] for o in r["ops"]} == {"point_lookup", "short_scan"}
    (dev,) = events["devices"]
    assert len(dev) == 40
    # the device's events lie inside the host's window: one clock
    (w,) = [(s, e) for n, s, e in events["host"] if n == xplane.WINDOW]
    assert all(w[0] <= s and e <= w[1] for _, s, e in dev)
    # no two ops overlap here, so busy is their summed duration, and all
    # of it falls inside the queries' annotations
    assert r["busy_s"] == pytest.approx(sum(e - s for _, s, e in dev) / 1e9)
    assert sum(o["device_s"] for o in r["ops"]) == pytest.approx(r["busy_s"])
    assert r["window_s"] == pytest.approx(2.492778058)
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert max(idle, key=idle.get) == "point_lookup.run"
    assert dict(r["device_ops"]) == pytest.approx({
        "jit_raw/compare_and_fusion pred[32768]": 3.7874e-05,
        "jit_raw/or_and_fusion pred[16384]": 4.244e-06,
    })


def test_op_name():
    assert xplane.op_name(
        "jit_raw(123)",
        "%fusion.12 = (f32[12800]{0:T(1024)S(1)}, f32[12800]{0:T(1024)S(1)}) fusion(f32[1] %a)",
    ) == "jit_raw/fusion.12 (f32[12800], f32[12800])"
