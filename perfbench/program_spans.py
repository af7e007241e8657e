"""The program's own spans, read two ways.

- From each query's profile (``QueryProfile.trace``): the mean per query
  of the summed walls of the spans of one name.
- On the profiler's clock: while the profiler records, the program
  enters a ``TraceAnnotation`` of each recorded span's name, so the
  traced window's ``.xplane.pb`` holds the spans beside the device's
  ops. A group's share is the union of its spans' intervals over every
  host thread, clipped to the ``window`` annotation, less the time the
  device was busy in it, over the window's length. An instant inside
  spans of two groups counts for the group named first in ``GROUPS``, so
  the shares add up to at most the whole window's idle share.

A program that has neither the spans nor the bridge gives None: the
metric is then left out of its line.
"""

from __future__ import annotations

from pathlib import Path

from perfbench import xplane
from perfbench.harness import WORK
from perfbench.spans import queries, span_seconds

# Span names of each group, in the order that settles an instant in two.
GROUPS = {
    "plan": ("plan.optimize",),
    "io": ("io.read", "io.footers", "device.stage"),
}


def mean_span_ms(run, name: str):
    """Mean per query of the summed walls of the `name` spans, in ms; None
    where no query of the window recorded such a span."""
    qs = queries(run)
    total = sum(span_seconds(op.evidence["profile"], name) for op in qs)
    return total / len(qs) * 1e3 if total > 0 else None


def read_groups(path: Path) -> dict:
    """The window annotation, the intervals of each group's spans, and
    each device's op intervals, from one trace file."""
    from jax.profiler import ProfileData

    group_of = {n: g for g, names in GROUPS.items() for n in names}
    window, spans, devices = [], {g: [] for g in GROUPS}, []
    for plane in ProfileData.from_file(str(path)).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            devices.append(xplane._device_events(plane))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name in group_of:
                        spans[group_of[ev.name]].append(iv)
                    elif ev.name == xplane.WINDOW:
                        window.append(iv)
    return {"window": window, "spans": spans, "devices": devices}


def _subtract(a: list, b: list) -> list:
    """The parts of the sorted disjoint intervals `a` outside those of `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def idle_by_group(events: dict) -> dict | None:
    """{group: device idle under the group's spans, % of the window};
    None without a window, a device, or any span of a group."""
    if not events["window"] or not events["devices"] or not any(events["spans"].values()):
        return None
    w0, w1 = events["window"][0]
    busy = [xplane.Busy([(max(s, w0), min(e, w1)) for _, s, e in evs if e > w0 and s < w1])
            for evs in events["devices"]]
    out, taken = {}, []
    for group in GROUPS:
        own = xplane._union([(max(s, w0), min(e, w1)) for s, e in events["spans"][group]
                             if e > w0 and s < w1])
        idle = sum((e - s) - sum(b.within(s, e) for b in busy) / len(busy)
                   for s, e in _subtract(own, taken))
        out[group] = idle / (w1 - w0) * 100.0
        taken = xplane._union(taken + own)
    return out


_memo: dict = {}


def idle_pct(run, group: str):
    """Device idle under `group`'s spans in the traced window of `run`, in
    %: one read of the trace file serves every group."""
    if run.trace is None:
        return None
    try:
        path = xplane.find_trace(WORK / f"trace-{run.cell.name}")
    except FileNotFoundError:
        return None
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _memo:
        _memo.clear()
        _memo[key] = idle_by_group(read_groups(path))
    shares = _memo[key]
    return None if shares is None else shares[group]
