"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The harness marks the measured window and each operation on the host
with ``jax.profiler.TraceAnnotation``: ``window``, ``op:<name>#<i>``
around one operation, and inside it ``run:<name>#<i>`` (the call into
the program) and ``decode:<name>#<i>`` (the answer decoded on the host).
From the device planes this module takes the intervals in which an
operation ran (the ``XLA Ops`` line) and gives:

- busy seconds in the window (the union of the op intervals, averaged
  over the chips) and the window's length;
- each operation's device seconds: the union clipped to its annotation;
- the device ops that took most time in the window;
- the device's idle time inside the window, attributed to what the host
  was doing in it: which operation, and whether in its run or decode.
"""

from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

WINDOW = "window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OP = re.compile(r"^(op|run|decode):(.+)#(\d+)$")


def find_trace(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Busy:
    """Merged busy intervals of one device, queried by time range."""

    def __init__(self, intervals: list):
        self.iv = _union(intervals)
        self.starts = [s for s, _ in self.iv]

    def within(self, a: float, b: float) -> float:
        """Busy nanoseconds inside [a, b)."""
        if b <= a or not self.iv:
            return 0.0
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        j = bisect.bisect_left(self.starts, b)
        total = 0.0
        for s, e in self.iv[i:j]:
            total += max(0.0, min(e, b) - max(s, a))
        return total


def op_name(module: str, hlo: str) -> str:
    """``<jitted module>/<instruction> <result type>`` from the event names:
    the module event is ``jit_raw(<hash>)``, the op event the HLO text
    ``%fusion.12 = (f32[12800]{...}, ...) fusion(...)``."""
    inst, _, rest = hlo.partition(" = ")
    m = re.match(r"(\(.*?\)|\S+)", re.sub(r"\{[^}]*\}", "", rest))
    return f"{module.split('(')[0]}/{inst.lstrip('%')} {m.group(1) if m else ''}".strip()


def _device_events(plane) -> list:
    lines = {ln.name: ln for ln in plane.lines}
    modules = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else [])
    )
    starts = [m[0] for m in modules]
    out = []
    for ev in (lines[OPS_LINE].events if OPS_LINE in lines else []):
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][2] if i >= 0 and modules[i][1] >= s else "?"
        out.append((op_name(module, ev.name), s, e))
    return out


def read(path: Path) -> dict:
    """Host annotations and device op events of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host, devices = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(_device_events(plane))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW or _OP.match(ev.name):
                        host.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return {"host": host, "devices": devices}


def reduce(events: dict, top: int = 10) -> dict:
    """The trace's numbers; see the module docstring. Times in seconds."""
    host, devices = events["host"], events["devices"]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows or not devices:
        raise ValueError("trace holds no window annotation or no device plane")
    w0, w1 = windows[0]
    busy = [Busy([(max(s, w0), min(e, w1)) for _, s, e in evs if e > w0 and s < w1])
            for evs in devices]
    n_dev = len(busy)

    def busy_in(a, b):
        return sum(x.within(a, b) for x in busy) / n_dev

    ops: dict = {}
    parts: dict = collections.defaultdict(list)
    for name, s, e in host:
        m = _OP.match(name)
        if m is None:
            continue
        kind, op, i = m.group(1), m.group(2), int(m.group(3))
        if kind == "op":
            ops[(op, i)] = (s, e)
        else:
            parts[(op, i)].append((s, e, f"{op}.{kind}"))

    per_op = []
    idle: dict = collections.defaultdict(float)
    covered = 0.0
    for (op, i), (s, e) in sorted(ops.items(), key=lambda kv: kv[1][0]):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        per_op.append({"op": op, "index": i, "wall_s": (e - s) / 1e9,
                       "device_s": busy_in(s, e) / 1e9})
        covered += e - s
        t = s
        for ps, pe, label in sorted(parts[(op, i)]):
            ps, pe = max(ps, s), min(pe, e)
            if ps > t:
                idle[f"{op}.other"] += (ps - t) - busy_in(t, ps)
            if pe > ps:
                idle[label] += (pe - ps) - busy_in(ps, pe)
                t = max(t, pe)
        if e > t:
            idle[f"{op}.other"] += (e - t) - busy_in(t, e)
    busy_ns = busy_in(w0, w1)
    op_busy = sum(o["device_s"] for o in per_op) * 1e9
    idle["between ops"] += ((w1 - w0) - covered) - (busy_ns - op_busy)

    op_time: dict = collections.defaultdict(float)
    for evs in devices:
        for name, s, e in evs:
            if e > w0 and s < w1:
                op_time[name] += (min(e, w1) - max(s, w0)) / n_dev
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": n_dev,
        "ops": per_op,
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top] if t > 0],
    }
