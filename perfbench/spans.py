"""Reading the program's own spans and the latencies of a run."""

from __future__ import annotations

import numpy as np


def span_seconds(profile_json: dict, name: str) -> float:
    """Summed wall seconds of the spans called `name` in one query's trace."""
    total, stack = 0.0, [profile_json.get("trace")]
    while stack:
        node = stack.pop()
        if not node:
            continue
        if node.get("name") == name:
            total += node.get("wall_s") or 0.0
        stack.extend(node.get("children", []))
    return total


def queries(run) -> list:
    return [op for op in run.ops if op.kind == "query"]


def builds(run) -> list:
    return [op for op in run.ops if op.kind == "build"]


def latency_ms(run, q: float):
    """The q-th percentile of the window's query latencies, in ms."""
    lat = [op.latency_s for op in queries(run)]
    return float(np.percentile(lat, q)) * 1e3 if lat else None


def idle_pct(run, ops) -> float | None:
    """Device idle share of the traced window, where the window ran `ops`."""
    if run.trace is None or not ops or run.trace["window_s"] <= 0:
        return None
    return (1.0 - run.trace["busy_s"] / run.trace["window_s"]) * 100.0
