"""Queries completed over the window's seconds (host clock)."""

from perfbench.spans import queries


def read(run):
    n = len(queries(run))
    return n / run.window_s if n else None
