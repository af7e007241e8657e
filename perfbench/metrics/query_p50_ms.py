"""Median latency of the queries completed in the window, call to
decoded answer (host clock)."""

from perfbench.spans import latency_ms


def read(run):
    return latency_ms(run, 50)
