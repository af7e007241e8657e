"""95th percentile latency of all queries completed in the window
(host clock)."""

from perfbench.spans import latency_ms


def read(run):
    return latency_ms(run, 95)
