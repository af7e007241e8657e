"""Share of the traced window in which no operation ran on the device,
in a window of queries (profiler trace), in %."""

from perfbench.spans import idle_pct, queries


def read(run):
    return idle_pct(run, queries(run))
