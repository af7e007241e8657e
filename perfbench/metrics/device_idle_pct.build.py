"""Share of the traced window in which no operation ran on the device,
in a window of index rebuilds (profiler trace), in %."""

from perfbench.spans import builds, idle_pct


def read(run):
    return idle_pct(run, builds(run))
