"""Share of the traced window in which the device is idle while some
host thread is inside the program's ``plan.optimize`` span (profiler
trace, the program's spans on the profiler clock), in %."""

from perfbench.program_spans import idle_pct


def read(run):
    return idle_pct(run, "plan")
