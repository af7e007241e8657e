"""Share of the traced window in which the device is idle while some
host thread is inside the program's ``io.read``, ``io.footers`` or
``device.stage`` span and none inside ``plan.optimize`` (profiler trace,
the program's spans on the profiler clock), in %."""

from perfbench.program_spans import idle_pct


def read(run):
    return idle_pct(run, "io")
