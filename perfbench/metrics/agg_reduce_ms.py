"""Mean per query of the summed walls of the program's ``agg.reduce``
spans (a grouped aggregate's device reduction, from the call until its
result is on the host), in ms."""

from perfbench.program_spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "agg.reduce")
