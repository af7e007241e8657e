"""Mean per query of the summed walls of the program's ``agg.channels``
spans (the host build and staging of a grouped aggregate's channels),
in ms."""

from perfbench.program_spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "agg.channels")
