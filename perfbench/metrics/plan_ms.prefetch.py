"""Mean per query of the summed walls of the program's ``plan.prefetch``
spans (the index files' prefetch, issued inside ``plan.optimize``), in
ms."""

from perfbench.program_spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "plan.prefetch")
