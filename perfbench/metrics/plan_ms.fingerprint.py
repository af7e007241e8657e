"""Mean per query of the summed walls of the program's
``plan.fingerprint`` spans (the source plan's fingerprint, matched
against each candidate index's signature), in ms."""

from perfbench.program_spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "plan.fingerprint")
