"""Mean per query of the summed walls of the program's
``plan.index_files`` spans (listing and stat of an index's bucket files
and its manifest read, once per rewrite), in ms."""

from perfbench.program_spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "plan.index_files")
