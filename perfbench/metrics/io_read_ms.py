"""Mean per query of the summed walls of the program's ``io.read``
spans (parquet decode on a decoded-table cache miss), in ms."""

from perfbench.spans import queries, span_seconds


def read(run):
    qs = queries(run)
    if not qs:
        return None
    return sum(span_seconds(op.evidence["profile"], "io.read") for op in qs) / len(qs) * 1e3
