"""Mean wall of the program's ``plan.optimize`` span per query (the
rewrite rules, pushdown and pruning), in ms."""

from perfbench.spans import queries, span_seconds


def read(run):
    qs = queries(run)
    if not qs:
        return None
    return sum(span_seconds(op.evidence["profile"], "plan.optimize") for op in qs) / len(qs) * 1e3
