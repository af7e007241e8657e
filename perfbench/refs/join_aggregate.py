"""Reference Aggregate(Join): orders joined to lineitem on the order
key, grouped by l_quantity: count, sum(o_custkey), sum(l_partkey).
Sums accumulate in the columns' own type (int64; int32 in the control)."""

import numpy as np

from perfbench.refs.compare import wrong_answer as compare  # noqa: F401


def answer(params, data):
    o = data.by_key("orders", "o_orderkey", ("o_orderkey", "o_custkey"))
    li = data.columns("lineitem", ("l_orderkey", "l_partkey", "l_quantity"))
    ok = o["o_orderkey"]
    pos = np.searchsorted(ok, li["l_orderkey"])
    hit = (pos < len(ok)) & (ok[np.minimum(pos, len(ok) - 1)] == li["l_orderkey"])
    cust = o["o_custkey"][pos[hit]]
    part = li["l_partkey"][hit]
    groups, inv = np.unique(li["l_quantity"][hit], return_inverse=True)
    order = np.argsort(inv, kind="stable")
    counts = np.bincount(inv, minlength=len(groups))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return {
        "l_quantity": groups,
        "n": counts.astype(np.int64),
        "s_cust": np.add.reduceat(cust[order], starts, dtype=cust.dtype),
        "s_part": np.add.reduceat(part[order], starts, dtype=part.dtype),
    }
