"""Reference short scan: l_orderkey in [lo, lo + span) and
l_quantity <= qty_max."""

import numpy as np

from perfbench.refs.compare import wrong_answer as compare  # noqa: F401

COLUMNS = ("l_orderkey", "l_partkey", "l_quantity")


def answer(params, data):
    li = data.by_key("lineitem", "l_orderkey", COLUMNS)
    k = li["l_orderkey"]
    as_key = k.dtype.type  # a needle of another dtype would copy the whole column
    lo = np.searchsorted(k, as_key(params["lo"]), side="left")
    hi = np.searchsorted(k, as_key(params["lo"] + params["span"]), side="left")
    m = li["l_quantity"][lo:hi] <= params["qty_max"]
    return {c: li[c][lo:hi][m] for c in COLUMNS}
