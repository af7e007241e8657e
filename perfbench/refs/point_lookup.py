"""Reference point lookup: the rows whose l_orderkey equals the key."""

import numpy as np

from perfbench.refs.compare import wrong_answer as compare  # noqa: F401

COLUMNS = ("l_orderkey", "l_partkey", "l_extendedprice")


def answer(params, data):
    li = data.by_key("lineitem", "l_orderkey", COLUMNS)
    k = li["l_orderkey"]
    as_key = k.dtype.type  # a needle of another dtype would copy the whole column
    lo = np.searchsorted(k, as_key(params["key"]), side="left")
    hi = np.searchsorted(k, as_key(params["key"]), side="right")
    return {c: li[c][lo:hi] for c in COLUMNS}
