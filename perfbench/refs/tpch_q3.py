"""Reference TPC-H Q3: orders placed before DATE joined to their lines
shipped after it, revenue per order, the ten largest first (ties by
order date). Revenues accumulate in the columns' own type (float64;
float32 in the control).

Each answer row has to be a group of the reference, with its date and
ship priority exact and its revenue within a relative 1e-9 of the
group's sum of absolute terms (refs/tpch.py says why). Rows whose
revenues lie within that tolerance of each other may swap places, so
the row at each place must hold a revenue within it of the reference's
revenue at that place; nothing else may move. The float32 control errs
by about 1e-7 of each revenue and fails that."""

import numpy as np

from perfbench.refs.tpch import TOL, day, days, group_sums

RESULT = ("o_orderkey", "revenue", "o_orderdate", "o_shippriority")


def answer(params, data):
    d = day(params["date"])
    o = data.by_key("orders", "o_orderkey", ("o_orderkey", "o_orderdate", "o_shippriority"))
    li = data.columns("lineitem", ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"))
    okeep = days(o["o_orderdate"]) < d
    okey = o["o_orderkey"][okeep]
    odate = days(o["o_orderdate"])[okeep]
    oprio = np.asarray(o["o_shippriority"])[okeep]
    lkeep = days(li["l_shipdate"]) > d
    lkey = li["l_orderkey"][lkeep]
    pos = np.minimum(np.searchsorted(okey, lkey), max(len(okey) - 1, 0))
    hit = (okey[pos] == lkey) if len(okey) else np.zeros(len(lkey), bool)
    price, disc = li["l_extendedprice"][lkeep][hit], li["l_discount"][lkeep][hit]
    terms = price * (price.dtype.type(1) - disc)
    grp = pos[hit]
    order = np.argsort(grp, kind="stable")
    uniq, starts = np.unique(grp[order], return_index=True)
    rev = group_sums(terms, order, starts)
    abs_sum = group_sums(np.abs(terms).astype(np.float64), order, starts)
    keys, dates, prios = okey[uniq], odate[uniq], oprio[uniq]
    top = np.lexsort((dates, -rev.astype(np.float64)))[: params["limit"]]
    return {
        "o_orderkey": keys[top], "revenue": rev[top].astype(np.float64),
        "o_orderdate": dates[top], "o_shippriority": prios[top], "abs": abs_sum[top],
        # Every group, by key: what an answer row is checked against.
        "groups": (keys, rev.astype(np.float64), abs_sum, dates, prios),
    }


def compare(got, want):
    if not isinstance(got, dict) or any(c not in got for c in RESULT):
        return {"wrong_answers": 1}
    n = len(want["o_orderkey"])
    gkey = np.asarray(got["o_orderkey"]).astype(np.int64)
    if len(gkey) != n or len(np.unique(gkey)) != n:
        return {"wrong_answers": 1}
    if n == 0:
        return {"wrong_answers": 0}
    keys, rev, abs_sum, dates, prios = want["groups"]
    i = np.minimum(np.searchsorted(keys, gkey), len(keys) - 1)
    ok = (
        np.array_equal(keys[i], gkey)
        and np.array_equal(days(got["o_orderdate"]), dates[i])
        and np.array_equal(np.asarray(got["o_shippriority"]).astype(np.int64), prios[i].astype(np.int64))
        and bool(np.all(np.abs(np.asarray(got["revenue"], np.float64) - rev[i]) <= TOL * abs_sum[i]))
        and bool(np.all(np.abs(rev[i] - want["revenue"]) <= TOL * np.maximum(abs_sum[i], want["abs"])))
    )
    return {"wrong_answers": int(not ok)}
