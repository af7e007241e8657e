"""Reference TPC-H Q12: lines of the two ship modes received within the
year, with commitdate < receiptdate and shipdate < commitdate, joined to
their orders and counted by mode into high (1-URGENT, 2-HIGH) and low
order priorities.

Its answer is counts, which compare exactly. The float32 control
changes neither dates nor strings nor counts this small, so it gives
the same answer here: the cell's other queries are what it fails."""

import numpy as np

from perfbench.refs.tpch import compare_groups, day, days, group_sums, groups_of, strings

HIGH = ("1-URGENT", "2-HIGH")


def answer(params, data):
    y = params["year"]
    o = data.by_key("orders", "o_orderkey", ("o_orderkey", "o_orderpriority"))
    li = data.columns("lineitem", ("l_orderkey", "l_shipmode", "l_shipdate",
                                   "l_commitdate", "l_receiptdate"))
    mode = strings(li["l_shipmode"])
    ship, commit, receipt = (days(li[c]) for c in ("l_shipdate", "l_commitdate", "l_receiptdate"))
    keep = (
        np.isin(mode, list(params["modes"])) & (commit < receipt) & (ship < commit)
        & (receipt >= day(f"{y}-01-01")) & (receipt < day(f"{y + 1}-01-01"))
    )
    okey = o["o_orderkey"]
    lkey = li["l_orderkey"][keep]
    pos = np.minimum(np.searchsorted(okey, lkey), len(okey) - 1)
    hit = okey[pos] == lkey
    high = np.isin(strings(o["o_orderpriority"])[pos[hit]], HIGH).astype(np.float64)
    (modes,), _inv, order, starts = groups_of([mode[keep][hit]])
    return {
        "l_shipmode": modes,
        "high_line_count": group_sums(high, order, starts),
        "low_line_count": group_sums(1.0 - high, order, starts),
    }


def compare(got, want):
    return compare_groups(got, want, ("l_shipmode",), ("high_line_count", "low_line_count"), ())
