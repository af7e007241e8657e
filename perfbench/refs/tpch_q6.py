"""Reference TPC-H Q6: one year of shipments with a discount within a
cent of DISCOUNT and a quantity under QUANTITY, summing extendedprice *
discount in the columns' own type (float64; float32 in the control).

The revenue compares within a relative 1e-9 of its sum of absolute
terms (refs/tpch.py says why). The float32 control errs by about 1e-7
or more over some 110,000 terms at SF1 and fails that."""

import numpy as np

from perfbench.refs.tpch import TOL, compare_groups, day, days, group_sums


def answer(params, data):
    li = data.columns("lineitem", ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice"))
    y = params["year"]
    ship = days(li["l_shipdate"])
    disc = li["l_discount"]
    keep = (
        (ship >= day(f"{y}-01-01")) & (ship < day(f"{y + 1}-01-01"))
        & (disc >= disc.dtype.type(params["lo"])) & (disc <= disc.dtype.type(params["hi"]))
        & (li["l_quantity"] < params["quantity"])
    )
    t = li["l_extendedprice"][keep] * disc[keep]
    # A global aggregate: one row, NULL over no rows (never at SF1).
    total = group_sums(t, np.arange(len(t)), np.zeros(1, np.int64))[0] if len(t) else np.nan
    return {
        "revenue": np.array([total], np.float64),
        "tol": {"revenue": np.array([TOL * float(np.abs(t).astype(np.float64).sum())])},
    }


def compare(got, want):
    return compare_groups(got, want, (), (), ("revenue",))
