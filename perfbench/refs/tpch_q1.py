"""Reference TPC-H Q1: the lines shipped by the cutoff, grouped by
return flag and line status, with the spec's eight aggregates. Sums
accumulate in the columns' own type (float64; float32 in the control).

Group keys and counts compare exactly; each sum and mean within a
relative 1e-9 of its group's sum of absolute terms (refs/tpch.py says
why). The float32 control errs by about 1e-7 or more on every sum and
fails that: sum_qty alone passes 2^24 at SF1, where float32 stops
holding whole numbers."""

import datetime

import numpy as np

from perfbench.refs.tpch import TOL, compare_groups, day, days, group_sums, groups_of, strings

KEYS = ("l_returnflag", "l_linestatus")
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
MEANS = ("avg_qty", "avg_price", "avg_disc")


def answer(params, data):
    li = data.columns("lineitem", ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
                                   "l_extendedprice", "l_discount", "l_tax"))
    cut = (datetime.date(1998, 12, 1) - datetime.timedelta(days=params["delta"])).isoformat()
    keep = days(li["l_shipdate"]) <= day(cut)
    qty, price = li["l_quantity"][keep], li["l_extendedprice"][keep]
    disc, tax = li["l_discount"][keep], li["l_tax"][keep]
    one = price.dtype.type(1)
    disc_price = price * (one - disc)
    terms = {
        "sum_qty": qty, "sum_base_price": price, "sum_disc_price": disc_price,
        "sum_charge": disc_price * (one + tax),
        "avg_qty": qty, "avg_price": price, "avg_disc": disc,
    }
    (flag, status), _inv, order, starts = groups_of(
        [strings(li["l_returnflag"][keep]), strings(li["l_linestatus"][keep])]
    )
    counts = np.diff(np.append(starts, len(order))).astype(np.int64)
    out = {"l_returnflag": flag, "l_linestatus": status, "count_order": counts, "tol": {}}
    for name, t in terms.items():
        s = group_sums(t, order, starts)
        abs_sum = group_sums(np.abs(t).astype(np.float64), order, starts)
        if name in MEANS:
            out[name] = s / counts
            out["tol"][name] = TOL * abs_sum / counts
        else:
            out[name] = s
            out["tol"][name] = TOL * abs_sum
    return out


def compare(got, want):
    return compare_groups(got, want, KEYS, ("count_order",), SUMS + MEANS)
