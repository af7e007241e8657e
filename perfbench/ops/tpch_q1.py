"""TPC-H Q1, the pricing summary report (clause 2.4.1): the lines
shipped by 1998-12-01 less DELTA days, grouped by return flag and line
status with the spec's eight aggregates, ordered by the two flags.
qgen draws DELTA in [60, 120] (2.4.1.3)."""

import datetime

KIND = "query"
TABLES = ("lineitem",)
INDEXES = ("li_shipdate",)
# The columns Q1 must read once, and its result columns: what the
# least-bytes count of its roofline share is made of.
INPUTS = {
    "lineitem": ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
                 "l_extendedprice", "l_discount", "l_tax"),
}
RESULT = ("l_returnflag", "l_linestatus", "sum_qty", "sum_base_price", "sum_disc_price",
          "sum_charge", "avg_qty", "avg_price", "avg_disc", "count_order")


def draw(rng, spec, keys, domain):
    return {"delta": int(rng.integers(60, 121))}


def cutoff(params) -> str:
    return (datetime.date(1998, 12, 1) - datetime.timedelta(days=params["delta"])).isoformat()


def execute(ctx, params):
    from hyperspace_tpu import AggSpec, col, lit
    from hyperspace_tpu.plan.expr import date_lit

    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    plan = ctx.scans["lineitem"].filter(col("l_shipdate") <= date_lit(cutoff(params))).aggregate(
        ["l_returnflag", "l_linestatus"],
        [
            AggSpec.of("sum", "l_quantity", "sum_qty"),
            AggSpec.of("sum", "l_extendedprice", "sum_base_price"),
            AggSpec.of("sum", disc_price, "sum_disc_price"),
            AggSpec.of("sum", disc_price * (lit(1.0) + col("l_tax")), "sum_charge"),
            AggSpec.of("mean", "l_quantity", "avg_qty"),
            AggSpec.of("mean", "l_extendedprice", "avg_price"),
            AggSpec.of("mean", "l_discount", "avg_disc"),
            AggSpec.of("count", None, "count_order"),
        ],
    ).sort(["l_returnflag", "l_linestatus"])
    return ctx.run(plan)
