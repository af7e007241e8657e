"""TPC-H Q3, the shipping priority report (clause 2.4.3): orders placed
before DATE with lines shipped after it, grouped by order with their
revenue, the ten largest first. qgen draws DATE in [1995-03-01,
1995-03-31] (2.4.3.3). The c_mktsegment predicate is dropped (there is
no customer table), and the group key o_orderkey equals l_orderkey
under the join."""

import datetime

KIND = "query"
TABLES = ("lineitem", "orders")
INDEXES = ("li_orderkey_ship", "o_orderkey_prio")
# The arrays the join-aggregate must read once, by side, and the
# query's result columns: what the least-bytes count of its roofline
# share is made of.
INPUTS = {
    "orders": ("o_orderkey", "o_orderdate", "o_shippriority"),
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"),
}
RESULT = ("o_orderkey", "revenue", "o_orderdate", "o_shippriority")


def draw(rng, spec, keys, domain):
    day = datetime.date(1995, 3, 1) + datetime.timedelta(days=int(rng.integers(0, 31)))
    return {"date": day.isoformat(), "limit": 10}


def execute(ctx, params):
    from hyperspace_tpu import AggSpec, col, lit
    from hyperspace_tpu.plan.expr import date_lit

    day = date_lit(params["date"])
    orders = ctx.scans["orders"].select("o_orderkey", "o_orderdate", "o_shippriority").filter(
        col("o_orderdate") < day
    )
    lines = ctx.scans["lineitem"].select(
        "l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"
    ).filter(col("l_shipdate") > day)
    revenue = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    plan = orders.join(lines, ["o_orderkey"], ["l_orderkey"]).aggregate(
        ["o_orderkey", "o_orderdate", "o_shippriority"], [AggSpec.of("sum", revenue, "revenue")]
    ).sort([("revenue", False), ("o_orderdate", True)]).limit(params["limit"])
    return ctx.run(plan)
