"""TPC-H Q12, the shipping modes and order priority report (clause
2.4.12): lines of two ship modes received within one year, late against
their commit date but shipped before it, joined to their orders and
counted by mode into high and low order priorities. qgen draws two
distinct modes of the seven and DATE as January 1 of a year in [1993,
1997] (2.4.12.3)."""

KIND = "query"
TABLES = ("lineitem", "orders")
INDEXES = ("li_orderkey_ship", "o_orderkey_prio")
# The arrays Q12's join-aggregate must read once, by side, and its
# result columns.
INPUTS = {
    "orders": ("o_orderkey", "o_orderpriority"),
    "lineitem": ("l_orderkey", "l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"),
}
RESULT = ("l_shipmode", "high_line_count", "low_line_count")
MODES = ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK")
HIGH = ("1-URGENT", "2-HIGH")


def draw(rng, spec, keys, domain):
    pick = rng.choice(len(MODES), size=2, replace=False)
    return {"modes": [MODES[int(i)] for i in pick], "year": int(rng.integers(1993, 1998))}


def execute(ctx, params):
    from hyperspace_tpu import AggSpec, col, when
    from hyperspace_tpu.plan.expr import date_lit

    y = params["year"]
    high = col("o_orderpriority").isin(list(HIGH))
    plan = ctx.scans["orders"].select("o_orderkey", "o_orderpriority").join(
        ctx.scans["lineitem"].select(
            "l_orderkey", "l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"
        ),
        ["o_orderkey"], ["l_orderkey"],
    ).filter(
        col("l_shipmode").isin(list(params["modes"]))
        & (col("l_commitdate") < col("l_receiptdate"))
        & (col("l_shipdate") < col("l_commitdate"))
        & (col("l_receiptdate") >= date_lit(f"{y}-01-01"))
        & (col("l_receiptdate") < date_lit(f"{y + 1}-01-01"))
    ).aggregate(
        ["l_shipmode"],
        [
            AggSpec.of("sum", when(high, 1.0).otherwise(0.0), "high_line_count"),
            AggSpec.of("sum", when(high, 0.0).otherwise(1.0), "low_line_count"),
        ],
    ).sort(["l_shipmode"])
    return ctx.run(plan)
