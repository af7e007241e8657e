"""TPC-H Q6, the forecasting revenue change report (clause 2.4.6): one
year of shipments with a discount within a cent of DISCOUNT and a
quantity under QUANTITY, summing extendedprice * discount. qgen draws
DATE as January 1 of a year in [1993, 1997], DISCOUNT in [0.02, 0.09]
and QUANTITY in [24, 25] (2.4.6.3)."""

KIND = "query"
TABLES = ("lineitem",)
INDEXES = ("li_shipdate",)
# The columns Q6 must read once, and its result columns.
INPUTS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")}
RESULT = ("revenue",)


def draw(rng, spec, keys, domain):
    cents = int(rng.integers(2, 10))
    return {
        "year": int(rng.integers(1993, 1998)),
        "discount": cents / 100,
        # The bounds in cents, as the data holds its discounts.
        "lo": (cents - 1) / 100,
        "hi": (cents + 1) / 100,
        "quantity": int(rng.integers(24, 26)),
    }


def execute(ctx, params):
    from hyperspace_tpu import AggSpec, col, lit
    from hyperspace_tpu.plan.expr import date_lit

    y = params["year"]
    pred = (
        (col("l_shipdate") >= date_lit(f"{y}-01-01"))
        & (col("l_shipdate") < date_lit(f"{y + 1}-01-01"))
        & (col("l_discount") >= lit(params["lo"]))
        & (col("l_discount") <= lit(params["hi"]))
        & (col("l_quantity") < lit(float(params["quantity"])))
    )
    plan = ctx.scans["lineitem"].filter(pred).aggregate(
        [], [AggSpec.of("sum", col("l_extendedprice") * col("l_discount"), "revenue")]
    )
    return ctx.run(plan)
