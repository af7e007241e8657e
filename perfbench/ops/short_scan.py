"""Short scan: a fixed-length order-key range with a residual
``l_quantity`` conjunct, as YCSB-E's fixed scan length."""

KIND = "query"
TABLES = ("lineitem",)
INDEXES = ("li_orderkey",)
COLUMNS = ("l_orderkey", "l_partkey", "l_quantity")


def draw(rng, spec, keys, domain):
    span = int(spec["orders"])
    return {"lo": int(rng.integers(0, domain - span + 1)), "span": span, "qty_max": spec["qty_max"]}


def execute(ctx, params):
    from hyperspace_tpu import col, lit

    lo, hi = params["lo"], params["lo"] + params["span"]
    pred = (
        (col("l_orderkey") >= lit(lo)) & (col("l_orderkey") < lit(hi))
        & (col("l_quantity") <= lit(params["qty_max"]))
    )
    return ctx.run(ctx.scans["lineitem"].filter(pred).select(*COLUMNS))
