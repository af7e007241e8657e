"""Point lookup: the lines of one order, three covered columns."""

KIND = "query"
TABLES = ("lineitem",)
INDEXES = ("li_orderkey",)
COLUMNS = ("l_orderkey", "l_partkey", "l_extendedprice")


def draw(rng, spec, keys, domain):
    return {"key": int(keys.keys(rng, 1)[0])}


def execute(ctx, params):
    from hyperspace_tpu import col

    plan = ctx.scans["lineitem"].filter(col("l_orderkey") == params["key"]).select(*COLUMNS)
    return ctx.run(plan)
