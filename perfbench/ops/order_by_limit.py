"""ORDER BY l_extendedprice DESC, l_orderkey LIMIT 10 over lineitem.
No index covers an ordering, so the rules leave it on the source scan;
the top-k select runs on the device."""

KIND = "query"
TABLES = ("lineitem",)
INDEXES = ()
COLUMNS = ("l_extendedprice", "l_orderkey", "l_partkey")


def draw(rng, spec, keys, domain):
    return {"limit": 10}


def execute(ctx, params):
    plan = ctx.scans["lineitem"].select(*COLUMNS).sort(
        [("l_extendedprice", False), ("l_orderkey", True)]
    ).limit(params["limit"])
    return ctx.run(plan)
