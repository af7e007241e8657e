"""Aggregate(Join): orders joined to lineitem on the order key, grouped
by ``l_quantity`` with a count and two integral sums (the fused
join-aggregate with the run-bounds kernel)."""

KIND = "query"
TABLES = ("lineitem", "orders")
INDEXES = ("li_orderkey", "o_orderkey")
# The arrays the join-aggregate must read once, by side, and its result
# columns: what the least-bytes count of its roofline share is made of.
INPUTS = {
    "orders": ("o_orderkey", "o_custkey"),
    "lineitem": ("l_orderkey", "l_partkey", "l_quantity"),
}
RESULT = ("l_quantity", "n", "s_cust", "s_part")


def draw(rng, spec, keys, domain):
    return {}


def execute(ctx, params):
    from hyperspace_tpu import AggSpec

    plan = ctx.scans["orders"].select("o_orderkey", "o_custkey", "o_totalprice").join(
        ctx.scans["lineitem"].select("l_orderkey", "l_partkey", "l_quantity"),
        ["o_orderkey"], ["l_orderkey"],
    ).aggregate(["l_quantity"], [
        AggSpec.of("count", None, "n"),
        AggSpec.of("sum", "o_custkey", "s_cust"),
        AggSpec.of("sum", "l_partkey", "s_part"),
    ])
    return ctx.run(plan)
