"""Full refresh of one index over its unchanged source: the whole
build (scan, hash-bucketize, sort, encode, write) through the action
protocol. The answer is the index version directory it left newest."""

KIND = "build"
TABLES = ("lineitem",)
INDEXES = ()


def draw(rng, spec, keys, domain):
    return {"index": spec["index"]}


def setup_indexes(spec):
    """The index the set-up builds for this op to refresh."""
    return (spec["index"],)


def execute(ctx, params):
    with ctx.annotate("run"):
        ctx.hs.refresh_index(params["index"], "full")
    root = ctx.index_root(params["index"])
    return max(root.glob("v__=*"), key=lambda p: int(p.name.split("=")[1]))
