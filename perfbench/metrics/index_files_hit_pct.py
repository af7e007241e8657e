"""Share of the window's ``plan.index_files`` spans whose ``cached``
attribute is true (every version directory's listing came from the
program's listing cache), in %. None where no span carries the
attribute."""

from perfbench.spans import queries


def read(run):
    hits = total = 0
    for op in queries(run):
        stack = [op.evidence["profile"].get("trace")]
        while stack:
            node = stack.pop()
            if not node:
                continue
            cached = (node.get("attrs") or {}).get("cached")
            if node.get("name") == "plan.index_files" and cached is not None:
                total += 1
                hits += bool(cached)
            stack.extend(node.get("children", []))
    return hits / total * 100.0 if total else None
