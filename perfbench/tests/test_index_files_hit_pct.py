"""The reader of ``index_files_hit_pct`` on synthetic query profiles."""

import types

import pytest

from perfbench.harness import load_module


def _query(*cached):
    kids = [{"name": "plan.index_files", "wall_s": 0.001,
             **({"attrs": {"index": "i", "cached": c}} if c is not None else {})}
            for c in cached]
    opt = {"name": "plan.optimize", "wall_s": 0.002, "children": kids}
    return types.SimpleNamespace(kind="query", evidence={"profile": {
        "trace": {"name": "query", "wall_s": 1.0, "children": [opt]}}})


@pytest.mark.parametrize("ops, want", [
    ([_query(True, True), _query(False)], 200 / 3),
    ([_query(True), _query()], 100.0),
    ([_query(None, None)], None),  # a program whose span has no such attribute
    ([], None),
])
def test_share_of_cached_spans(ops, want):
    got = load_module("metrics", "index_files_hit_pct").read(types.SimpleNamespace(ops=ops))
    assert got == (pytest.approx(want) if want is not None else None)
