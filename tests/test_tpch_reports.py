"""TPC-H Q1, Q3, Q6 and Q12 with qgen parameters through the normal path
(HyperspaceSession with Hyperspace enabled, rules, executor, ops) at a
small scale, against the plain references of the benchmark's
``sf1_reports`` cell (perfbench/refs/tpch_q*.py)."""

import json
from pathlib import Path

import numpy as np
import pytest

from hyperspace_tpu import stats
from perfbench import harness
from perfbench.refs.data import Data

SF = 0.02
QUERIES = ("tpch_q1", "tpch_q3", "tpch_q6", "tpch_q12")
SEEDS = (2**31 + 17, 41)


@pytest.fixture(scope="module")
def reports(tmp_path_factory):
    """(cell, ctx, data roots) with the cell's three indexes built over
    generated SF 0.02 tables."""
    import jax

    cell = harness.load_cell("sf1_reports")
    cell.config = {**cell.config, "scale_factor": SF}
    work = tmp_path_factory.mktemp("tpch_reports")
    tables = {t for m in cell.ops.values() for t in m.TABLES}
    roots, _ = harness.generate(cell.config, tables, work, seed=7)
    session, hs = harness.make_session(cell.config, work / "indexes", jax.devices(), 1)
    scans = {t: session.parquet(r) for t, r in roots.items()}
    ctx = harness.Ctx(session, hs, scans, work / "indexes", trace=False)
    names = {i for m in cell.ops.values() for i in m.INDEXES}
    harness.build_indexes(ctx, cell.config, names, roots)
    session.enable_hyperspace()
    return cell, ctx, roots


def draw(cell, name: str, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    domain = int(cell.config["key_domain"]["rows_per_scale_factor"] * SF)
    return cell.ops[name].draw(rng, {"op": name, "count": 1}, None, domain)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", QUERIES)
def test_query_matches_reference_from_its_indexes(reports, name, seed):
    cell, ctx, roots = reports
    params = draw(cell, name, seed)
    op = harness.execute(ctx, cell, name, params, 0)
    assert op.error is None, op.error
    assert set(op.evidence["indexes"]) == set(cell.ops[name].INDEXES)
    assert harness.off_device(op.evidence["profile"].to_json()) == []
    ref = cell.refs[name]
    want = ref.answer(params, Data(roots, cell.config))
    assert ref.compare(harness.answer_columns(op.answer), want) == {"wrong_answers": 0}


@pytest.mark.parametrize("name", ("tpch_q1", "tpch_q3", "tpch_q6"))
def test_float32_control_fails_the_tolerance(reports, name):
    cell, _ctx, roots = reports
    params = draw(cell, name, SEEDS[0])
    ref = cell.refs[name]
    got = ref.answer(params, Data(roots, cell.config, control=True))
    want = ref.answer(params, Data(roots, cell.config))
    assert ref.compare(got, want) == {"wrong_answers": 1}


def _spans(node, name):
    if not node:
        return []
    own = [node] if node.get("name") == name else []
    return own + [s for c in node.get("children", []) for s in _spans(c, name)]


def test_q1_profile_shows_the_aggregate_spans_and_path_counter(reports):
    cell, ctx, _roots = reports
    paths = ("lax", "sharded")
    before = {p: stats.get(f"device.kernel.segment_reduce_{p}") for p in paths}
    built = ("device", "host")
    built_before = {b: stats.get(f"device.kernel.agg_channels_{b}") for b in built}
    op = harness.execute(ctx, cell, "tpch_q1", draw(cell, "tpch_q1", SEEDS[1]), 0)
    trace = op.evidence["profile"].to_json()["trace"]
    # Q1's eight AggSpecs all build their channels in the device program:
    # six channels from four staged columns.
    assert {b: stats.get(f"device.kernel.agg_channels_{b}") - built_before[b] for b in built} == {
        "device": 8, "host": 0,
    }
    assert [s["attrs"]["channels"] for s in _spans(trace, "agg.channels")] == [6]
    reduces = _spans(trace, "agg.reduce")
    assert len(reduces) == 1
    path = reduces[0]["attrs"]["path"]
    after = {p: stats.get(f"device.kernel.segment_reduce_{p}") for p in paths}
    assert {p: after[p] - before[p] for p in paths} == {p: int(p == path) for p in paths}


def test_cell_reads_only_what_its_indexes_cover():
    """Each query's columns lie in the indexes its op declares, so the
    rewrite can serve it from them alone."""
    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "perfbench/configs/tpch_reports_sf1.json").read_text())
    cols = {i["name"]: {*i["indexed"], *i["included"]} for i in cfg["indexes"]}
    cell = harness.load_cell("sf1_reports")
    for name in QUERIES:
        covered = set().union(*(cols[i] for i in cell.ops[name].INDEXES))
        for table_cols in getattr(cell.ops[name], "INPUTS", {}).values():
            assert set(table_cols) <= covered, name
