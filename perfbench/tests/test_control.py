"""The control (the reference one precision step down) fails each
cell's check, at a size a test run holds."""

import json
from pathlib import Path

import pytest

from perfbench import control, harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tmp_path, workload):
    cell = harness.load_cell(workload)
    cell.config = {**cell.config, "scale_factor": 0.01}
    tables = {t for m in cell.ops.values() for t in m.TABLES}
    roots, _ = harness.generate(cell.config, tables, tmp_path, seed=2**31 + 5)
    counts = control.control_counts(cell, roots, seed=2**31 + 5, n_ops=40)
    assert any(v > 0 for v in counts.values()), counts


@pytest.mark.parametrize("workload", CELLS)
def test_reference_against_itself_is_correct(tmp_path, workload):
    from perfbench.refs.data import Data

    cell = harness.load_cell(workload)
    cell.config = {**cell.config, "scale_factor": 0.01}
    tables = {t for m in cell.ops.values() for t in m.TABLES}
    roots, _ = harness.generate(cell.config, tables, tmp_path, seed=7)
    data = Data(roots, cell.config)
    from perfbench import generator

    domain = int(cell.config["key_domain"]["rows_per_scale_factor"] * 0.01)
    stream = generator.stream(cell.traffic, cell.ops, domain, 7, "window")
    for _ in range(12):
        name, params = next(stream)
        ref = cell.refs[name]
        want = ref.answer(params, data)
        assert all(v == 0 for v in ref.compare(want, want).values())


def test_refs_import_nothing_of_the_program():
    import ast

    for f in (ROOT / "perfbench" / "refs").glob("*.py"):
        tree = ast.parse(f.read_text())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n.startswith("hyperspace_tpu") for n in names), (f, names)
