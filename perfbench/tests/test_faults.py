"""A run whose timed path is broken underneath comes out not correct:
for each fault a cell can have (one chip: no exchange between chips)."""

import dataclasses

import numpy as np
import pytest

from perfbench.tests.util import run_cell


def _alter(table):
    """One value of the first numeric column changed."""
    for name, col in table.columns.items():
        if col.dtype.kind in "if" and len(col) and name not in table.dictionaries:
            col = col.copy()
            col[0] = col[0] + 1
            return dataclasses.replace(table, columns={**table.columns, name: col},
                                       validity=dict(table.validity))
    raise AssertionError("no numeric column to alter")


def _half(table):
    """The first half of the rows; the rest left out."""
    keep = table.num_rows // 2
    return dataclasses.replace(
        table,
        columns={k: v[:keep] for k, v in table.columns.items()},
        validity={k: v[:keep] for k, v in table.validity.items()},
    )


QUERY_FAULTS = {"answer_altered": _alter, "half_left_out": _half}


@pytest.mark.parametrize("fault", sorted(QUERY_FAULTS))
@pytest.mark.parametrize("workload", ["sf1_lookup", "sf1_join"])
def test_query_fault_is_caught(tmp_path, monkeypatch, workload, fault):
    from hyperspace_tpu.execution.executor import Executor

    execute = Executor.execute
    monkeypatch.setattr(Executor, "execute", lambda self, plan: QUERY_FAULTS[fault](execute(self, plan)))
    res = run_cell(tmp_path, workload)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def _refresh_unchanged(monkeypatch):
    from hyperspace_tpu.hyperspace import Hyperspace

    monkeypatch.setattr(Hyperspace, "refresh_index", lambda self, name, mode="full": None)


def _half_of_the_rows_built(monkeypatch):
    from hyperspace_tpu.execution.builder import DeviceIndexBuilder

    write = DeviceIndexBuilder.write_table
    monkeypatch.setattr(DeviceIndexBuilder, "write_table",
                        lambda self, table, *a, **kw: write(self, _half(table), *a, **kw))


def _key_altered_at_the_write(monkeypatch):
    """One row's key changed after its bucket was assigned."""
    from hyperspace_tpu.execution import io as hio

    carve = hio.carve_and_write

    def altered(dest, table, *a, **kw):
        key = table.columns["l_orderkey"].copy()
        key[0] += 1
        return carve(dest, dataclasses.replace(table, columns={**table.columns, "l_orderkey": key}),
                     *a, **kw)

    monkeypatch.setattr(hio, "carve_and_write", altered)


BUILD_FAULTS = {
    "state_unchanged": (_refresh_unchanged, "refreshes_without_new_version"),
    "answer_altered": (_key_altered_at_the_write, "index_rows_wrong"),
    "half_left_out": (_half_of_the_rows_built, "index_rows_wrong"),
}


@pytest.mark.parametrize("fault", sorted(BUILD_FAULTS))
def test_build_fault_is_caught(tmp_path, monkeypatch, fault):
    plant, number = BUILD_FAULTS[fault]
    plant(monkeypatch)
    res = run_cell(tmp_path, "sf1_build", seconds=3.0)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > 0


def test_alter_changes_exactly_one_value():
    from hyperspace_tpu.execution.table import ColumnTable
    import pyarrow as pa

    t = ColumnTable.from_arrow(pa.table({"a": np.arange(4, dtype=np.int64)}))
    assert np.sum(_alter(t).columns["a"] != t.columns["a"]) == 1
    assert _half(t).num_rows == 2


# Faults that move each of the other numbers compared, so every number
# has a reading above its limit of 0.

def _rules_left_out(monkeypatch):
    from hyperspace_tpu.hyperspace import HyperspaceSession

    monkeypatch.setattr(HyperspaceSession, "optimized_plan", lambda self, plan, snapshot=None: plan)


def _host_venue(monkeypatch):
    from perfbench import harness

    load = harness.load_cell

    def host_filter(name, *a, **kw):
        cell = load(name, *a, **kw)
        cell.config = {**cell.config, "session": {
            **cell.config["session"], "hyperspace.filter.venue": "host"}}
        return cell

    monkeypatch.setattr(harness, "load_cell", host_filter)


def _every_third_query_raises(monkeypatch):
    from hyperspace_tpu.execution.executor import Executor

    execute, calls = Executor.execute, []

    def flaky(self, plan):
        calls.append(1)
        if len(calls) > 40 and len(calls) % 3 == 0:  # after warm-up
            raise RuntimeError("planted failure")
        return execute(self, plan)

    monkeypatch.setattr(Executor, "execute", flaky)


OTHER_QUERY_FAULTS = {
    "rules_left_out": (_rules_left_out, "not_from_index"),
    "host_venue": (_host_venue, "off_device_ops"),
    "query_raises": (_every_third_query_raises, "failed_ops"),
}


@pytest.mark.parametrize("fault", sorted(OTHER_QUERY_FAULTS))
def test_other_query_fault_is_caught(tmp_path, monkeypatch, fault):
    plant, number = OTHER_QUERY_FAULTS[fault]
    plant(monkeypatch)
    res = run_cell(tmp_path, "sf1_lookup")
    assert res["correct"] is False
    assert res["checks"][number]["value"] > 0


def test_unsorted_buckets_are_caught(tmp_path, monkeypatch):
    """The build's permutation with each bucket's rows in reverse."""
    import hyperspace_tpu.ops.bucketize as bz

    perm = bz.bucketize_perm

    def reversed_within_buckets(*a, **kw):
        order, rows = perm(*a, **kw)
        order = np.asarray(order).copy()
        start = 0
        for n in np.asarray(rows):
            order[start:start + n] = order[start:start + n][::-1]
            start += n
        return order, rows

    monkeypatch.setattr(bz, "bucketize_perm", reversed_within_buckets)
    res = run_cell(tmp_path, "sf1_build", seconds=3.0)
    assert res["correct"] is False
    assert res["checks"]["buckets_unsorted"]["value"] > 0
    assert res["checks"]["index_rows_wrong"]["value"] == 0


def test_altered_key_is_misbucketed(tmp_path, monkeypatch):
    _key_altered_at_the_write(monkeypatch)
    res = run_cell(tmp_path, "sf1_build", seconds=3.0)
    # two versions are checked; in each, the row is in a bucket its key
    # does not hash to, and reads as one row missing and one extra
    assert res["checks"]["rows_misbucketed"]["value"] == 2
    assert res["checks"]["index_rows_wrong"]["value"] == 4
