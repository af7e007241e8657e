"""Peaks and byte counts, checked by hand at a tiny size."""

import pytest

from perfbench import harness, peaks


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_join_aggregate_least_bytes_at_a_tiny_size():
    mod = harness.load_module("ops", "join_aggregate")
    widths = {c: 8 for cols in mod.INPUTS.values() for c in cols}
    # 3 orders x 2 arrays + 7 lines x 3 arrays, read once; 2 groups x 4 columns written
    got = peaks.least_bytes(mod.INPUTS, {"orders": 3, "lineitem": 7}, mod.RESULT, 2, widths)
    assert got == (3 * 2 + 7 * 3) * 8 + 2 * 4 * 8


def test_index_bytes_counts_indexed_and_included_columns():
    cell = harness.load_cell("sf1_build")
    assert peaks.index_bytes(cell.config, "li_orderkey", 10) == 10 * 5 * 8
