"""Published peaks of each chip and the byte counts of the work.

The peaks are copied from the repository's ``benchmarks/bench_venues.py``
table with their source; the benchmark owns this copy. A device kind
missing from the table is an error, never a default.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by JAX's `device_kind`. Source:
# Google Cloud documentation, "TPU v5e" (16 GB HBM at 819 GB/s,
# 197 TFLOP/s bf16, 393 TOP/s int8).
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add them to "
            "perfbench/peaks.py with their source"
        )
    return PEAKS[device_kind]


def least_bytes(inputs: dict, rows: dict, result_columns, result_rows: int,
                column_bytes: dict, result_bytes: int = 8) -> int:
    """Bytes an operator must move at the least: each input array read
    once (`inputs` maps a table to its columns, `rows` a table to its
    row count) and its result written once."""
    read = sum(rows[t] * column_bytes[c] for t, cols in inputs.items() for c in cols)
    return read + result_rows * len(result_columns) * result_bytes


def index_bytes(config: dict, index: str, rows: int) -> int:
    """Bytes of an index's indexed and included columns over `rows` rows."""
    spec = next(i for i in config["indexes"] if i["name"] == index)
    return rows * sum(config["column_bytes"][c] for c in (*spec["indexed"], *spec["included"]))
