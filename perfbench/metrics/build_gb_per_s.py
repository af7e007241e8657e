"""Bytes of the indexed and included columns of every completed
rebuild (rows x column widths, perfbench/peaks.py) over the window's
seconds, in GB/s on one chip (host clock)."""

from perfbench.peaks import index_bytes
from perfbench.spans import builds


def read(run):
    done = builds(run)
    if not done:
        return None
    config = run.cell.config
    total = 0
    for op in done:
        spec = next(i for i in config["indexes"] if i["name"] == op.params["index"])
        total += index_bytes(config, spec["name"], run.rows[spec["table"]])
    return total / run.window_s / 1e9
