"""An operation's share of HBM's roofline, for the readers of
``<op>_hbm_roofline_pct`` metrics."""

from perfbench.peaks import least_bytes, peaks


def hbm_roofline_pct(run, op_name: str):
    """The least bytes the `op_name` operations of a traced window must
    move (``ops/<op>.py``'s ``INPUTS`` read once plus its ``RESULT``,
    perfbench/peaks.py) at the chip's published HBM bandwidth, over the
    device time inside their annotations, in %; None without a trace or
    such an operation."""
    if run.trace is None or op_name not in run.cell.ops:
        return None
    spans = [o for o in run.trace["ops"] if o["op"] == op_name]
    device_s = sum(o["device_s"] for o in spans)
    if not spans or device_s <= 0:
        return None
    mod = run.cell.ops[op_name]
    rows = max(len(op.answer[mod.RESULT[0]]) for op in run.ops if op.name == op_name)
    nbytes = least_bytes(mod.INPUTS, run.rows, mod.RESULT, rows, run.cell.config["column_bytes"])
    return len(spans) * nbytes / peaks(run.device_kind)["hbm_bytes_per_s"] / device_s * 100.0
