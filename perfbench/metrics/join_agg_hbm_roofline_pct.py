"""Share of HBM's roofline reached by the Aggregate(Join) queries: the
least bytes each must move (its join-key and channel input arrays read
once plus its result, perfbench/peaks.py) at the chip's published HBM
bandwidth, over the device time inside those queries' annotations
(profiler trace), in %."""

from perfbench.peaks import least_bytes, peaks

OP = "join_aggregate"


def read(run):
    if run.trace is None or OP not in run.cell.ops:
        return None
    device_s = sum(o["device_s"] for o in run.trace["ops"] if o["op"] == OP)
    n = sum(1 for o in run.trace["ops"] if o["op"] == OP)
    if n == 0 or device_s <= 0:
        return None
    mod = run.cell.ops[OP]
    groups = {len(op.answer[mod.RESULT[0]]) for op in run.ops if op.name == OP}
    nbytes = least_bytes(mod.INPUTS, run.rows, mod.RESULT, max(groups),
                         run.cell.config["column_bytes"])
    least_s = n * nbytes / peaks(run.device_kind)["hbm_bytes_per_s"]
    return least_s / device_s * 100.0
