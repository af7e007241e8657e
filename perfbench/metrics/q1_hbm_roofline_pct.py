"""Share of HBM's roofline reached by TPC-H Q1: the least bytes it must
move (its seven lineitem columns read once plus its result) at the
chip's published HBM bandwidth, over the device time inside the Q1
annotations (profiler trace), in %."""

from perfbench.roofline import hbm_roofline_pct


def read(run):
    return hbm_roofline_pct(run, "tpch_q1")
