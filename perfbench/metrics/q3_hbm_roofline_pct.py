"""Share of HBM's roofline reached by TPC-H Q3: the least bytes its
join-aggregate must move (both sides' inputs read once plus the top 10)
at the chip's published HBM bandwidth, over the device time inside the
Q3 annotations (profiler trace), in %."""

from perfbench.roofline import hbm_roofline_pct


def read(run):
    return hbm_roofline_pct(run, "tpch_q3")
