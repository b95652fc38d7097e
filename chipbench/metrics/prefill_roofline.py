"""Least time for the traced window's prefill calls (chipbench/work.py, at
each chunk's real item count; the larger of the FLOP and the byte bound)
over the device time of the prefill program in the profiler trace."""
from chipbench.harness import roofline


def read(run):
    return roofline(run, "prefill")
