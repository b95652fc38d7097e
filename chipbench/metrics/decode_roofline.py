"""Least time for the traced window's decode calls (chipbench/work.py;
these are bound by the bytes of the weights each call reads) over the
device time of the decode program in the profiler trace."""
from chipbench.harness import roofline


def read(run):
    return roofline(run, "decode")
