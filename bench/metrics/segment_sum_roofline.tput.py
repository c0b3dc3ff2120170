"""Kernel segment_sum (``kernels/segment_reduce``): percent of the
chip's roofline, from its events in the profiler trace."""

from bench.layer import roofline


def read(ctx):
    return roofline(ctx, "segment_sum")
