"""Kernel radius_join (``kernels/spatial_join``): percent of the chip's
roofline, from the ``%radius_join_pallas`` events in the profiler trace
(Q4's, Q5's and Q7's radius top-k in a fused chain)."""

from bench.layer import roofline


def read(ctx):
    return roofline(ctx, "radius_join")
