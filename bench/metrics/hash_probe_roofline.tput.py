"""Kernel hash_probe (``kernels/hash_probe``): percent of the chip's
roofline, from its events in the profiler trace."""

from bench.layer import roofline


def read(ctx):
    return roofline(ctx, "hash_probe")
