"""Parse: wall time of parse in which the worker's thread was not running
on a CPU, per batch (``ComputingStats.parse_s - parse_cpu_s`` over the
window): parse waiting for the GIL or the scheduler."""

from bench.layer import ms_per


def read(ctx):
    w = ctx.window
    if "parse_cpu_s" not in w:
        return None
    return ms_per(w["parse_s"] - w["parse_cpu_s"], ctx.batches)
