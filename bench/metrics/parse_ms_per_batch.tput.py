"""Parse: host time to turn a frame of JSON lines into columns, per batch
(``ComputingStats.parse_s`` over the window)."""

from bench.layer import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "parse_s")
