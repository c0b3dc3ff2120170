"""Computing job, state build: Model 2 rebuilds each stateful UDF's
state from the reference tables every batch (``ComputingStats.state_s``
over the window, per batch).  Nothing to read for a stateless plan."""

from bench.layer import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "state_s")
