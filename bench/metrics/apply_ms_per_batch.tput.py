"""Computing job, apply: the predeployed enrichment executable, waited
for, per batch (``ComputingStats.apply_s`` over the window)."""

from bench.layer import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "apply_s")
