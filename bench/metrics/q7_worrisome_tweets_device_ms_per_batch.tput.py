"""Computing job, fused stage Q7 (worrisome tweets: radius top-3 over
the religious buildings, attacks per religion in a two-month window):
its device time per batch, from the device operations under its
``jax.named_scope`` in the trace."""

from bench.layer import stage_device_ms_per_batch


def read(ctx):
    return stage_device_ms_per_batch(ctx, "q7_worrisome_tweets")
