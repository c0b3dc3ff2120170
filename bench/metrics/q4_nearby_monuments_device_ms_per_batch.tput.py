"""Computing job, fused stage Q4 (nearby monuments: radius top-8 and
count over the monuments): its device time per batch, from the device
operations under its ``jax.named_scope`` in the trace."""

from bench.layer import stage_device_ms_per_batch


def read(ctx):
    return stage_device_ms_per_batch(ctx, "q4_nearby_monuments")
