"""Computing job, fused stage Q5 (suspicious names: radius top-3 over
the religious buildings, the 16-type radius count over the facilities,
the probe of the 1M-row suspicious names): its device time per batch,
from the device operations under its ``jax.named_scope`` in the trace."""

from bench.layer import stage_device_ms_per_batch


def read(ctx):
    return stage_device_ms_per_batch(ctx, "q5_suspicious_names")
