"""Arithmetic the per-layer metric readers (``bench/metrics/``) share.
Each returns None where the run gives it nothing to read."""

from __future__ import annotations

from typing import Optional

from bench import peaks


def per_batch_ms(ctx, *counters: str) -> Optional[float]:
    """Milliseconds per computing batch of the summed program counters,
    over the window's batches."""
    total = sum(ctx.window[c] for c in counters)
    if ctx.batches <= 0 or total <= 0:
        return None
    return 1000.0 * total / ctx.batches


def idle_share(ctx) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device."""
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def roofline(ctx, kernel: str) -> Optional[float]:
    """Percent of the chip's roofline for the kernel's events in the
    trace: the bytes of their operands and results at the call's shapes
    over peak bandwidth, against their device time.  A join or a
    group-by needs no operation that every implementation must do beyond
    reading its input, so the bound is the bytes."""
    if ctx.trace is None:
        return None
    k = ctx.trace["kernels"].get(kernel)
    if not k or k["time_ns"] <= 0 or k["bytes"] <= 0:
        return None
    return peaks.roofline_share(k["time_ns"] / 1e9, k["bytes"], 0.0,
                                ctx.device_kind)
