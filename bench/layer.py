"""Arithmetic the per-layer metric readers (``bench/metrics/``) share.
Each returns None where the run gives it nothing to read."""

from __future__ import annotations

from typing import Optional

from bench import peaks

# the program's span around one run of the enrichment program
EXECUTE = "compute.execute"


def ms_per(total_s: float, n: float) -> Optional[float]:
    """Milliseconds per item of ``total_s`` seconds over ``n`` items; None
    where either is nothing."""
    if n <= 0 or total_s <= 0:
        return None
    return 1000.0 * total_s / n


def per_batch_ms(ctx, *counters: str) -> Optional[float]:
    """Milliseconds per computing batch of the summed program counters,
    over the window's batches."""
    return ms_per(sum(ctx.window[c] for c in counters), ctx.batches)


def per_frame_ms(ctx, *counters: str) -> Optional[float]:
    """Milliseconds per frame the intake drew (``intake.frames_in``) of
    the summed program counters, over the window; None where the program
    keeps one of them not."""
    names = counters + ("intake.frames_in",)
    if any(c not in ctx.window for c in names):
        return None
    return ms_per(sum(ctx.window[c] for c in counters),
                  ctx.window["intake.frames_in"])


def stage_device_ms_per_batch(ctx, stage: str) -> Optional[float]:
    """Device milliseconds per batch of one stage of a fused chain: the
    time of the device operations under the stage's ``jax.named_scope``
    in the traced part, over the executions of the enrichment program
    (the ``compute.execute`` spans) there.  None for a plan with no such
    stage, or where nothing was traced."""
    if ctx.trace is None:
        return None
    scope = ctx.trace["scopes"].get(stage)
    runs = ctx.trace["spans"].get(EXECUTE, 0)
    if not scope or runs <= 0:
        return None
    return ms_per(scope["time_ns"] / 1e9, runs)


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
