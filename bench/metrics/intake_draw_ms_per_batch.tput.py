"""Intake: drawing one frame from the socket adapter, first read to frame
handed over, per frame (``IntakeJob.draw_s / frames_in`` over the window;
a frame is one batch)."""

from bench.layer import per_frame_ms


def read(ctx):
    return per_frame_ms(ctx, "intake.draw_s")
