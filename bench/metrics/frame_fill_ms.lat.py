"""Intake: filling one frame, from its first line read to the frame
complete, per frame (``IntakeJob.fill_s / frames_in`` over the window);
under open-loop arrivals, the wait for a batch's tweets to arrive."""

from bench.layer import per_frame_ms


def read(ctx):
    return per_frame_ms(ctx, "intake.fill_s")
