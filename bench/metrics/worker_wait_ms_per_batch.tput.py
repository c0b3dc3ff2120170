"""Feed and holders: the computing worker waiting for a frame from its
holder and blocked pushing an enriched batch downstream, per batch
(``ComputingStats.wait_input_s + wait_output_s`` over the window)."""

from bench.layer import per_batch_ms


def read(ctx):
    if not {"wait_input_s", "wait_output_s"} <= ctx.window.keys():
        return None
    return per_batch_ms(ctx, "wait_input_s", "wait_output_s")
