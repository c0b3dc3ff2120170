"""Computing job: parse, transfers, state build and apply of one batch
(``ComputingStats`` over the window, per batch)."""

from bench.layer import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "parse_s", "upload_s", "convert_s", "state_s",
                        "apply_s")
