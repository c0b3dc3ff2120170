"""Host-device transfer: reference-table upload, batch upload and the
copy back of the enriched columns, per batch (``ComputingStats.upload_s +
convert_s`` over the window)."""

from bench.layer import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "upload_s", "convert_s")
