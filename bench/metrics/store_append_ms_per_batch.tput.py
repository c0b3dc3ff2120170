"""Store: the column-store write of one enriched batch
(``StorageJob.write_s`` over the window, per store write)."""


def read(ctx):
    n = ctx.window["store_batches"]
    if n <= 0:
        return None
    return 1000.0 * ctx.window["store_write_s"] / n
