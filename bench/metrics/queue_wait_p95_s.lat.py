"""Feed and holders: 95th percentile of how long a frame waited in its
partition holder before a worker pulled it (the program's
``holder_backlog_age_s`` histogram)."""


def read(ctx):
    return ctx.queue_wait_p95_s
