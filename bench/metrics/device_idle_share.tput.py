"""Device: percent of the traced part of the window with no operation on
the chip (profiler trace)."""

from bench.layer import idle_share


def read(ctx):
    return idle_share(ctx)
