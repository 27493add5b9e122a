"""Device, HFL cells: share of the traced window in which no operation
ran on the chip (profiler trace), in percent."""
from bench.metrics._idle import idle_share


def read(ctx):
    return idle_share(ctx, "hfl")
