"""Share of the encoding window in which the device ran nothing."""

from bench.metrics import _shared


def read(ctx):
    return _shared.idle(ctx)
