"""Model FLOPs utilization of the training step (launch/steps.py)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.mfu(ctx)
