"""Model FLOPs utilization of the encoder
(runtime/serving.make_config_encoder)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.mfu(ctx)
