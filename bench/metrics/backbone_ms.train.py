"""Device time a training step in the backbone
(``models/transformer.forward_hidden``, forward and backward)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "backbone")
