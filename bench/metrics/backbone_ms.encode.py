"""Device time an encoded batch in the backbone
(``models/transformer.forward_hidden``)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "backbone")
