"""Device time an encoded batch in the rep sparsifier
(``core/head_api.make_encoder``)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "sparsify")
