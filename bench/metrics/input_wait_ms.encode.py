"""Host time an encoded batch waiting for input
(``data/loader.HostShardedLoader``, span ``loader.next``)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.span_ms(ctx, "loader.next")
