"""Device time an encoded batch in the head's length ordering: the
argsort of row extents and the row gathers (``kernels/ops.py``)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "head_order")
