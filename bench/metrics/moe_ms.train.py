"""Device time a training step in the MoE FFN (``models/moe``: router,
dispatch, held and shared experts, combine, forward and backward),
scope ``moe``."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "moe")
