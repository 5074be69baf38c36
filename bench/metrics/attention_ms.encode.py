"""Device time an encoded batch in self-attention
(``models/attention.attention``: the score, softmax and value product of
every layer after RoPE and before the output projection), scope
``attention``."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "attention")
