"""Device time a training step in self-attention
(``models/attention.attention``: the score, softmax and value product of
every layer after RoPE and before the output projection, the fused
kernel's forward and backward calls or ``chunked_attention``), scope
``attention``."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "attention")
