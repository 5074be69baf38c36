"""Device time a training step in multi-head latent attention
(``models/attention.mla_attention``: latent projections, the latent's
norm, RoPE, attention and the output projection, forward and
backward), scope ``mla``."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "mla")
