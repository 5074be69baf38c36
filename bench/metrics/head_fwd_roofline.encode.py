"""Roofline share of the Sparton head's forward kernel in corpus encoding."""

from bench.metrics import _shared


def read(ctx):
    return _shared.roofline(ctx, {_shared.FWD_KERNEL: "head_fwd"})
