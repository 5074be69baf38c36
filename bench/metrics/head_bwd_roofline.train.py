"""Roofline share of the Sparton head's backward kernels (dH and dE)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.roofline(ctx, {_shared.DH_KERNEL: "head_dh",
                                  _shared.DE_KERNEL: "head_de"})
