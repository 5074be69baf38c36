"""Device time a training step in the optimizer's update
(``launch/steps.build_lsr_train_step``)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.scope_ms(ctx, "optimizer")
