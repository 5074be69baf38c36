"""Roofline share of the MoE FFN in training: the least time its work
(the backbone module's ``step_work``, key ``moe``) would take at the
chip's peak, over the device time in scope ``moe``."""

from bench.metrics import _shared


def read(ctx):
    seconds = ctx["reduced"].get("scope_seconds", {}).get("moe")
    if not seconds or "moe" not in ctx["work"]:
        return None
    return 100.0 * ctx["work"]["moe"].min_seconds(ctx["peaks"]) / seconds
