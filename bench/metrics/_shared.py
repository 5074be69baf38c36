"""Arithmetic the per-layer readers share. A reader takes ``ctx``:
``reduced`` (``bench.trace.reduce`` of the traced window, with the
program's ``scope_seconds`` and ``span_seconds``), ``work`` (the
driver's counts for that window, ``steps`` among them: the steps or
batches it ran), ``peaks`` (the chip's row of ``bench/peaks.json``) and
``chips``. It returns None where the trace holds nothing to read."""

from bench import trace

# The trace names each Pallas kernel's instruction after the jitted
# function in kernels/sparton*.py that calls it.
FWD_KERNEL = "_forward_call"
DH_KERNEL = "_dh_call"
DE_KERNEL = "_de_call"


def roofline(ctx, kernels):
    """Share (%) of the kernels' time that the work they need would take
    at the chip's peak: ``kernels`` maps a kernel name to its work key."""
    seconds = sum(trace.kernel_seconds(ctx["reduced"], k) for k in kernels)
    if seconds <= 0:
        return None
    least = sum(ctx["work"][w].min_seconds(ctx["peaks"])
                for w in kernels.values())
    return 100.0 * least / seconds


def mfu(ctx):
    """Model FLOPs of the window over the window, as a share (%) of the
    chips' bf16 peak."""
    window = ctx["reduced"]["window_s"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["work"]["model_flops"] / window / peak


def idle(ctx):
    """Share (%) of the window in which no operation ran on the device."""
    red = ctx["reduced"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def _per_step_ms(ctx, seconds):
    if not seconds:
        return None
    return 1000.0 * seconds / ctx["work"]["steps"]


def scope_ms(ctx, scope):
    """Device time (ms) a step or batch under the program's named
    ``scope`` (``bench.scopes``); None where the scope reads 0."""
    return _per_step_ms(ctx, ctx["reduced"].get("scope_seconds", {})
                        .get(scope))


def span_ms(ctx, span):
    """Time (ms) a step or batch in the program's host ``span``; None
    where it reads 0."""
    return _per_step_ms(ctx, ctx["reduced"].get("span_seconds", {})
                        .get(span))
