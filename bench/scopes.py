"""Layer time from the program's named scopes and host spans in a
profiler trace.

The program names its layers with ``jax.named_scope`` (``backbone`` in
``models/transformer.forward_hidden``, ``optimizer`` in
``launch/steps.build_lsr_train_step``, ``sparsify`` in
``core/head_api.make_encoder``, ``head_order`` in ``kernels/ops.py``,
and whatever scope a later change adds). A scope reaches the compiled
module as the ``op_name`` metadata of each instruction
(``jit(step)/transpose(jvp(backbone))/while``), and the device trace
names each op by its instruction (``bench.trace.op_name``). So the map
from instruction to ``op_name`` that ``op_names`` parses out of the
module's text (``Compiled.as_text()``) attributes each device op to its
scopes: every component of its ``op_name`` but the last, which is the
operation itself (so the jitted function's own name and those of the
functions it calls count as scopes too). Instruction names such as
``fusion.12`` repeat across modules: an op is looked up in the module
whose event on the device's ``XLA Modules`` line contains it.

A scope's time is the union of its ops' intervals, clipped to the
window, so that a ``while`` and the ops of its body count once; a host
span's time (``loader.next``, the program's wait for input in
``data/loader.HostShardedLoader``, or any other) is the union of its
intervals in the window. Device times are averaged over the devices that
ran, as in ``bench.trace.reduce``, which calls both.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Mapping, Tuple

from bench import trace

MODULES_LINE = "XLA Modules"

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """The module's name and ``{instruction name: op_name}`` for every
    instruction of a compiled module's text that carries an
    ``op_name``."""
    module = _MODULE.search(hlo_text)
    if module is None:
        raise ValueError("no HloModule line in the text")
    out = {}
    for line in hlo_text.splitlines():
        inst = _INSTRUCTION.match(line)
        meta = inst and _OP_NAME.search(line)
        if meta:
            out[inst.group(1)] = meta.group(1)
    return module.group(1), out


def scopes_of(op_name: str) -> List[str]:
    """The ``/``-separated components of an ``op_name`` with transform
    wrappers taken off: ``transpose(jvp(backbone))`` -> ``backbone``."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(part)) is not None:
            part = m.group(1)
        out.append(part)
    return out


def _module_name(event_name: str) -> str:
    """A module's name from its trace event, ``jit_step(8412...)``."""
    return event_name.split("(", 1)[0]


def _clip(intervals: Iterable[trace.Interval], lo: float, hi: float
          ) -> List[trace.Interval]:
    return trace.merge((max(a, lo), min(b, hi)) for a, b in intervals
                       if min(b, hi) > max(a, lo))


def _window(pd) -> trace.Interval:
    """The run's ``window`` host span (ns)."""
    spans = trace.host_spans(pd).get(trace.WINDOW)
    if not spans:
        raise ValueError(f"the trace has no host span named "
                         f"{trace.WINDOW!r}")
    return min(a for a, _ in spans), max(b for _, b in spans)


def _device_lines(pd):
    """(XLA Ops events, XLA Modules events) of each device that ran."""
    for plane in pd.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace.OPS_LINE not in lines:
            continue
        ops = list(trace._events(lines[trace.OPS_LINE], trace.op_name))
        mods = sorted((trace._events(lines[MODULES_LINE], _module_name)
                       if MODULES_LINE in lines else ()),
                      key=lambda ev: ev[1])
        if ops:
            yield ops, mods


def scope_seconds(pd, modules: Mapping[str, Mapping[str, str]]
                  ) -> Dict[str, float]:
    """Seconds of device time in the window under each scope the ops'
    ``op_name``s carry, averaged over the devices that ran. ``modules``
    maps a module name to its ``op_names``; ops of other modules belong
    to no scope."""
    lo, hi = _window(pd)
    total: Dict[str, float] = {}
    of: Dict[Tuple[str, str], frozenset] = {}
    n = 0
    for ops, mods in _device_lines(pd):
        n += 1
        starts = [a for _, a, _ in mods]
        per: Dict[str, list] = {}
        for name, a, b in ops:
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a > mods[i][2]:
                continue
            key = (mods[i][0], name)
            if key not in of:
                op = modules.get(key[0], {}).get(name)
                of[key] = frozenset(scopes_of(op)[:-1] if op else ())
            for s in of[key]:
                per.setdefault(s, []).append((a, b))
        for s, iv in per.items():
            total[s] = total.get(s, 0.0) + trace.length(_clip(iv, lo, hi))
    return {s: v * 1e-9 / max(n, 1) for s, v in total.items()}


def span_seconds(pd) -> Dict[str, float]:
    """Seconds of each host span in the window (the union of its
    intervals), for every span name the trace holds."""
    lo, hi = _window(pd)
    return {n: trace.length(_clip(iv, lo, hi)) * 1e-9
            for n, iv in trace.host_spans(pd).items()}
