"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The run marks its measured window with a host span named ``window`` and
the host's work inside it with spans of its own (fetching a batch,
dispatching, waiting for a result, copying results to the host). The
reduction reads, for each device plane:

* the device operations of its ``XLA Ops`` line, clipped to the window;
* busy time: the union of those operations' intervals;
* time per operation name (kernel time is the sum over a kernel's
  events);
* idle time: the window less the busy union, attributed to the host
  span it falls in, or to ``other`` where no span covers it;
* given the compiled modules' maps from instruction to ``op_name``, the
  device time under each of the program's named scopes and the time of
  each host span (``bench.scopes``).

Times are averaged over the devices that ran anything. Reading needs
only ``jax.profiler.ProfileData``; nothing here touches a device.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
WINDOW = "window"
OTHER = "other"


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def complement(busy: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The parts of [lo, hi] that no interval of ``busy`` (merged)
    covers."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_name(text: str) -> str:
    """A device event's name is its HLO instruction's text; keep the
    instruction's name (``%fusion.12 = f32[...] fusion(...)`` ->
    ``fusion.12``). A Pallas kernel's instruction is named after the
    jitted function that calls it (``jvp_jit__forward_call__.1``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n):
    for ev in line.events:
        yield (name(ev.name), float(ev.start_ns),
               float(ev.start_ns + ev.duration_ns))


def host_spans(pd) -> Dict[str, List[Interval]]:
    """Every host event by name (ns), from every host plane's lines."""
    spans: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                spans.setdefault(name, []).append((a, b))
    return spans


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """Device plane name -> its ``XLA Ops`` events (name, start, end)."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in _events(line, op_name)]
        if ops:
            out[plane.name] = ops
    return out


def reduce(pd, labels: Sequence[str],
           modules: Optional[Mapping[str, Mapping[str, str]]] = None
           ) -> Dict:
    """Window, busy time, time per device operation and idle time by
    host span, in seconds, averaged over the devices that ran. With
    ``modules`` (module name -> ``bench.scopes.op_names``' map) also
    ``scope_seconds`` and ``span_seconds``."""
    spans = host_spans(pd)
    if WINDOW not in spans:
        raise ValueError(f"the trace has no host span named {WINDOW!r}")
    lo = min(a for a, _ in spans[WINDOW])
    hi = max(b for _, b in spans[WINDOW])
    labelled = {n: merge((max(a, lo), min(b, hi)) for a, b in spans.get(n, ())
                         if min(b, hi) > max(a, lo))
                for n in labels}
    devices = device_ops(pd)
    if not devices:
        raise ValueError("the trace has no device operations")
    n = len(devices)
    busy, op_s, op_n, idle = 0.0, {}, {}, {}
    for ops in devices.values():
        clipped = [(name, max(a, lo), min(b, hi)) for name, a, b in ops
                   if min(b, hi) > max(a, lo)]
        union = merge((a, b) for _, a, b in clipped)
        busy += length(union)
        for name, a, b in clipped:
            op_s[name] = op_s.get(name, 0.0) + (b - a)
            op_n[name] = op_n.get(name, 0) + 1
        gaps = complement(union, lo, hi)
        left = length(gaps)
        for label, iv in labelled.items():
            t = length(intersect(gaps, iv))
            idle[label] = idle.get(label, 0.0) + t
            left -= t
        idle[OTHER] = idle.get(OTHER, 0.0) + max(left, 0.0)
    ns = 1e-9 / n
    out = {"window_s": (hi - lo) * 1e-9, "busy_s": busy * ns,
           "n_devices": n,
           "op_seconds": {k: v * ns for k, v in op_s.items()},
           "op_counts": {k: v / n for k, v in op_n.items()},
           "idle_seconds": {k: v * ns for k, v in idle.items()}}
    if modules is not None:
        from bench import scopes

        out["scope_seconds"] = scopes.scope_seconds(pd, modules)
        out["span_seconds"] = scopes.span_seconds(pd)
    return out


def kernel_seconds(reduced: Dict, pattern: str) -> float:
    """Summed time of the device operations whose name holds
    ``pattern``."""
    return sum(s for name, s in reduced["op_seconds"].items()
               if pattern in name)


def breakdown(reduced: Dict, top: int = 10) -> Dict:
    """The device operations that took most time and the idle time by
    what the host was doing, largest first."""
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    idle = sorted(((k, v) for k, v in reduced["idle_seconds"].items() if v),
                  key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in idle[:top]]}
