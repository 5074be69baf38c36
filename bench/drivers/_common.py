"""What the drivers share: the measured window, its optional trace, and
the device's peak memory. (What belongs to one architecture, the
drivers take from its module under ``bench/backbones/``.)"""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time
from typing import Callable, Dict, Optional, Sequence

from bench import scopes
from bench import trace as tr


@dataclasses.dataclass
class Cell:
    """One run of one cell, as ``run.py`` resolved it."""

    name: str
    config: Dict
    traffic: Dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    t0: float                 # process start, time.monotonic()
    limits: Dict[str, float]


@dataclasses.dataclass
class Outcome:
    """What a driver measured; ``run.py`` turns it into the result."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    numbers: Dict[str, float]
    memory_peak_bytes: int
    window_compiles: int
    work: Dict = dataclasses.field(default_factory=dict)
    reduced: Optional[Dict] = None


def span(name: str, on: bool):
    """A host span in the profiler's trace, or nothing when untraced."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts the compilations (and compile-cache reads) JAX reports."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1


@dataclasses.dataclass
class Window:
    steps: int
    seconds: float
    compiles: int
    reduced: Optional[Dict]


def measure(seconds: float, fetch: Callable[[], object],
            dispatch: Callable[[object], object],
            finish: Callable[[object], None], *, sync_label: str,
            traced: bool, counter: CompileCounter,
            modules: Optional[Callable[[], Sequence[str]]] = None
            ) -> Window:
    """Run the window: fetch, dispatch, then finish the previous call, so
    one call is in flight while the host waits; stop once ``seconds``
    have passed and the last call has finished. With ``traced`` the
    profiler records the window and the trace is reduced; ``modules``
    then gives the text of the compiled modules the window ran (from
    jit's cache, after the window), whose ``op_name``s put the device's
    ops under the program's scopes."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        if traced:
            jax.profiler.start_trace(tmp)
        before = counter.count
        with span(tr.WINDOW, traced):
            t_start = time.monotonic()
            steps, prev = 0, None
            while True:
                with span("fetch_batch", traced):
                    item = fetch()
                with span("dispatch", traced):
                    cur = dispatch(item)
                if prev is not None:
                    with span(sync_label, traced):
                        finish(prev)
                steps, prev = steps + 1, cur
                if time.monotonic() - t_start >= seconds:
                    break
            with span(sync_label, traced):
                finish(prev)
            elapsed = time.monotonic() - t_start
        compiles = counter.count - before
        reduced = None
        if traced:
            jax.profiler.stop_trace()
            pd = tr.load(tr.find_xplane(tmp))
            names = (dict(scopes.op_names(t) for t in modules())
                     if modules is not None else None)
            reduced = tr.reduce(pd, ("fetch_batch", "dispatch", sync_label),
                                names)
        return Window(steps, elapsed, compiles, reduced)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def peak_bytes(devices: Sequence) -> int:
    """Peak bytes on the fullest device: the runtime allocator's peak of
    buffers in use (state, batches, results) plus its peak of memory
    reserved for the programs' temporaries, which a TPU counts apart."""
    stats = [d.memory_stats() or {} for d in devices]
    return int(max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0) for s in stats))


def delete(tree) -> None:
    """Free the device buffers of a pytree now."""
    import jax

    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()
