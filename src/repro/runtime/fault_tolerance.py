"""Fault-tolerant training runtime: checkpoint/restart, stragglers,
elastic re-mesh.

Designed for 1000+ nodes where *something* is always failing:

* **Checkpoint/restart** — async atomic checkpoints every N steps
  (repro.checkpoint); on start the runner auto-resumes from the latest
  valid step. A SIGTERM-style shutdown hook flushes a final
  checkpoint.
* **Failed steps** — an exception from a step is treated as a lost
  device: the step is skipped, like a straggler. The exception is that
  of the first step a run attempts, which is where the step compiles:
  it is raised, so a kernel that cannot compile stops the run.
* **Straggler mitigation** — every step runs under a deadline
  (EWMA of recent step times × slack). A step exceeding the deadline
  is retried once; a second miss marks the step skipped (the grad
  accumulation window renormalizes — see optim.accumulation) and the
  host is recorded as suspect. Persistent suspects trigger a re-mesh.
* **Elastic re-mesh** — on device loss (or operator resize request),
  ``ElasticMeshManager`` rebuilds the mesh at the largest supported
  (pod, data, model) factorization of the surviving device count,
  re-places the *host-side* checkpoint against the new sharding (pure
  pytree: no device-order assumptions), and re-jits the step.

The runner is deliberately engine-agnostic: it owns *policy* (when to
checkpoint / retry / re-mesh) and delegates *mechanism* to injected
callables, so unit tests drive it with toy steps and fault injectors.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.checkpoint.store import (AsyncCheckpointer, latest_step,
                                    load_checkpoint)

PyTree = Any


@dataclasses.dataclass
class StragglerPolicy:
    slack: float = 3.0           # deadline = slack * EWMA step time
    ewma_alpha: float = 0.1
    min_deadline_s: float = 1.0
    max_retries: int = 1
    suspect_threshold: int = 3   # suspect marks before demanding re-mesh


@dataclasses.dataclass
class RunnerConfig:
    ckpt_dir: str
    ckpt_every: int = 100
    keep_ckpts: int = 3
    max_steps: int = 1000
    straggler: StragglerPolicy = dataclasses.field(
        default_factory=StragglerPolicy)
    log_every: int = 10


class ElasticMeshManager:
    """Owns mesh (re)construction under changing device counts.

    ``factorize(n)`` picks the largest (pod, data, model) with
    pod*data*model == usable <= n, preferring to keep the model axis
    (resharding E is cheaper than re-tuning per-device batch) and
    power-of-two axes.
    """

    def __init__(self, make_mesh: Callable[[Tuple[int, ...]], Any],
                 *, model_axis: int = 16):
        self.make_mesh = make_mesh
        self.model_axis = model_axis

    def factorize(self, n_devices: int) -> Tuple[int, int, int]:
        model = self.model_axis
        while model > 1 and n_devices < model:
            model //= 2
        rest = n_devices // model
        # largest power of two <= rest for the data axis
        data = 1 << (max(rest, 1).bit_length() - 1)
        pod = 1  # pods collapse into data when devices are lost
        return (pod, data, model)

    def build(self, n_devices: int):
        shape = self.factorize(n_devices)
        return self.make_mesh(shape), shape


class _StepClock:
    def __init__(self, policy: StragglerPolicy):
        self.policy = policy
        self.ewma: Optional[float] = None

    def deadline(self) -> float:
        if self.ewma is None:
            return float("inf")  # first step: no baseline yet
        return max(self.policy.min_deadline_s,
                   self.policy.slack * self.ewma)

    def record(self, dt: float) -> None:
        a = self.policy.ewma_alpha
        self.ewma = dt if self.ewma is None else (1 - a) * self.ewma + a * dt


class FaultTolerantRunner:
    """Drives (state, batch) -> (state, metrics) steps with FT policy.

    Parameters
    ----------
    step_fn: the jitted train step.
    state: initial train state pytree (params, opt state, step).
    batches: iterator of host batches.
    place_batch: host batch -> device arrays (applies shardings).
    config: RunnerConfig.
    on_remesh: optional callback(state) -> (step_fn, state) invoked when
      the straggler policy demands a re-mesh (tests inject this;
      launch/train.py wires it to ElasticMeshManager + re-jit).
    on_step: optional callback(step, state) invoked after every
      *successful* step (skipped/straggled steps don't fire it) — the
      periodic-work hook (eval, extra logging). A returned non-empty
      dict is appended to ``metrics_log`` as its own
      ``{"step": step, **extras}`` entry; the callback decides its own
      cadence. Exceptions propagate: the hook runs host-side work the
      caller asked for, not step execution the FT policy owns.
    """

    def __init__(
        self,
        step_fn: Callable[[PyTree, PyTree], Tuple[PyTree, Dict[str, Any]]],
        state: PyTree,
        batches,
        *,
        config: RunnerConfig,
        place_batch: Callable[[Dict[str, np.ndarray]], PyTree] = lambda b: b,
        on_remesh: Optional[Callable[[PyTree],
                                     Tuple[Callable, PyTree]]] = None,
        on_step: Optional[Callable[[int, PyTree],
                                   Optional[Dict[str, Any]]]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.step_fn = step_fn
        self.state = state
        self.batches = batches
        self.config = config
        self.place_batch = place_batch
        self.on_remesh = on_remesh
        self.on_step = on_step
        self.clock = clock
        self._step_clock = _StepClock(config.straggler)
        self._ckpt = AsyncCheckpointer(config.ckpt_dir,
                                       keep=config.keep_ckpts)
        self.start_step = 0
        self._attempted = False
        self.suspect_strikes = 0
        self.skipped_steps: List[int] = []
        self.remesh_events: List[int] = []
        self.metrics_log: List[Dict[str, Any]] = []

    # -- resume ----------------------------------------------------------
    def try_resume(self) -> bool:
        step = latest_step(self.config.ckpt_dir)
        if step is None:
            return False
        self.state, self.start_step = load_checkpoint(
            self.config.ckpt_dir, self.state)
        return True

    # -- main loop --------------------------------------------------------
    def run(self) -> PyTree:
        cfg = self.config
        step = self.start_step
        while step < cfg.max_steps:
            batch = next(self.batches)
            placed = self.place_batch(batch)
            ok, metrics = self._attempt_step(placed, step)
            if not ok:
                self.skipped_steps.append(step)
                self.suspect_strikes += 1
                if (self.suspect_strikes
                        >= cfg.straggler.suspect_threshold
                        and self.on_remesh is not None):
                    self.step_fn, self.state = self.on_remesh(self.state)
                    self.remesh_events.append(step)
                    self.suspect_strikes = 0
                step += 1
                continue
            self.suspect_strikes = 0
            if cfg.log_every and step % cfg.log_every == 0:
                self.metrics_log.append({"step": step, **metrics})
            if self.on_step is not None:
                extras = self.on_step(step, self.state)
                if extras:
                    self.metrics_log.append({"step": step, **extras})
            step += 1
            if cfg.ckpt_every and step % cfg.ckpt_every == 0:
                self._ckpt.save(step, self.state)
        self._ckpt.save(cfg.max_steps, self.state)
        self._ckpt.close()
        return self.state

    def _attempt_step(self, placed_batch, step: int
                      ) -> Tuple[bool, Dict[str, Any]]:
        deadline = self._step_clock.deadline()
        first = not self._attempted
        self._attempted = True
        for _ in range(1 + self.config.straggler.max_retries):
            t0 = self.clock()
            try:
                new_state, metrics = self.step_fn(self.state, placed_batch)
                new_state = jax.block_until_ready(new_state)
            except Exception as e:  # device loss surfaces as XlaRuntimeError
                # The first step compiles; a lost device and a compile
                # error are both RuntimeErrors, so a failure there is
                # raised rather than skipped.
                if first:
                    raise
                return False, {"error": repr(e)}
            dt = self.clock() - t0
            if dt <= deadline:
                self._step_clock.record(dt)
                self.state = new_state
                m = dict(metrics)
                m["step_time_s"] = dt
                return True, m
            # straggler: discard result, retry once with fresh deadline
        return False, {"straggler": True, "deadline_s": deadline}
