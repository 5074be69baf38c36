"""Training cells: the program's jitted contrastive step, as
``repro.launch.train`` builds it, fed through its host loader.

Set-up makes the train state from the seed on the device, builds the
step with the state donated, and drives it through its first steps on
the traffic's first batches (the first call compiles). Those steps are
the ones checked: their losses, the first gradient as the optimizer got
it and the parameters' change after them are read from the state before
the window starts, and the same object runs on into the window. After
the window, with the program's state freed, the plain reference follows
the same first steps from the same seeded weights (``bench.compare``).
Weights, reference and work come from the configuration's backbone
module (``bench/backbones/``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import backbones, compare, reference, traffic
from bench.drivers._common import (Cell, CompileCounter, Outcome, delete,
                                   measure, peak_bytes)

# steps the reference follows, from the first: two, so that the plain
# reference at the cells' batches takes about as long as the window
CHECKED_STEPS = 2


@functools.partial(jax.jit, static_argnames=("b1",))
def _first_grad(mu, *, b1):
    """Leaf norms of the gradient the optimizer got at its first step:
    its first moment then is (1 - b1) times that gradient."""
    return reference.leaf_norms(mu) / (1.0 - b1)


def _place(batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _real_tokens(batch: Dict[str, np.ndarray]) -> int:
    return int(batch["q_mask"].sum() + batch["d_mask"].sum())


@dataclasses.dataclass
class Started:
    step: object               # the jitted, state-donating train step
    state: Dict
    loader: object
    batches: List[Dict]        # host copies of the checked steps' rows
    readings: Dict             # the program's checked readings


def build(cell: Cell):
    """The program's train step, jitted with its state donated."""
    from repro.launch.steps import build_lsr_train_step

    hp = cell.config["train"]
    cfg = backbones.load(cell.config).program_config(cell.config)
    step = build_lsr_train_step(cfg, None, n_micro=1,
                                n_pairs=cell.traffic["pairs"], lr=hp["lr"],
                                total_steps=hp["total_steps"])
    return jax.jit(step, donate_argnums=(0,))


def start(cell: Cell, jitted) -> Started:
    """Set-up: state, loader, and the checked first steps of ``jitted``."""
    from repro.data.loader import HostShardedLoader

    hp, V = cell.config["train"], cell.config["vocab_size"]
    bb = backbones.load(cell.config)
    state = bb.init_state(cell.config, cell.seed)
    loader = HostShardedLoader(
        lambda shard, n_shards: traffic.pair_batches(cell.traffic, V,
                                                     cell.seed))
    batches, losses, grad = [], [], None
    for i in range(CHECKED_STEPS):
        batch = next(loader)
        batches.append(batch)
        state, metrics = jitted(state, _place(batch))
        losses.append(float(metrics["loss"]))
        if grad is None:
            grad = np.asarray(_first_grad(state["opt"]["mu"], b1=hp["b1"]))
    names = reference.leaf_names(state["params"])
    change = bb.change_norms(state["params"], cell.config, cell.seed)
    readings = {"loss": losses, "grad": dict(zip(names, grad.tolist())),
                "change": dict(zip(names, change.tolist()))}
    return Started(jitted, state, loader, batches, readings)


def run(cell: Cell) -> Outcome:
    counter = CompileCounter()
    devices = jax.devices()[:cell.chips]
    bb = backbones.load(cell.config)
    st = start(cell, build(cell))
    tokens, failed, one_batch = [0], [0], {}

    def fetch():
        batch = next(st.loader)
        tokens[0] += _real_tokens(batch)
        one_batch.setdefault("b", batch)
        return _place(batch)

    def dispatch(placed):
        st.state, metrics = st.step(st.state, placed)
        return metrics["loss"]

    def finish(loss):
        if not math.isfinite(float(loss)):
            failed[0] += 1

    setup_s = time.monotonic() - cell.t0

    def modules():
        # every batch has the checked steps' shapes
        return [st.step.lower(st.state, _place(st.batches[0])).compile()
                .as_text()]

    win = measure(cell.seconds, fetch, dispatch, finish,
                  sync_label="sync_loss", traced=cell.trace, counter=counter,
                  modules=modules)
    peak = peak_bytes(devices)
    st.loader.close()
    delete(st.state)
    t_ref = time.monotonic()
    ref = bb.train_readings(cell.config, cell.seed, st.batches)
    print(f"reference: {time.monotonic() - t_ref:.1f} s", file=sys.stderr)
    per_step = bb.step_work(cell.config, one_batch["b"])
    return Outcome(
        attempted=win.steps, failed=failed[0],
        end_to_end={"train_tokens_per_s": tokens[0] / win.seconds,
                    "train_peak_hbm_gib": peak / 2 ** 30,
                    "setup_s": setup_s},
        numbers=compare.train_numbers(st.readings, ref),
        memory_peak_bytes=peak, window_compiles=win.compiles,
        work={**{k: v * win.steps for k, v in per_step.items()},
              "steps": win.steps},
        reduced=win.reduced)
