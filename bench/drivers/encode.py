"""Corpus-encoding cells: ``repro.runtime.serving.make_config_encoder`` on
batches of documents, each batch's sparse representations copied to the
host as an index build takes them.

Set-up makes the weights from the seed on the device and encodes one
batch (which compiles). The window encodes batch after batch. After it,
a sample of the rows the window returned, drawn from the seed, is
compared with the plain reference's encoding of the same documents
(``bench.compare``). Weights, reference and work come from the
configuration's backbone module (``bench/backbones/``).
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import backbones, compare, traffic
from bench.drivers._common import (Cell, CompileCounter, Outcome, delete,
                                   measure, peak_bytes)


@dataclasses.dataclass
class Started:
    encode: object             # (tokens, mask) -> SparseRep
    params: Dict
    loader: object


def start(cell: Cell) -> Started:
    """Set-up: weights, the encoder, the loader, one batch encoded."""
    from repro.data.loader import HostShardedLoader
    from repro.runtime import serving

    V = cell.config["vocab_size"]
    bb = backbones.load(cell.config)
    params = bb.init_params(cell.config, cell.seed)
    encode = serving.make_config_encoder(params,
                                         bb.program_config(cell.config))
    loader = HostShardedLoader(
        lambda shard, n_shards: traffic.doc_batches(cell.traffic, V,
                                                    cell.seed))
    batch = next(loader)
    jax.block_until_ready(encode(jnp.asarray(batch["tokens"]),
                                 jnp.asarray(batch["mask"])))
    return Started(encode, params, loader)


def sample_rows(cell: Cell, n_rows: int) -> np.ndarray:
    """The rows of the window that are compared, drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([cell.seed, 2]))
    k = min(cell.traffic["check_rows"], n_rows)
    return np.sort(rng.choice(n_rows, size=k, replace=False))


@dataclasses.dataclass
class Encoded:
    """Every batch encoded, as the host holds it: the documents and the
    program's reps, one entry a batch."""

    batches: List[Dict] = dataclasses.field(default_factory=list)
    reps: List = dataclasses.field(default_factory=list)

    def steps(self, st: Started):
        """The window's fetch, dispatch and finish: each batch is kept
        and its reps are copied to the host."""
        def fetch():
            batch = next(st.loader)
            self.batches.append(batch)
            return batch

        def dispatch(batch):
            return st.encode(jnp.asarray(batch["tokens"]),
                             jnp.asarray(batch["mask"]))

        def finish(rep):
            self.reps.append(jax.device_get((rep.values, rep.indices)))

        return fetch, dispatch, finish

    def sampled(self, cell: Cell):
        """Tokens, mask, kept weights and kept ids of the compared rows."""
        values = np.concatenate([np.asarray(v, np.float32)
                                 for v, _ in self.reps])
        rows = sample_rows(cell, values.shape[0])
        tokens = np.concatenate([b["tokens"] for b in self.batches])[rows]
        mask = np.concatenate([b["mask"] for b in self.batches])[rows]
        indices = np.concatenate([np.asarray(i) for _, i in self.reps])[rows]
        return tokens, mask, values[rows], indices


def check(cell: Cell, tokens: np.ndarray, mask: np.ndarray,
          values: np.ndarray, indices: np.ndarray) -> Dict[str, float]:
    """The compared numbers for these rows and the program's reps."""
    ref = backbones.load(cell.config).encode_readings(
        cell.config, cell.seed, tokens, mask, indices,
        block=cell.config["reference"]["rows"])
    return compare.encode_numbers(values, ref["at"], ref["values"])


def run(cell: Cell) -> Outcome:
    counter = CompileCounter()
    devices = jax.devices()[:cell.chips]
    st = start(cell)
    enc = Encoded()
    fetch, dispatch, finish = enc.steps(st)
    setup_s = time.monotonic() - cell.t0

    def modules():
        b = enc.batches[-1]
        return [st.encode.func.lower(st.params, jnp.asarray(b["tokens"]),
                                     jnp.asarray(b["mask"]))
                .compile().as_text()]

    win = measure(cell.seconds, fetch, dispatch, finish,
                  sync_label="copy_reps", traced=cell.trace, counter=counter,
                  modules=modules)
    peak = peak_bytes(devices)
    st.loader.close()
    delete(st.params)
    sampled = enc.sampled(cell)
    t_ref = time.monotonic()
    numbers = check(cell, *sampled)
    print(f"reference: {time.monotonic() - t_ref:.1f} s", file=sys.stderr)
    lengths = np.concatenate([b["mask"].sum(axis=1) for b in enc.batches])
    return Outcome(
        attempted=len(lengths),
        failed=sum(int(np.sum(~np.isfinite(np.asarray(v, np.float32))
                              .all(axis=1))) for v, _ in enc.reps),
        end_to_end={"encode_tokens_per_s": float(lengths.sum()) / win.seconds,
                    "setup_s": setup_s},
        numbers=numbers, memory_peak_bytes=peak,
        window_compiles=win.compiles,
        work={**backbones.load(cell.config).encode_work(cell.config,
                                                         enc.batches),
              "steps": win.steps},
        reduced=win.reduced)
