"""Host-sharded loader + length bucketing.

Production multi-host JAX training feeds each host its own slice of
the global batch (``jax.process_index()`` selecting the shard); arrays
are then placed with ``jax.device_put`` against the global sharding.
On this single-process container the loader still exercises the same
shard arithmetic (n_shards > 1 with a fixed shard id).
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import jax
import numpy as np


class HostShardedLoader:
    """Wraps a batch iterator factory with host sharding + prefetch.

    ``make_iter(shard, n_shards)`` must return an iterator of dict
    batches whose leading dim is the *per-host* batch.
    """

    def __init__(
        self,
        make_iter: Callable[[int, int], Iterator[Dict[str, np.ndarray]]],
        *,
        shard: int = 0,
        n_shards: int = 1,
        prefetch: int = 2,
    ):
        self.shard = shard
        self.n_shards = n_shards
        self._it = make_iter(shard, n_shards)
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self) -> None:
        try:
            for batch in self._it:
                if self._stop.is_set():
                    return
                self._q.put(batch)
        finally:
            self._q.put(None)

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        # the wait for input, on the profiler's clock when one records
        with jax.profiler.TraceAnnotation("loader.next"):
            item = self._q.get()
        if item is None:
            raise StopIteration
        return item

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


def length_bucket(
    lengths: Sequence[int],
    boundaries: Sequence[int],
) -> List[List[int]]:
    """Group example indices into length buckets (minimizes padding).

    Returns one list of indices per bucket; bucket i holds lengths in
    (boundaries[i-1], boundaries[i]].
    """
    buckets: List[List[int]] = [[] for _ in range(len(boundaries) + 1)]
    for idx, ln in enumerate(lengths):
        placed = False
        for bi, bound in enumerate(boundaries):
            if ln <= bound:
                buckets[bi].append(idx)
                placed = True
                break
        if not placed:
            buckets[-1].append(idx)
    return buckets
