"""Row-sharded embedding tables (DLRM model parallelism) via shard_map.

A 40M-row x 128-dim table cannot be replicated per chip; the classic
DLRM answer is to shard table *rows* across devices and resolve
lookups with a mask-and-reduce: every device gathers the indices that
fall inside its row range (clipped gather on its local shard) and the
partial results are ``psum``-combined. No table is ever all-gathered,
and the collective payload is only ``(batch, dim)`` per table.

This mirrors the Sparton head's vocabulary sharding (DESIGN.md §3):
the heavy dimension lives sharded, and only the reduced output crosses
the interconnect.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def sharded_lookup_local(
    local_table: Array,    # (rows_local, dim) — this device's row shard
    idx: Array,            # (batch,) global row ids (replicated)
    *,
    axis_name: str,
) -> Array:
    """Inside-shard_map body: masked local gather + psum."""
    rows_local = local_table.shape[0]
    shard = jax.lax.axis_index(axis_name)
    lo = shard * rows_local
    local_idx = idx - lo
    in_range = (local_idx >= 0) & (local_idx < rows_local)
    safe = jnp.clip(local_idx, 0, rows_local - 1)
    out = jnp.take(local_table, safe, axis=0)
    out = jnp.where(in_range[:, None], out, 0.0)
    return jax.lax.psum(out, axis_name)


def make_sharded_lookup(mesh: Mesh, axis_name: str = "model"):
    """Returns lookup(table, idx) with the table row-sharded on `axis_name`.

    The table must be padded so rows % axis_size == 0 (see
    ``pad_table_rows``).
    """

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name, None), P()),
        out_specs=P(),
    )
    def lookup(table: Array, idx: Array) -> Array:
        return sharded_lookup_local(table, idx, axis_name=axis_name)

    return lookup


def pad_table_rows(rows: int, n_shards: int) -> int:
    return rows + ((-rows) % n_shards)


def table_sharding(mesh: Mesh, axis_name: str = "model") -> NamedSharding:
    return NamedSharding(mesh, P(axis_name, None))


def init_tables(
    key: jax.Array, table_sizes: Sequence[int], dim: int,
    n_shards: int = 1, dtype=jnp.float32,
):
    """One (padded_rows, dim) array per table; rows padded for sharding."""
    keys = jax.random.split(key, len(table_sizes))
    return [
        jax.random.normal(k, (pad_table_rows(r, n_shards), dim), dtype)
        * (dim ** -0.5)
        for k, r in zip(keys, table_sizes)
    ]
