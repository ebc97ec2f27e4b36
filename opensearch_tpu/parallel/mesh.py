"""Where an index's shards sit on the process's devices.

SURVEY.md §2.5 mapping: the "data" axis is shard partitioning (the
reference's document-hash sharding, OperationRouting) — each mesh slot along
"data" owns one or more index shards' segment arrays in its HBM.
"""

from __future__ import annotations

import jax

DATA_AXIS = "data"


def serving_devices(n_shards: int) -> list:
    """The devices an index of `n_shards` shards is served on: the first w
    of `jax.devices()`, w the largest divisor of the shard count that the
    process has devices for (a [S, ...] stack splits evenly over them)."""
    devs = jax.devices()
    width = next(w for w in range(min(n_shards, len(devs)), 0, -1)
                 if n_shards % w == 0)
    return devs[:width]


def shard_device(shard: int, n_shards: int):
    """The one device that holds shard `shard`: where the serving mesh's
    data axis puts row `shard` of a [S, ...] stack (contiguous blocks of
    S / w shards a device; shard i on device i when S == w), so that a
    shard's segment columns and its slice of the mesh bundle share a chip."""
    devs = serving_devices(n_shards)
    return devs[shard // (n_shards // len(devs))]
