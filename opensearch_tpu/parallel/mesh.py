"""Device mesh construction for the search engine's parallelism axes.

SURVEY.md §2.5 mapping:
- "data"  axis = shard partitioning (the reference's document-hash sharding,
  OperationRouting) — each mesh slot along "data" owns one index shard's
  segment arrays in its HBM
- "model" axis = intra-shard parallelism (the reference's concurrent segment
  search) — a shard's vector dim / postings space split across chips, partial
  results psum-reduced over ICI

Replication across mesh replicas (the availability axis) and cross-slice DCN
federation (CCS) layer on top of these two compute axes.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def build_mesh(
    n_data: int | None = None,
    n_model: int = 1,
    devices: list | None = None,
) -> Mesh:
    devs = devices if devices is not None else jax.devices()
    if n_data is None:
        n_data = len(devs) // n_model
    if n_data * n_model > len(devs):
        raise ValueError(
            f"mesh {n_data}x{n_model} needs {n_data * n_model} devices, "
            f"have {len(devs)}"
        )
    grid = np.asarray(devs[: n_data * n_model]).reshape(n_data, n_model)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def shard_spec(mesh: Mesh, *axes: str | None) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


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
