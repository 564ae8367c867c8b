"""Device mesh construction (reference analogue: cluster topology).

The reference scales by adding stateless CNs and shipping operator subtrees
over morpc (`pkg/sql/compile/remoterun.go:86`); the TPU-native equivalent is
a `jax.sharding.Mesh` whose axes carry the same roles:

  axis "shard"  — data placement: table rows / index vectors partitioned
                  across devices (reference: pkg/shardservice + ParallelRun
                  DOP splitting, compile/scope.go:504)

Collectives over ICI replace the shuffle/dispatch/merge operator trio
(`colexec/{shuffle,dispatch,merge}`).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = "shard") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def shard_rows(mesh: Mesh, arr, axis_name: str = "shard"):
    """Place a [n, ...] array row-sharded over the mesh."""
    spec = P(axis_name, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def replicate(mesh: Mesh, arr):
    return jax.device_put(arr, NamedSharding(mesh, P()))
