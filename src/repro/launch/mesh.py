"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state -- the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, TypeVar

import jax
from jax.sharding import Mesh

_T = TypeVar("_T")


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(dp: int, tp: int, pods: int = 1) -> Mesh:
    """Arbitrary (pod) x data x model mesh (smoke tests use 1x1)."""
    auto = jax.sharding.AxisType.Auto
    if pods > 1:
        return jax.make_mesh((pods, dp, tp), ("pod", "data", "model"),
                             axis_types=(auto,) * 3)
    return jax.make_mesh((dp, tp), ("data", "model"),
                         axis_types=(auto,) * 2)


def mesh_axis_sizes(mesh) -> dict:
    """Axis name -> size; works for Mesh and AbstractMesh."""
    return dict(mesh.shape)


def n_chips(mesh) -> int:
    out = 1
    for s in mesh_axis_sizes(mesh).values():
        out *= s
    return out


def host_shard(items: Sequence[_T], *,
               process_index: Optional[int] = None,
               process_count: Optional[int] = None) -> List[_T]:
    """This host's contiguous shard of ``items`` in a multi-host run.

    Defaults to ``jax.process_index()`` / ``jax.process_count()``;
    pass both explicitly to shard without touching jax device state
    (e.g. in tests, or CPU-only sweep fleets coordinated outside jax).
    Shards are contiguous and cover ``items`` exactly: earlier hosts
    get the extra item when the split is uneven, and a single-process
    run returns the whole list.
    """
    if process_count is None:
        process_count = jax.process_count()
    if process_index is None:
        process_index = jax.process_index()
    if not 0 <= process_index < process_count:
        raise ValueError(
            f"process_index {process_index} outside [0, {process_count})")
    n = len(items)
    base, extra = divmod(n, process_count)
    start = process_index * base + min(process_index, extra)
    stop = start + base + (1 if process_index < extra else 0)
    return list(items[start:stop])
