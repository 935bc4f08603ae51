"""Production mesh construction.

Target: TPU v5e, 256 chips/pod. Single-pod mesh is (data=16, model=16);
multi-pod adds a leading "pod" axis: (pod=2, data=16, model=16) = 512
chips. A *function* (not a module constant) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def _make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axes: the arena's slot-sharded
    ``.at[].set`` scatters and the shard_map scan entries rely on the
    compiler propagating shardings, which Explicit axes (the default
    of ``jax.make_mesh`` on the JAX this repository runs) refuse for
    scatters with a ``ShardingTypeError``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh on whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    model = min(model, n)
    return _make_mesh((n // model, model), ("data", "model"))


def make_memory_mesh(shards: int = 0):
    """The mesh a sharded ``MemoryArena`` / ``DistributedVenusMemory``
    wants: all ``shards`` devices on the ``model`` axis (the slot/row
    slab axis), data=1. ``shards=0`` means every visible device. Under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` (set BEFORE
    jax initialises — the multi-device CI lane exports it as a job env
    var) this gives K host-platform shards for equivalence testing.
    Asking for more shards than there are devices raises ``ValueError``:
    the arena sized for ``shards`` chips would not fit on fewer."""
    n = len(jax.devices())
    if shards > n:
        raise ValueError(f"{shards} memory shards asked for, but only {n} "
                         f"devices are visible")
    return make_host_mesh(model=n if shards <= 0 else shards)


def data_axes(mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


HARDWARE = {
    "name": "TPU v5e",
    "peak_bf16_flops": 197e12,        # per chip
    "hbm_bw": 819e9,                  # bytes/s per chip
    "ici_bw": 50e9,                   # bytes/s per link (~3 links usable)
    "hbm_bytes": 16e9,
    "chips_per_pod": 256,
}
