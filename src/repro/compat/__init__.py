"""Version-portability shims. ``jax_compat`` is the only place in the repo
allowed to reference version-gated JAX symbols (scalecheck's compat-boundary
rule)."""

from repro.compat import jax_compat
from repro.compat.jax_compat import (
    axis_size,
    Mesh,
    NamedSharding,
    P,
    PartitionSpec,
    make_mesh,
    set_mesh,
    shard_map,
)

__all__ = [
    "jax_compat",
    "axis_size",
    "Mesh",
    "NamedSharding",
    "P",
    "PartitionSpec",
    "make_mesh",
    "set_mesh",
    "shard_map",
]
