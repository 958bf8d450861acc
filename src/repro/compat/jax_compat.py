"""The single import point for JAX's mesh, collective and float8 API.

The repo targets the one JAX it is installed with (0.9). Every other module
reaches these symbols through this module and nowhere else (scalecheck's
``compat-boundary`` rule enforces it), so a JAX upgrade that moves one of them
is a one-file change:

  * ``make_mesh`` — ``jax.make_mesh`` with every axis ``AxisType.Auto`` (the
    reduce path relies on GSPMD-inferred shardings, never on Explicit-mode
    sharding-in-types);
  * ``set_mesh`` / ``shard_map`` / ``axis_size`` — ``jax.set_mesh``,
    ``jax.shard_map``, ``jax.lax.axis_size``;
  * ``optimization_barrier`` — the scheduling fence of core.overlap;
  * ``float8_e4m3_dtype`` / ``cast_to_e4m3`` — the e4m3 residue storage of
    the fp8 codecs (core.state).

Stable sharding symbols (Mesh / NamedSharding / PartitionSpec) are re-exported
so the rest of the repo has one canonical import point for sharding API.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

__all__ = [
    "Mesh",
    "NamedSharding",
    "PartitionSpec",
    "P",
    "make_mesh",
    "set_mesh",
    "shard_map",
    "axis_size",
    "pallas_available",
    "optimization_barrier",
    "float8_e4m3_dtype",
    "cast_to_e4m3",
    "describe",
]


def make_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    *,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD-inferred)."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape),
        axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def set_mesh(mesh: Mesh):
    """Context manager activating ``mesh`` for jit/sharding resolution."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def axis_size(axis_name: str):
    return jax.lax.axis_size(axis_name)


def pallas_available() -> bool:
    """Call-time probe: does this jax ship the pallas package?

    The import probe lives here behind the compat boundary; the kernel
    registry (repro.backends.base) consumes the verdict, never the import.
    """
    try:
        from jax.experimental import pallas  # noqa: F401
    except Exception:
        return False
    return True


def optimization_barrier(tree):
    """``jax.lax.optimization_barrier``: a value-level identity that forbids
    XLA from reordering or eliminating computation across it."""
    return jax.lax.optimization_barrier(tree)


def float8_e4m3_dtype():
    """The e4m3 residue storage dtype."""
    return jnp.float8_e4m3fn


def cast_to_e4m3(x):
    """Round ``x`` onto the e4m3 grid (nearest, ties to even)."""
    return x.astype(jnp.float8_e4m3fn)


def describe() -> str:
    """One-line runtime summary for launcher logs."""
    devs = jax.devices()
    return (
        f"jax {jax.__version__} | {devs[0].platform} {devs[0].device_kind} "
        f"x{len(devs)}"
    )
