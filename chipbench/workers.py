"""One learner per chip: where a cell on several chips keeps its state and
its batches, and the loop that compiles the program's step for that layout.

The layout is the one the program's four-chip bring-up checks
(``chip_smoke.four_chip_programs``): a 1-D mesh ``("data",)`` over the
cell's chips; parameters and optimizer state replicated; the ScaleCom
residues, which hold one row per learner, and every batch split on their
leading worker axis, so that learner ``w`` and its data live on chip ``w``.
The step is the program's own ``build_train_step`` with ``worker_axis`` and
per-worker parameter shardings, which pin the expanded parameters and the
per-worker gradients to the worker axis; the exchange between learners is
what GSPMD makes of the reduce's worker mean.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax

from repro.compat.jax_compat import NamedSharding, P, make_mesh
from repro.core.state import ScaleComState
from repro.training import TrainLoop
from repro.training.train_step import build_train_step

AXIS = "data"


def placement(devices, shapes):
    """(state sharding, batch sharding) of one learner on each of ``devices``,
    for a train state of ``shapes``."""
    mesh = make_mesh((len(devices),), (AXIS,), devices=devices)
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P(AXIS))
    state = dataclasses.replace(
        jax.tree.map(lambda _: whole, shapes),
        sc_state=ScaleComState(
            jax.tree.map(lambda _: split, shapes.sc_state.residues), whole
        ),
    )
    return state, split


@dataclasses.dataclass
class WorkerShardedLoop(TrainLoop):
    """``TrainLoop`` whose steps are compiled for ``state_sharding`` and
    ``batch_sharding`` (``placement``): the same ``step`` and ``compiled``,
    with the worker axis named and the per-worker parameters pinned to it,
    and each step's state returned in the layout it came in, so that no
    step compiles again."""

    state_sharding: Any = None
    batch_sharding: Any = None

    def __post_init__(self):
        per_worker = jax.tree.map(lambda _: self.batch_sharding, self.state_sharding.params)
        whole = NamedSharding(self.batch_sharding.mesh, P())
        for attr, mode in (("_dense", "dense"), ("_compressed", "scalecom")):
            step = build_train_step(
                self.model, self.optimizer, self.schedule, self.sc_cfg, mode=mode,
                n_workers=self.n_workers, worker_axis=self.worker_axis,
                worker_shardings=per_worker, grad_clip=self.grad_clip,
                compute_stats=self.compute_stats, buckets=self.buckets,
            )
            setattr(self, attr, jax.jit(
                step, in_shardings=(self.state_sharding, self.batch_sharding),
                out_shardings=(self.state_sharding, whole), donate_argnums=(0,),
            ))
