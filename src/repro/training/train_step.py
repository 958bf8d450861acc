"""Train step: per-worker gradients + ScaleCom reduce + optimizer, pure GSPMD.

Two compiled variants:

  * **scalecom** — the paper's path. Parameters are broadcast to a leading
    worker axis (``n`` = ScaleCom workers) and the loss is vmapped over it
    (``spmd_axis_name`` shards the axis over the mesh). Because worker i's loss
    touches only ``pex[i]``, the Jacobian is block-diagonal and ``jax.grad``
    yields *unreduced per-worker gradients* — no shard_map, no process groups.
    ``scalecom_reduce`` then performs Algorithm 1, told the worker axis's
    sharding so that each device sums its learner's per-chunk values first;
    the only cross-worker gradient collective in the compiled module is the
    k-element value all-reduce (plus the O(k) leader-index broadcast).

  * **dense** — the uncompressed baseline (and the compression warm-up path):
    plain data-parallel GSPMD, loss over the folded global batch, XLA's own
    dense gradient all-reduce. Also the only option for fsdp-sharded params
    with per-rank workers (DESIGN.md §5).

The worker mesh axis is configurable ("data" single-pod, "pod" for hierarchical
multi-pod ScaleCom where the intra-pod reduction stays dense).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat.jax_compat import NamedSharding, PartitionSpec
from repro.core.scalecom import ScaleComConfig, dense_reduce, scalecom_reduce
from repro.core.state import ScaleComState
from repro.optim.optimizer import Optimizer

Array = jnp.ndarray
Pytree = Any

__all__ = ["TrainState", "build_train_step"]


@dataclasses.dataclass
class TrainState:
    params: Pytree
    opt_state: Pytree
    sc_state: ScaleComState
    step: Array  # int32

    def tree_flatten(self):
        return (self.params, self.opt_state, self.sc_state, self.step), None


jax.tree_util.register_pytree_node(
    TrainState,
    TrainState.tree_flatten,
    lambda aux, ch: TrainState(*ch),
)


def _global_norm(tree: Pytree) -> Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


def build_train_step(
    model,
    optimizer: Optimizer,
    schedule: Callable[[Array], Array],
    sc_cfg: ScaleComConfig,
    *,
    n_workers: int,
    mode: str = "scalecom",  # scalecom | dense
    worker_axis: Optional[str] = None,  # mesh axis for the worker dim (None=CPU tests)
    worker_shardings: Optional[Pytree] = None,  # NamedSharding tree for (n, *param)
    microbatches: int = 1,
    grad_clip: Optional[float] = None,
    compute_stats: bool = False,
    buckets: Any = None,
) -> Callable[[TrainState, Pytree], Tuple[TrainState, Dict[str, Array]]]:
    """Returns train_step(state, batch) -> (state, metrics).

    batch: worker-stacked {"tokens": (n, B, S), ...}.

    buckets selects the launch granularity of the ScaleCom reduce (see
    scalecom_reduce): None/False (the default) is the single-shot launch;
    True, a byte count or a prebuilt bucket tuple buckets it. With bucketing
    on, each bucket's compress + all-reduce is staged in reverse-autodiff
    grad-ready order behind an optimization_barrier token chain, so XLA's
    scheduler can overlap the per-bucket collectives with the rest of
    backward — numerics unchanged.

    worker_shardings pins the expanded params AND the per-worker gradient
    cotangents to (worker_axis, *param_sharding). Without the explicit
    constraint GSPMD can de-shard the backward activations over the worker
    axis (observed: per-layer TP all-reduces at n-times payload).

    microbatches=M splits each worker's batch into M sequential chunks with
    fp32 gradient accumulation — activation peak scales ~1/M, compute and
    communication unchanged (the ScaleCom reduce still happens once per step).
    The accumulation scan is not differentiated through, so no per-step
    residuals are stored.
    """

    def _pin(tree):
        if worker_shardings is None:
            return tree
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            tree,
            worker_shardings,
        )

    def _pin_reduced(tree):
        """Pin the reduced gradient ĝ to the parameter sharding (worker axes
        dropped => replicated across workers). Without this GSPMD may leave
        the k-value mean worker-sharded and then ALL-GATHER the dense scatter
        (observed: 54 GB/step of gathers in the pure-DP lowering vs the
        ~1.5 GB k-value all-reduce this constraint restores)."""
        if worker_shardings is None:
            return tree

        def pin_one(x, s):
            spec = PartitionSpec(*tuple(s.spec)[1:])  # drop worker axis entry
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(s.mesh, spec)
            )

        return jax.tree.map(pin_one, tree, worker_shardings)

    # one learner a chip: the reduce's worker mean takes the per-chunk values
    # each chip holds (core.scalecom._learner_local)
    worker_sharding = None
    if worker_axis is not None and worker_shardings is not None:
        mesh = jax.tree.leaves(worker_shardings)[0].mesh
        worker_sharding = NamedSharding(mesh, PartitionSpec(worker_axis))

    def per_worker_grads(params, batch):
        n = n_workers
        pex = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), params
        )
        pex = _pin(pex)

        def grads_of(mb):
            def total_loss(pex):
                losses, auxs = jax.vmap(
                    model.loss, spmd_axis_name=worker_axis
                )(pex, mb)
                return jnp.sum(losses), auxs

            return jax.value_and_grad(total_loss, has_aux=True)(pex)

        if microbatches == 1:
            (loss_sum, auxs), gpw = grads_of(batch)
            gpw = _pin(gpw)
            return loss_sum / n, auxs, gpw

        M = microbatches
        mbs = jax.tree.map(
            lambda x: x.reshape((n, M, x.shape[1] // M) + x.shape[2:]).swapaxes(0, 1),
            batch,
        )

        def body(acc, mb):
            (loss_sum, auxs), g = grads_of(mb)
            acc = jax.tree.map(
                lambda a, gg: a + gg.astype(jnp.float32), acc, _pin(g)
            )
            return acc, (loss_sum, auxs)

        acc0 = jax.tree.map(
            lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params
        )
        acc0 = _pin(acc0)
        gpw, (losses, auxs) = jax.lax.scan(body, acc0, mbs)
        gpw = jax.tree.map(lambda g: g / M, gpw)
        auxs = jax.tree.map(lambda a: jnp.mean(a, axis=0), auxs)
        return jnp.mean(losses) / n, auxs, gpw

    def dense_grads(params, batch):
        folded = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), batch)
        (loss, auxs), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, folded
        )
        return loss, auxs, grads

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict[str, Array]]:
        # The phase scopes name every op of the step in the compiled module's
        # op_name metadata (fwd_bwd / reduce / optimizer); a device trace is
        # split by phase through them. Trace-time only: no runtime cost.
        if mode == "scalecom":
            with jax.named_scope("fwd_bwd"):
                loss, auxs, gpw = per_worker_grads(state.params, batch)
                auxs = {k: jnp.mean(v) for k, v in auxs.items()}
            with jax.named_scope("reduce"):
                ghat, sc_state, stats = scalecom_reduce(
                    gpw, state.sc_state, sc_cfg, compute_stats=compute_stats,
                    buckets=buckets, worker_sharding=worker_sharding,
                )
                ghat = _pin_reduced(ghat)
        elif mode == "dense":
            with jax.named_scope("fwd_bwd"):
                loss, auxs, grads = dense_grads(state.params, batch)
                auxs = {k: jnp.mean(v) for k, v in auxs.items()}
            ghat = grads
            sc_state = ScaleComState(
                residues=state.sc_state.residues, t=state.sc_state.t + 1
            )
            stats = {}
        else:
            raise ValueError(mode)

        with jax.named_scope("optimizer"):
            gnorm = _global_norm(ghat)
            if grad_clip is not None:
                scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12))
                ghat = jax.tree.map(lambda g: g * scale, ghat)
            lr = schedule(state.step)
            params, opt_state = optimizer.update(
                ghat, state.opt_state, state.params, lr
            )
            step = state.step + 1
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "lr": lr,
            **auxs,
            **stats,
        }
        new_state = TrainState(params, opt_state, sc_state, step)
        return new_state, metrics

    return train_step


def init_train_state(
    model, optimizer: Optimizer, sc_cfg: ScaleComConfig, key, *, n_workers: int
) -> Tuple[TrainState, Pytree]:
    """Initialize params/optimizer/ScaleCom state. Returns (state, logical_axes)."""
    from repro.core.state import init_state as sc_init

    params, axes = model.init(key)
    opt_state = optimizer.init(params)
    sc_state = sc_init(
        params,
        sc_cfg.n_workers(n_workers),
        sc_cfg.residue_dtype,
        sc_cfg.min_size,
        sc_cfg.layout,
    )
    return TrainState(params, opt_state, sc_state, jnp.zeros((), jnp.int32)), axes
