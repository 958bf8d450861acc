"""Training loop: warm-up phase (dense) -> compressed phase, metric logging,
periodic checkpointing, and optional residue-similarity probes.

The warm-up uses a *separately compiled* dense step (the paper trains 1-5 epochs
uncompressed before enabling compression); ScaleCom residues are zero during
warm-up so switching steps is state-compatible by construction.

Logging routes through the ``repro`` telemetry logger (repro.obs.get_logger)
by default — silent unless a consumer attaches a handler
(obs.enable_console_logging, which the launch CLI does), so benches and the
harness importing this loop stay quiet. Pass ``log=print`` for the old
behaviour, or ``log=None`` alongside ``telemetry=`` a TelemetryRun to get
step spans + per-step metric events (including the ``obs/`` tap leaves the
reduce emits under ``ScaleComConfig.telemetry``) without console noise.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import numpy as np

from repro import obs
from repro.core.scalecom import ScaleComConfig
from repro.training.train_step import TrainState, build_train_step

__all__ = ["TrainLoop", "run_training"]

# default-log sentinel: distinguishes "not passed" (route to the telemetry
# logger) from an explicit log=None (fully silent, the historical opt-out)
_LOGGER = object()


@dataclasses.dataclass
class TrainLoop:
    model: Any
    optimizer: Any
    schedule: Callable
    sc_cfg: ScaleComConfig
    n_workers: int
    worker_axis: Optional[str] = None
    grad_clip: Optional[float] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    log_every: int = 10
    compute_stats: bool = False
    # overlap-aware bucketed reduce launch spec (scalecom_reduce buckets=...);
    # None is the unbucketed single-shot launch
    buckets: Any = None

    def __post_init__(self):
        common = dict(
            n_workers=self.n_workers,
            worker_axis=self.worker_axis,
            grad_clip=self.grad_clip,
            compute_stats=self.compute_stats,
            buckets=self.buckets,
        )
        self._dense = jax.jit(
            build_train_step(self.model, self.optimizer, self.schedule,
                             self.sc_cfg, mode="dense", **common),
            donate_argnums=(0,),
        )
        self._compressed = jax.jit(
            build_train_step(self.model, self.optimizer, self.schedule,
                             self.sc_cfg, mode="scalecom", **common),
            donate_argnums=(0,),
        )

    def _program(self, step_idx: int):
        compressed = (
            self.sc_cfg.compressor.name != "none"
            and step_idx >= self.sc_cfg.warmup_steps
        )
        return self._compressed if compressed else self._dense

    def step(self, state: TrainState, batch, step_idx: int):
        # a step marker on the profiler's host plane, on the device trace's
        # clock; one inactive TraceMe when no profiler is open
        with jax.profiler.StepTraceAnnotation("train_step", step_num=step_idx):
            return self._program(step_idx)(state, batch)

    def compiled(self, state: TrainState, batch, step_idx: int) -> jax.stages.Compiled:
        """The compiled program ``step`` runs for these arguments (JAX's
        in-memory cache once ``step`` has run them). Its ``as_text()`` is the
        optimized module whose op_name metadata carries the step's phase
        scopes."""
        return self._program(step_idx).lower(state, batch).compile()


def run_training(
    loop: TrainLoop,
    state: TrainState,
    batches: Iterator[Dict[str, np.ndarray]],
    num_steps: int,
    *,
    log: Any = _LOGGER,
    telemetry: Optional["obs.TelemetryRun"] = None,
) -> tuple[TrainState, List[Dict[str, float]]]:
    """Drive ``num_steps`` through the loop's compiled steps.

    log:       a ``str -> None`` callable for the per-interval step line.
               Default: the ``repro.training`` telemetry logger — a no-op
               unless a handler is attached (obs.enable_console_logging), so
               library consumers are quiet by default. ``None`` silences
               entirely; ``print`` restores the historical console output.
    telemetry: an ``obs.TelemetryRun``: every step gets a span and a
               ``step`` event carrying the full metrics dict (converting the
               metrics is a per-step device sync — the honest cost of
               per-step observability), and every checkpoint save a
               ``checkpoint`` span. The caller closes the run.
    """
    if log is _LOGGER:
        log = obs.get_logger("training").info
    history: List[Dict[str, float]] = []
    t0 = time.time()
    for i, batch in enumerate(batches):
        if i >= num_steps:
            break
        if telemetry is not None:
            with telemetry.step_span(i):
                state, metrics = loop.step(state, batch, i)
                telemetry.record_step(i, {k: float(v) for k, v in metrics.items()})
        else:
            state, metrics = loop.step(state, batch, i)
        if (i % loop.log_every == 0) or i == num_steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = i
            m["wall_s"] = time.time() - t0
            history.append(m)
            if log is not None:
                log(
                    f"step {i:5d}  loss {m['loss']:.4f}  gnorm {m['grad_norm']:.3f}"
                    f"  lr {m['lr']:.2e}"
                )
        if (
            loop.checkpoint_dir
            and loop.checkpoint_every
            and i
            and i % loop.checkpoint_every == 0
        ):
            from repro import checkpoint

            with (
                telemetry.tracer.span("checkpoint")
                if telemetry is not None
                else contextlib.nullcontext()
            ):
                checkpoint.save(loop.checkpoint_dir, i, state)
    return state, history
