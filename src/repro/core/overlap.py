"""Overlap-aware bucket scheduler — hide the compressed all-reduce behind
backward compute.

``scalecom_reduce`` historically compressed the whole gradient tree in one
shot after backward completed, so the k-value all-reduce sat on the critical
path even at 65-400X compression — exactly the failure mode Agarwal et al.
2021 measure (compression schemes lose most of their modeled gain when
overlap is ignored) and the reason DGC pipelines local accumulation with
backprop. This module is the *launch* stage that fixes it:

  plan      core.plan.plan_buckets packs TensorPlans into size-targeted
            buckets (ScaleComConfig.bucket_bytes, default 25 MB — DDP's
            bucket_cap_mb heritage) in reverse-autodiff grad-ready order.
  schedule  (this module) — per bucket, in grad-ready order: stage the
            bucket's gradient leaves, run compress + all-reduce for exactly
            those tensors, then fence a scalar token on the bucket's outputs.
            The token chain gives XLA two guarantees it can schedule around:

              * each bucket's collective subgraph depends ONLY on that
                bucket's gradients (not the whole tree), so the latency-
                hiding scheduler may issue bucket 0's all-reduce while
                earlier layers are still in backward;
              * bucket i+1's inputs are staged behind bucket i's outputs, so
                collectives issue in the SAME order on every rank (the
                classic deadlock-avoidance requirement for bucketed
                collectives) instead of wherever the scheduler felt like.

Both staging points are ``jax.lax.optimization_barrier`` — a value-level
identity — so the bucketed reduce is BITWISE identical to the unbucketed
path: same per-tensor plans, same EF residues, only launch granularity
changes (asserted over 20-step trajectories by tests/test_overlap.py). With
``overlap=False`` the scheduler emits the same per-bucket trace with no
ordering hints.

Resolution mirrors layout/backend: ``resolve_bucket_bytes`` probes the
``SCALECOM_BUCKET_MB`` env var at call time (the CI leg that runs tier-1
through the bucketed pipeline), and explicit specs always win.
``analysis.perfmodel.overlap_report`` models the resulting timeline
(per-bucket compress/comm occupancy vs backward compute) and reports the
hidden fraction; benchmarks/bench_overlap.py sweeps it.
"""

from __future__ import annotations

import os
from typing import Any, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.compat import jax_compat
from repro.core.plan import Bucket, plan_buckets
from repro.obs import taps

Array = jnp.ndarray

__all__ = [
    "BUCKET_ENV",
    "resolve_bucket_bytes",
    "resolve_buckets",
    "init_token",
    "stage_bucket",
    "fence_bucket",
]

BUCKET_ENV = "SCALECOM_BUCKET_MB"


def resolve_bucket_bytes(
    spec: Any = None, default_bytes: int = 25 << 20
) -> Optional[int]:
    """Resolve a bucketing spec to a bucket byte target (None = unbucketed).

    spec:
      None | "auto"  probe $SCALECOM_BUCKET_MB at call time (compat-layer
                     style, like SCALECOM_LAYOUT / SCALECOM_BACKEND): unset
                     or <= 0 disables bucketing, otherwise the value is the
                     bucket size in MB.
      False          force the unbucketed single-shot path.
      True           bucketed at ``default_bytes`` (ScaleComConfig.bucket_bytes).
      int/float > 0  explicit bucket size in BYTES.

    Explicit specs always win over the env var.
    """
    if spec is False:
        return None
    if spec is True:
        return int(default_bytes)
    if spec is None or spec == "auto":
        env = os.environ.get(BUCKET_ENV, "").strip()
        if not env:
            return None
        try:
            mb = float(env)
        except ValueError:
            raise ValueError(
                f"invalid ${BUCKET_ENV}={env!r}: expected a bucket size in MB "
                f"(a number; values <= 0 disable bucketing)"
            ) from None
        return int(mb * (1 << 20)) if mb > 0 else None
    if isinstance(spec, (int, float)):
        if spec <= 0:
            raise ValueError(
                f"explicit bucket size must be positive bytes, got {spec!r} "
                f"(use buckets=False to disable bucketing)"
            )
        return int(spec)
    raise TypeError(
        f"buckets spec must be None/'auto', bool, a byte count, or a tuple "
        f"of core.plan.Bucket; got {type(spec).__name__}"
    )


def resolve_buckets(spec: Any, cfg, plans) -> Optional[Tuple[Bucket, ...]]:
    """Resolve ``scalecom_reduce(..., buckets=...)`` to a bucket schedule.

    A pre-built tuple/list of Buckets passes through verbatim (tests, custom
    packers); everything else goes through ``resolve_bucket_bytes`` +
    ``plan_buckets``. Returns None for the unbucketed single-shot path.
    """
    if isinstance(spec, (tuple, list)) and spec and all(
        isinstance(b, Bucket) for b in spec
    ):
        return tuple(spec)
    bucket_bytes = resolve_bucket_bytes(spec, cfg.bucket_bytes)
    if bucket_bytes is None:
        return None
    return plan_buckets(plans, bucket_bytes)


# ---------------------------------------------------------------------------
# the token chain
# ---------------------------------------------------------------------------


def init_token() -> Array:
    """The scalar scheduling token threaded through the bucket chain."""
    return jnp.zeros((), jnp.float32)


def stage_bucket(
    leaves: Sequence[Array], token: Array, *, overlap: bool = True,
    bucket: Optional[int] = None,
) -> Tuple[List[Array], Array]:
    """Stage one bucket's gradient leaves behind the scheduler token.

    The barrier ties the staged leaves to ``token`` (= the previous bucket's
    fence), so this bucket's compress + all-reduce cannot be hoisted ahead of
    the previous bucket's collective. Identity on values. With
    ``overlap=False`` the leaves pass through untouched.

    ``bucket`` is the schedule index for the telemetry tap (a static count of
    staged leaves per bucket, repro.obs.taps — a trace-time no-op unless a
    telemetry collector is open); it never affects the staged values.
    """
    if bucket is not None:
        taps.tap(
            "bucket_staged_leaves",
            jnp.asarray(len(leaves), jnp.float32),
            bucket=bucket,
            overlap=overlap,
        )
    if not overlap:
        return list(leaves), token
    staged, token = jax_compat.optimization_barrier((tuple(leaves), token))
    return list(staged), token


def fence_bucket(
    outputs: Sequence[Array], token: Array, *, overlap: bool = True
) -> Array:
    """Advance the token past one bucket's outputs.

    The returned token depends on every output of the bucket (the barrier
    takes the whole tuple), while the outputs themselves are returned to the
    caller UN-barriered — the optimizer never serializes behind the token
    chain, only the next bucket's launch does.
    """
    if not overlap:
        return token
    _, token = jax_compat.optimization_barrier((tuple(outputs), token))
    return token
