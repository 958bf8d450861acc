"""Kernel-backend protocol and registry — the dispatch layer of the hot path.

ScaleCom's per-step compute cost is the chunk-wise selection + error-feedback
update (paper Table 1: ~3 FLOPs/element for the compressor; the EF residue is
the largest state in the system). ``scalecom_reduce`` routes every chunked
operation through a ``KernelBackend`` so the same algorithm runs on the
pure-jnp oracles (CPU, any-device correctness path) or the Pallas TPU kernels
(fused, autotuned — see benchmarks/bench_kernels.py for the measured sweep),
selected per run by ``resolve_backend``.

Protocol
--------
ONE trailing-axis op set. A backend implements three *primitive* ops;
everything else has a default composition in this base class:

  select_indices(x, chunk, topm)        -> per-chunk magnitude top-m offsets
  gather(x, idx, chunk, topm)           -> values at per-chunk offsets
  scatter(vals, idx, chunk, size, topm) -> dense array from (offset, value)

All ops chunk the LAST axis of an arbitrarily-batched array, so every shape
the reduce dispatches is one call (and, on the Pallas backend, one kernel
launch): a flat 1-D buffer, a worker-stacked (n_workers, size) tensor, and a
layout-preserving (n_workers, *param_shape) tensor are the same op — flat is
the degenerate single-row case of the trailing-axis form
((G, size) ≡ (G, 1, size)). Callers never vmap a backend op, and there are no
per-layout op variants: a feature implemented against this surface lands in
both layouts at once. Backends handle trailing-axis padding internally (zero
padding is select-safe — core.chunked.pad_to_chunks).

Derived ops that backends override for fusion:

  select(x, chunk, topm)                  -> (idx, vals) in one pass
  ef_update(m, g, idx, beta, chunk, topm) -> (m', vals): the fused Eq. 5
                                             residue update (ef=m+g, gather,
                                             scatter, axpy in one read/write
                                             per tile)
  fused_reduce(m, g, beta, chunk, topm,
               mode, leader)              -> (idx, vals, m', ghat): the whole
                                             per-tensor inner loop — select
                                             over worker-stacked EF, residue
                                             update, ĝ scatter. The default
                                             here composes the three
                                             primitives (3 launches on a
                                             kernel backend); PallasBackend
                                             overrides it with the
                                             single-launch VMEM-resident
                                             kernel (kernels.fused_reduce).
                                             Only shared-index compressors
                                             are fusable (mode "clt_k" /
                                             "true_topk"); the reduce falls
                                             back to the unfused path for
                                             the rest (local_topk, random_k,
                                             exact).

so a minimal backend is exactly {select_indices, gather, scatter}.
``lane_dense(shape, chunk, dtype)`` reports whether a kernel backend tiles
such an array lane-dense (the telemetry's ``lane_dense`` tap); the base
answers False.

Whether the reduce *calls* fused_reduce is a separate, orthogonal resolution:
``resolve_fused(spec)`` with spec True/False/"auto" ("auto" = the
SCALECOM_FUSED env var at call time, default off until the on-TPU sweep
lands — see ROADMAP). Explicit config wins over env, mirroring
layout/backend resolution.

Resolution
----------
``resolve_backend(spec)`` with spec one of:

  "jnp"     the pure-jnp reference backend (core.chunked ops)
  "pallas"  the Pallas kernels; native on TPU, interpret mode elsewhere
  "auto"    call-time probes, compat-layer style (repro.compat.jax_compat):
            the SCALECOM_BACKEND env var wins if set; otherwise pallas iff
            the pallas package imports AND jax.default_backend() == "tpu"
            (interpret mode is a correctness path, not a fast CPU path);
            jnp otherwise.
  a KernelBackend instance — returned as-is (tests, custom backends)

Probes run at call time, not import time, so tests can monkeypatch either
branch and deployments that hot-swap jax stay correct. Third-party backends
register with ``register_backend(name, factory)``.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Tuple, Union

import jax
import jax.numpy as jnp

from repro.compat import jax_compat

Array = jnp.ndarray

__all__ = [
    "KernelBackend",
    "FUSABLE_MODES",
    "register_backend",
    "available_backends",
    "resolve_backend",
    "resolve_fused",
    "pallas_available",
]

# Selection modes fused_reduce implements — the shared-index compressors.
# Must agree with kernels.fused_reduce.FUSABLE_MODES (kept separate so this
# module never imports the pallas package).
FUSABLE_MODES = ("clt_k", "true_topk")


class KernelBackend:
    """Dispatch target for the chunked hot-path ops (see module docstring)."""

    name: str = "base"

    # -- primitives (implement these) ------------------------------------

    def select_indices(self, x: Array, chunk: int, topm: int = 1) -> Array:
        """Per-chunk magnitude top-m offsets along the last axis.

        x: (..., n). Returns int32 (..., n_chunks) for topm == 1, else
        (..., n_chunks, topm) ordered by descending magnitude (ties to the
        lower offset, matching jax.lax.top_k).
        """
        raise NotImplementedError

    def gather(self, x: Array, idx: Array, chunk: int, topm: int = 1) -> Array:
        """Values of (..., n) ``x`` at per-chunk offsets ``idx``.

        idx broadcasts against x's leading dims (shared leader indices vs
        per-worker data) and ends in (..., n_chunks) or, for topm > 1,
        (..., n_chunks, topm) — pass ``topm``; trailing shape alone cannot
        distinguish a shared (n_chunks, topm) set from a worker-stacked
        (n_workers, n_chunks) one. Output follows the broadcast of idx.
        """
        raise NotImplementedError

    def scatter(
        self, vals: Array, idx: Array, chunk: int, size: int, topm: int = 1
    ) -> Array:
        """Dense (..., size) with per-chunk ``vals`` at ``idx``, else zeros.

        vals and idx broadcast against each other; for topm > 1 both end in
        (..., n_chunks, topm) (pass ``topm`` — trailing shape alone is
        ambiguous when topm == n_chunks). Writes into the zero-padded tail
        chunk are dropped by the final slice to ``size``.
        """
        raise NotImplementedError

    def lane_dense(self, shape, chunk: int, dtype) -> bool:
        """Whether the 3-launch ops stream a chunked (..., n) array of
        ``shape`` as lane-dense tiles (full 128-lane rows, several chunks a
        row). A static fact of the shapes; only a kernel backend has tiles,
        so the default answers False."""
        return False

    # -- derived (override for fusion) ------------------------------------

    def select(self, x: Array, chunk: int, topm: int = 1) -> Tuple[Array, Array]:
        """Per-chunk (indices, values) — fused on kernel backends."""
        idx = self.select_indices(x, chunk, topm)
        return idx, self.gather(x, idx, chunk, topm)

    def ef_update(
        self, m: Array, g: Array, idx: Array, beta: float, chunk: int,
        topm: int = 1,
    ) -> Tuple[Array, Array]:
        """Fused low-pass EF residue update (paper Eq. 5) along the last axis.

        m, g: (..., size); idx broadcastable per-chunk offsets (see gather
        for the topm convention). Returns (m_new, vals) where vals = (m+g)
        gathered at idx and m_new = m + beta * (g - scatter(vals, idx)).
        """
        ef = m + g
        vals = self.gather(ef, idx, chunk, topm)
        own = self.scatter(vals, idx, chunk, m.shape[-1], topm)
        return m + beta * (g - own), vals

    def fused_reduce(
        self,
        m: Array,
        g: Array,
        beta: float,
        chunk: int,
        topm: int = 1,
        mode: str = "clt_k",
        leader: Union[Array, None] = None,
    ) -> Tuple[Array, Array, Array, Array]:
        """The whole per-tensor inner loop: select → EF update → ĝ scatter.

        m, g: worker-stacked (n_workers, ..., size). mode is the shared-index
        selection rule ("clt_k" needs ``leader``, the traced int32 leader
        rank t mod n; "true_topk" selects over the worker mean and ignores
        it). Returns (idx, vals, m_new, ghat):

          idx    (..., n_chunks[, topm])             shared index set
          vals   (n_workers, ..., n_chunks[, topm])  per-worker EF values
          m_new  m.shape                             Eq. 5 residue update
          ghat   (..., size)                         scatter of mean(vals)

        This default composes the three primitives — the exact op sequence
        ``core.scalecom._execute`` runs on the unfused path, so any backend
        implementing the minimal surface gets fused_reduce for free (3
        launches on a kernel backend). PallasBackend overrides it with the
        single-launch VMEM-resident kernel.
        """
        if mode not in FUSABLE_MODES:
            raise ValueError(
                f"fused_reduce supports modes {FUSABLE_MODES}, got {mode!r}"
            )
        ef = m + g
        if mode == "clt_k":
            from repro.core.compressors import leader_pick

            idx = leader_pick(self.select_indices(ef, chunk, topm), leader)
        else:
            idx = self.select_indices(jnp.mean(ef, axis=0), chunk, topm)
        m_new, vals = self.ef_update(m, g, idx, beta, chunk, topm)
        ghat = self.scatter(
            jnp.mean(vals, axis=0), idx, chunk, m.shape[-1], topm
        )
        return idx, vals, m_new, ghat

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"<KernelBackend {self.name}>"


# ---------------------------------------------------------------------------
# registry + resolution
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[], KernelBackend]] = {}

_ENV_VAR = "SCALECOM_BACKEND"


def register_backend(name: str, factory: Callable[[], KernelBackend]) -> None:
    """Register a backend factory under ``name`` (resolved lazily)."""
    _REGISTRY[name] = factory


def available_backends() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def pallas_available() -> bool:
    """Call-time probe: does this jax ship the pallas package?

    Delegates to the compat layer (repro.compat.jax_compat), the one module
    allowed to touch ``jax.experimental`` — scalecheck's compat-boundary
    rule enforces that split. Re-exported here because the backend registry
    is the probe's consumer (and tests monkeypatch it at this name).
    """
    return jax_compat.pallas_available()


def resolve_backend(
    spec: Union[str, KernelBackend, None] = "auto",
) -> KernelBackend:
    """Resolve a backend spec ("auto" | "jnp" | "pallas" | instance).

    See the module docstring for the "auto" probe order. Raises ValueError
    for unknown names (listing what is registered).
    """
    if isinstance(spec, KernelBackend):
        return spec
    name = spec or "auto"
    if name == "auto":
        env = os.environ.get(_ENV_VAR, "").strip()
        if env:
            name = env
        elif pallas_available() and jax.default_backend() == "tpu":
            name = "pallas"
        else:
            name = "jnp"
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel backend {name!r}; registered: "
            f"{sorted(_REGISTRY)} (register_backend to add one)"
        ) from None
    return factory()


_FUSED_ENV = "SCALECOM_FUSED"
_FUSED_TRUE = ("1", "true", "on", "yes")
_FUSED_FALSE = ("0", "false", "off", "no")


def resolve_fused(spec: Union[bool, str, None] = "auto") -> bool:
    """Resolve the fused-reduce decision (True | False | "auto").

    Explicit booleans win unconditionally ("explicit beats env", same
    contract as layout/backend resolution). "auto"/None reads the
    SCALECOM_FUSED env var at CALL time (so tests and hot-swapping
    deployments see updates): accepted truthy values {1, true, on, yes},
    falsy {0, false, off, no} (case-insensitive); unset/empty means False —
    the fused kernel stays opt-in until the on-TPU autotune sweep validates
    native lowering (ROADMAP follow-up). Anything else raises naming the
    valid set.
    """
    if isinstance(spec, bool):
        return spec
    if spec in (None, "auto"):
        env = os.environ.get(_FUSED_ENV, "").strip().lower()
        if not env:
            return False
        if env in _FUSED_TRUE:
            return True
        if env in _FUSED_FALSE:
            return False
        raise ValueError(
            f"invalid {_FUSED_ENV}={env!r}; expected one of "
            f"{_FUSED_TRUE + _FUSED_FALSE}"
        )
    raise ValueError(
        f"fused must be True, False, or 'auto' "
        f"(then ${_FUSED_ENV} decides); got {spec!r}"
    )
