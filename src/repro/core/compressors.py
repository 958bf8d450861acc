"""Sparsifying compressors for error-feedback gradient compression.

Each compressor consumes the *worker-axis stacked* error-feedback gradients of one
flat tensor, ``ef`` with shape (n_workers, size), and returns

    (values, indices, dense_mean)

where ``values[i]`` are worker i's entries at the *shared* index set, ``indices`` is
that shared index set, and ``dense_mean`` is the dense reconstruction of the
all-reduced compressed gradient, i.e. sparse(mean) == mean(sparse) for commutative
compressors (Eq. 1 of the paper).

Compressors implemented (paper Table 1 comparisons):

  clt_k        — the paper's contribution: Cyclic Local Top-k. The leader
                 (``t mod n``) selects per-chunk magnitude arg-max indices of its own
                 EF gradient; everyone compresses with them. Commutative.
  true_topk    — the impractical oracle: indices from the *averaged* EF gradient
                 (requires a dense all-reduce; used for contraction analysis only).
  local_topk   — Strom-style per-worker local selection [21]: each worker picks its
                 own indices. NOT commutative — models the gradient build-up
                 baseline; the "reduced" gradient is the union-average (gather
                 semantics). Communication volume grows O(n).
  random_k     — shared random index set per step (commutative, weak contraction).
  none         — identity (no compression) baseline.

All selection is chunk-wise (chunk C, top-m per chunk) to match the paper's
production implementation; exact dense top-k equivalents are available through
``exact=True`` for analysis at small sizes.

Every chunked op dispatches through a ``repro.backends`` KernelBackend
(pure-jnp oracles or the Pallas TPU kernels — top-1 *and* top-m, there is no
silent jnp fallback). Callers pass a resolved backend; the default resolves
"auto" (pallas on a TPU, else jnp).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

__all__ = [
    "CompressorConfig",
    "compress",
    "select_indices",
    "exact_k",
    "COMPRESSORS",
    "FUSABLE_MODES",
    "compression_rate",
]


@dataclasses.dataclass(frozen=True)
class CompressorConfig:
    """Static configuration of a sparsifying compressor.

    name:       one of COMPRESSORS
    chunk:      chunk size C (compression rate = C / topm) for chunked selection
    topm:       entries kept per chunk
    exact:      use exact dense top-k over the whole tensor instead of chunked
                selection (analysis only; k = size * topm / chunk)
    """

    name: str = "clt_k"
    chunk: int = 64
    topm: int = 1
    exact: bool = False

    def __post_init__(self):
        # fail fast: topm > chunk would silently duplicate indices in the
        # masked-argmax kernels (double-counted scatters) instead of erroring
        if not 1 <= self.topm <= self.chunk:
            raise ValueError(
                f"topm must be in [1, chunk]; got topm={self.topm} "
                f"chunk={self.chunk} (compression rate = chunk/topm)"
            )

    @property
    def rate(self) -> float:
        return self.chunk / self.topm


def compression_rate(cfg: CompressorConfig) -> float:
    return cfg.rate


# ---------------------------------------------------------------------------
# index selection strategies (per flat tensor, worker-stacked ef: (n, size))
# ---------------------------------------------------------------------------


def leader_pick(stacked: Array, leader: Array) -> Array:
    """Select row ``leader`` of a worker-sharded (n, ...) array as a masked
    SUM over the worker axis.

    A dynamic slice over a sharded axis makes GSPMD all-gather the whole
    array (observed: 18 GB/step of index gathers at n=256); the masked psum
    moves only the k-sized reduction payload — the paper's O(k) index
    broadcast (§5: ~0.5%% of baseline traffic, O(1) in n).
    """
    n = stacked.shape[0]
    mask = (jnp.arange(n) == leader).astype(stacked.dtype)
    return jnp.sum(stacked * mask.reshape((n,) + (1,) * (stacked.ndim - 1)), axis=0)


def _select_clt(ef: Array, t: Array, cfg: CompressorConfig, backend) -> Array:
    """Leader (= t mod n) chunk-top-m indices: every worker computes its own
    candidate index row in one batched backend call; the leader's is
    broadcast via ``leader_pick``."""
    n = ef.shape[0]
    idx_all = backend.select_indices(ef, cfg.chunk, cfg.topm)
    return leader_pick(idx_all, jnp.mod(t, n))


def _select_true(ef: Array, t: Array, cfg: CompressorConfig, backend) -> Array:
    """True top-k oracle: indices of the *averaged* EF gradient (dense comm)."""
    del t
    return backend.select_indices(jnp.mean(ef, axis=0), cfg.chunk, cfg.topm)


def _select_random(ef: Array, t: Array, cfg: CompressorConfig, backend) -> Array:
    """Shared random index set, re-drawn each step from a counter-derived key.

    The draw is layout-consistent: jax.random fills shapes in row-major
    order from the flat counter stream, so a (n_chunks,) flat draw and a
    (*lead, n_chunks_per_row) trailing-axis draw of the same total chunk
    count are bitwise identical after reshape — flat ≡ rowwise holds for
    random_k exactly like for the data-dependent selectors.

    Tail chunks: when the trailing axis is not a chunk multiple, the last
    chunk only covers ``size mod chunk`` real elements. A raw draw over
    [0, chunk) can point past the end — the gather then reads the zero
    padding and the scatter's write is sliced away, so the entry is silently
    dropped from ĝ while ``plan.bytes_payload`` still bills a real value.
    Draws are therefore confined to the tail's real width (the magnitude
    selectors get this for free: zero padding never wins an arg-max against
    real data). Both guards are no-ops when the axis is a chunk multiple, so
    the flat ≡ rowwise bitwise property is untouched.
    """
    del backend
    key = jax.random.fold_in(jax.random.PRNGKey(0x5CA1EC0), t)
    lead = ef.shape[1:-1]  # per-tensor dims between the worker axis and chunks
    size = ef.shape[-1]
    n_ch = -(-size // cfg.chunk)
    tail = size - (n_ch - 1) * cfg.chunk  # real width of the last chunk
    if cfg.topm == 1:
        idx = jax.random.randint(
            key, lead + (n_ch,), 0, cfg.chunk, dtype=jnp.int32
        )
        if tail < cfg.chunk:
            width = jnp.where(
                jnp.arange(n_ch) == n_ch - 1, tail, cfg.chunk
            ).astype(jnp.int32)
            idx = jnp.minimum(idx, width - 1)
        return idx
    # sample without replacement per chunk via random values + top_k
    r = jax.random.uniform(key, lead + (n_ch, cfg.chunk))
    if tail < cfg.chunk:
        # rank past-the-end tail lanes below every real lane (uniform draws
        # are >= 0) so top_k only reaches them once the tail's real lanes are
        # exhausted — the same semantics as magnitude selection over padding
        valid = (jnp.arange(n_ch)[:, None] < n_ch - 1) | (
            jnp.arange(cfg.chunk)[None, :] < tail
        )
        r = jnp.where(valid, r, -1.0)
    _, idx = jax.lax.top_k(r, cfg.topm)
    return idx.astype(jnp.int32)


_SHARED_INDEX_SELECTORS = {
    "clt_k": _select_clt,
    "true_topk": _select_true,
    "random_k": _select_random,
}

COMPRESSORS = ("clt_k", "true_topk", "local_topk", "random_k", "none")

# The shared-index compressors: every worker sends values at one index set,
# so select, EF update and ĝ scatter run as one op (KernelBackend.fused_reduce
# and the single-launch kernel). local_topk (per-worker index sets) and
# random_k (counter-PRNG draws, not reproducible in-tile) keep the 3-op path.
FUSABLE_MODES = ("clt_k", "true_topk")


def select_indices(ef: Array, t: Array, cfg: CompressorConfig, backend) -> Array:
    """The chunked index-selection step of each compressor, backend-dispatched.

    ef is worker-stacked with chunks along the trailing axis — (n, size) in
    the flat layout or (n, *param_shape) in the layout-preserving rowwise
    layout; the selectors are layout-agnostic. Shared-index compressors
    return the shared (..., n_chunks[, topm]) set (no worker axis);
    local_topk returns per-worker (n, ..., n_chunks[, topm]) sets. This is
    the entry point ``scalecom_reduce``'s execute stage shares with
    ``compress``.
    """
    if cfg.name == "local_topk":
        return backend.select_indices(ef, cfg.chunk, cfg.topm)
    return _SHARED_INDEX_SELECTORS[cfg.name](ef, t, cfg, backend)


# ---------------------------------------------------------------------------
# exact (dense, non-chunked) top-k — analysis path
# ---------------------------------------------------------------------------


def exact_k(size: int, cfg: CompressorConfig) -> int:
    """k of the exact (dense top-k) analysis path: size * topm / chunk."""
    return max(1, int(size * cfg.topm // cfg.chunk))


def _compress_exact(
    ef: Array, t: Array, cfg: CompressorConfig
) -> Tuple[Array, Array, Array]:
    n, size = ef.shape
    k = exact_k(size, cfg)
    if cfg.name == "clt_k":
        idx_all = jax.vmap(lambda e: jax.lax.top_k(jnp.abs(e), k)[1])(ef)
        idx = leader_pick(idx_all, jnp.mod(t, n))
    elif cfg.name == "true_topk":
        _, idx = jax.lax.top_k(jnp.abs(jnp.mean(ef, axis=0)), k)
    elif cfg.name == "random_k":
        key = jax.random.fold_in(jax.random.PRNGKey(0x5CA1EC0), t)
        idx = jax.random.choice(key, size, (k,), replace=False)
    elif cfg.name == "local_topk":
        idx_all = jax.vmap(lambda e: jax.lax.top_k(jnp.abs(e), k)[1])(ef)
        vals = jnp.take_along_axis(ef, idx_all, axis=-1)
        dense = jnp.zeros((n, size), ef.dtype)
        dense = jax.vmap(
            lambda d, i, v: d.at[i].set(v, mode="drop")
        )(dense, idx_all, vals)
        return vals, idx_all, jnp.mean(dense, axis=0)
    else:
        raise ValueError(cfg.name)
    vals = jnp.take_along_axis(ef, jnp.broadcast_to(idx, (n, k)), axis=-1)
    vmean = jnp.mean(vals, axis=0)
    dense = jnp.zeros((size,), ef.dtype).at[idx].set(vmean, mode="drop")
    return vals, idx, dense


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------


def compress(
    ef: Array, t: Array, cfg: CompressorConfig, backend=None
) -> Tuple[Array, Array, Array]:
    """Compress worker-stacked EF gradients ``ef`` (n, size) at step ``t``.

    backend: a resolved ``repro.backends.KernelBackend``; None resolves
    "auto".

    Returns (values, indices, dense_mean):
      values:     (n, k)  per-worker entries at the shared index set
                  (local_topk: each worker's own set)
      indices:    (k,) shared index layout — for chunked selection this is
                  (n_chunks,) or (n_chunks, topm) per-chunk offsets
      dense_mean: (size,) dense reconstruction of the reduced gradient ĝ
    """
    if ef.ndim != 2:
        raise ValueError(f"ef must be (n_workers, size), got {ef.shape}")
    n, size = ef.shape

    if cfg.name == "none":
        vmean = jnp.mean(ef, axis=0)
        return ef, jnp.zeros((0,), jnp.int32), vmean

    if cfg.exact:
        return _compress_exact(ef, t, cfg)

    if backend is None:
        from repro.backends import resolve_backend

        backend = resolve_backend()

    idx = select_indices(ef, t, cfg, backend)
    vals = backend.gather(ef, idx, cfg.chunk, cfg.topm)
    if cfg.name == "local_topk":
        # Every worker its own indices: gather semantics (gradient build-up).
        dense_each = backend.scatter(vals, idx, cfg.chunk, size, cfg.topm)
        return vals, idx, jnp.mean(dense_each, axis=0)
    # Commutative reduce: mean over the worker axis touches only k values.
    vmean = jnp.mean(vals, axis=0)
    dense = backend.scatter(vmean, idx, cfg.chunk, size, cfg.topm)
    return vals, idx, dense
