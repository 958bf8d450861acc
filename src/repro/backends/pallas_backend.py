"""Pallas kernel backend: the fused TPU hot path.

Routes every chunked op of the reduce through the Pallas kernels
(repro.kernels.{chunk_topk, ef_update, rowwise}), turning the per-tensor
inner loop from the 7-pass jnp chain (add, argmax, gather, mean-prep,
scatter, scatter, axpy) into

    1 launch  select          — worker-stacked per-chunk argmax (+ top-m)
    1 launch  ef_update       — fused ef=m+g / gather / scatter / axpy
                                (~2.3x less HBM traffic on the residue, the
                                largest state in the system)
    1 launch  scatter         — densify the k reduced values into ĝ

in *both* layouts: every op goes through the trailing-axis wrappers in
kernels.rowwise (kernels.chunk_topk row launchers underneath), so a flat
1-D buffer and a layout-preserving (n_workers, *param_shape) tensor take
the identical code path — the backend pads the trailing axis to a chunk
multiple here and slices dense outputs back (both no-ops when the axis is
a chunk multiple). The tile geometry follows the shapes
(``chunk_topk.lane_dense``, reported by ``lane_dense``): full-width flat
buffers take lane-dense tiles, which the kernels read and write in place.

Execution mode is a call-time probe: native Mosaic lowering when
jax.default_backend() == "tpu", interpret mode elsewhere (the same math at
host speed — the CPU correctness path, exercised by tests/test_paths.py).
``chip_smoke.py`` asserts the native mode on the chip. The chunks a grid
step covers are the constants of kernels.chunk_topk: ``DENSE_BLOCK_CHUNKS``
for a lane-dense launch, ``BLOCK_CHUNKS`` otherwise (the fused launch
included).

Constructing the backend requires the pallas package to import; resolution
via resolve_backend("pallas") raises a clear error on jax builds without it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.backends.base import KernelBackend, pallas_available, register_backend

Array = jnp.ndarray

__all__ = ["PallasBackend"]


class PallasBackend(KernelBackend):
    name = "pallas"

    def __init__(self, *, interpret=None):
        """interpret: force the execution mode; None = probe per call."""
        if not pallas_available():
            raise ImportError(
                "backend 'pallas' requested but jax.experimental.pallas does "
                "not import on this jax build; use backend='jnp' (or 'auto')"
            )
        self._interpret = interpret

    def _interp(self) -> bool:
        if self._interpret is not None:
            return self._interpret
        return jax.default_backend() != "tpu"

    def _block(self, shape, chunk: int, dtype) -> int:
        """Chunks a grid step of a 3-launch op over ``shape`` covers."""
        from repro.kernels.chunk_topk import BLOCK_CHUNKS, DENSE_BLOCK_CHUNKS

        dense = self.lane_dense(shape, chunk, dtype)
        return DENSE_BLOCK_CHUNKS if dense else BLOCK_CHUNKS

    def lane_dense(self, shape, chunk: int, dtype) -> bool:
        from repro.kernels.chunk_topk import lane_dense

        cp = -(-shape[-1] // chunk) * chunk
        return lane_dense(chunk, cp, math.prod(shape[:-1]) * cp, dtype)

    def select_indices(self, x: Array, chunk: int, topm: int = 1) -> Array:
        return self.select(x, chunk, topm)[0]

    def select(self, x: Array, chunk: int, topm: int = 1):
        from repro.kernels import rowwise

        return rowwise.select_trailing(
            _padded(x, chunk), chunk, topm, interpret=self._interp(),
            block_chunks=self._block(x.shape, chunk, x.dtype),
        )

    def gather(self, x: Array, idx: Array, chunk: int, topm: int = 1) -> Array:
        from repro.kernels import rowwise

        return rowwise.gather_trailing(
            _padded(x, chunk), idx, chunk, topm, interpret=self._interp(),
            block_chunks=self._block(x.shape, chunk, x.dtype),
        )

    def scatter(
        self, vals: Array, idx: Array, chunk: int, size: int, topm: int = 1
    ) -> Array:
        from repro.kernels import rowwise

        n_chunks = -(-size // chunk)
        # the launch's shape includes the broadcast leading dims, which
        # decide whether its tiles are lane-dense
        tail = 1 if topm == 1 else 2
        lead = jnp.broadcast_shapes(idx.shape[:-tail], vals.shape[:-tail])
        out = rowwise.scatter_trailing(
            vals, idx, chunk, n_chunks * chunk, topm=topm,
            interpret=self._interp(),
            block_chunks=self._block(
                tuple(lead) + (n_chunks * chunk,), chunk, vals.dtype
            ),
        )
        return out[..., :size]

    def ef_update(
        self, m: Array, g: Array, idx: Array, beta: float, chunk: int,
        topm: int = 1,
    ):
        from repro.kernels import rowwise

        n = m.shape[-1]
        m_new, vals = rowwise.ef_update_trailing(
            _padded(m, chunk), _padded(g, chunk), idx, beta, chunk, topm,
            interpret=self._interp(),
            block_chunks=self._block(m.shape, chunk, m.dtype),
        )
        return m_new[..., :n], vals

    def fused_reduce(
        self, m: Array, g: Array, beta: float, chunk: int, topm: int = 1,
        mode: str = "clt_k", leader=None,
    ):
        # ONE launch for the whole inner loop — select over worker-stacked
        # EF, Eq. 5 residue update, ĝ scatter — with each chunk tile
        # VMEM-resident across all three phases (kernels.fused_reduce).
        from repro.kernels import fused_reduce as fr

        n = m.shape[-1]
        if leader is None:
            leader = jnp.zeros((), jnp.int32)
        idx, vals, m_new, ghat = fr.fused_reduce_trailing(
            _padded(m, chunk), _padded(g, chunk), leader, float(beta),
            chunk, topm, mode, interpret=self._interp(),
        )
        return idx, vals, m_new[..., :n], ghat[..., :n]


def _padded(x: Array, chunk: int) -> Array:
    """Pad the trailing axis to a chunk multiple (trailing-kernel contract)."""
    from repro.core import chunked

    return chunked.pad_to_chunks(x, chunk)


@functools.lru_cache(maxsize=4)
def _instance(interpret=None) -> PallasBackend:
    return PallasBackend(interpret=interpret)


register_backend("pallas", _instance)
