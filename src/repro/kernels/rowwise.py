"""Trailing-axis Pallas wrappers — the one kernel surface of the reduce.

Every chunked op of ``scalecom_reduce`` runs over the trailing axis of an
arbitrarily-batched array ((..., Cp) with Cp % chunk == 0): a flat 1-D
buffer, a worker-stacked (n_workers, size) tensor, and a layout-preserving
(n_workers, *param_shape) tensor are all the *same launch* — flat is the
degenerate single-row case. An input of shape (..., Cp) is locally a
contiguous stack of (Cp/chunk) chunks per row, so the 2-D tile view taken
here (``chunk_topk.tile_view``) is a row-major reshape, *per-shard* legal
under GSPMD: the kernels always execute on the local shard, whose trailing
dim is a chunk multiple by the sharding contract, unlike a global 1-D
flatten of a model-sharded tensor (which forces resharding and motivated the
layout-preserving rowwise layout in the first place — see core/chunked.py).
Whether the reshape is free depends on the view: the lane-dense
(size/128, 128) view of a flat fp32 buffer with whole (8, 128) tiles is a
bitcast, while a (total_chunks, chunk) view with chunk < 128 pads every row
to 128 lanes in TPU memory — a relayout copy of the whole buffer — and m'
and ĝ pay a second one on the way back. ``chunk_topk.lane_dense`` picks the
lane-dense view wherever the shapes allow it.

All wrappers accept arbitrary leading batch dims (worker axis included), so
callers never vmap a pallas_call: one launch covers every worker's tiles.
``idx``/``vals`` broadcast against the data the way core.chunked ops do
(shared leader indices vs per-worker values); ``topm`` is explicit and
static, so a shared (n_chunks, topm) index set is never confused with a
worker-stacked (n_workers, n_chunks) one.

Tile geometry and grid handling are shared with the flat 1-D kernels
(kernels.chunk_topk row launchers); ``block_chunks``, the chunks a grid step
covers in either geometry, is swept by repro.backends.autotune and
benchmarked in benchmarks/bench_kernels.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.chunk_topk import (
    BLOCK_CHUNKS,
    row_gather,
    row_scatter,
    row_select,
    scatter_width,
    tile_view,
)
from repro.kernels.ef_update import row_ef_update

__all__ = [
    "select_trailing",
    "gather_trailing",
    "scatter_trailing",
    "ef_update_trailing",
]


def _check_padded(cp: int, chunk: int) -> int:
    if cp % chunk:
        raise ValueError(
            f"trailing-axis kernels need the last dim pre-padded to the chunk "
            f"size (got {cp} % {chunk} != 0); call core.chunked.pad_to_chunks "
            f"first"
        )
    return cp // chunk


def _idx_rows(idx: jnp.ndarray, lead, ncr: int, topm_tail) -> jnp.ndarray:
    """Broadcast per-chunk indices over leading dims, flatten to rows."""
    idx = jnp.broadcast_to(idx, tuple(lead) + (ncr,) + tuple(topm_tail))
    return idx.reshape((-1,) + tuple(topm_tail))


def _tail(topm: int):
    return () if topm == 1 else (topm,)


@functools.partial(
    jax.jit, static_argnames=("chunk", "topm", "interpret", "block_chunks")
)
def select_trailing(
    x: jnp.ndarray, chunk: int, topm: int = 1, *, interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Per-chunk magnitude top-m along the last dim.

    x: (..., Cp). Returns (idx, vals) of shape (..., Cp/chunk) for topm == 1,
    (..., Cp/chunk, topm) otherwise — matching core.chunked.chunk_argmax /
    chunk_topm_indices + chunk_gather.
    """
    ncr = _check_padded(x.shape[-1], chunk)
    idx, val = row_select(
        tile_view(x, chunk), chunk, topm=topm, interpret=interpret,
        block_chunks=block_chunks,
    )
    out_shape = x.shape[:-1] + (ncr,) + _tail(topm)
    return idx.reshape(out_shape), val.reshape(out_shape)


@functools.partial(
    jax.jit, static_argnames=("chunk", "topm", "interpret", "block_chunks")
)
def gather_trailing(
    x: jnp.ndarray, idx: jnp.ndarray, chunk: int, topm: int = 1, *,
    interpret: bool = True, block_chunks: int = BLOCK_CHUNKS,
):
    """Values of (..., Cp) ``x`` at per-chunk offsets ``idx`` (broadcastable
    (..., Cp/chunk) or, for topm > 1, (..., Cp/chunk, topm))."""
    ncr = _check_padded(x.shape[-1], chunk)
    idx2 = _idx_rows(idx, x.shape[:-1], ncr, _tail(topm))
    val = row_gather(
        tile_view(x, chunk), idx2, chunk, interpret=interpret,
        block_chunks=block_chunks,
    )
    return val.reshape(x.shape[:-1] + (ncr,) + _tail(topm))


@functools.partial(
    jax.jit, static_argnames=("chunk", "cp", "topm", "interpret", "block_chunks")
)
def scatter_trailing(
    vals: jnp.ndarray, idx: jnp.ndarray, chunk: int, cp: int, *,
    topm: int = 1, interpret: bool = True, block_chunks: int = BLOCK_CHUNKS,
):
    """Dense (..., cp) with per-chunk ``vals`` at ``idx``, zeros elsewhere.

    vals and idx broadcast against each other (shared leader idx vs per-worker
    vals), like core.chunked.chunk_scatter. For topm > 1 both end in
    (..., cp/chunk, topm).
    """
    ncr = _check_padded(cp, chunk)
    tail = _tail(topm)
    n_tail = len(tail) + 1
    lead = jnp.broadcast_shapes(idx.shape[:-n_tail], vals.shape[:-n_tail])
    idx2 = _idx_rows(idx, lead, ncr, tail)
    val2 = _idx_rows(vals, lead, ncr, tail)
    out = row_scatter(
        val2, idx2, chunk, scatter_width(chunk, cp, math.prod(lead) * cp, vals.dtype),
        interpret=interpret, block_chunks=block_chunks,
    )
    return out.reshape(tuple(lead) + (cp,))


@functools.partial(
    jax.jit, static_argnames=("beta", "chunk", "topm", "interpret", "block_chunks")
)
def ef_update_trailing(
    m: jnp.ndarray,
    g: jnp.ndarray,
    idx: jnp.ndarray,
    beta: float,
    chunk: int,
    topm: int = 1,
    *,
    interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Fused Eq. 5 residue update along the trailing axis.

    m, g: (..., Cp) with Cp % chunk == 0; idx broadcastable (..., Cp/chunk)
    or, for topm > 1, (..., Cp/chunk, topm). beta static. Returns
    (m_new (..., Cp), vals (..., Cp/chunk[, topm])).
    """
    ncr = _check_padded(m.shape[-1], chunk)
    tail = _tail(topm)
    idx2 = _idx_rows(idx, m.shape[:-1], ncr, tail)
    m_new, vals = row_ef_update(
        tile_view(m, chunk), tile_view(g, chunk), idx2, beta, chunk,
        interpret=interpret, block_chunks=block_chunks,
    )
    return (
        m_new.reshape(m.shape),
        vals.reshape(m.shape[:-1] + (ncr,) + tail),
    )
