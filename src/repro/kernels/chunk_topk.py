"""Pallas TPU kernels: chunk-wise magnitude selection, gather and scatter.

This is the paper's compute hot spot: Table 1 prices ScaleCom's compressor at
~3 FLOPs/element of "chunk-wise sort" (GPU quasi-sort, [39]); the leader runs it
over its full error-feedback gradient every step and every worker runs the
gather at the selected offsets.

TPU adaptation (DESIGN.md §2): instead of porting a GPU bitonic sorting network,
the chunked top-1 selection is phrased as a *lane-local arg-max over a 2-D VMEM
tile*. The flat gradient is viewed as (n_chunks, chunk); the kernel streams
(block_chunks, chunk) tiles HBM->VMEM and emits per-chunk (argmax, value) pairs.
All reductions are along the minor (lane) axis, the natural VPU reduction
direction: no data-dependent control flow, no cross-lane shuffles, MXU not
needed. Reads at a data-dependent lane offset are a one-hot compare-and-select
followed by a lane sum (``lane_pick``): Mosaic lowers no in-kernel gather.
``block_chunks`` is a static tuning knob swept by ``repro.backends.autotune``;
it must be a multiple of 1024 (see ``BLOCK_CHUNKS``). With the default
chunk of 64 each tile row fills half of the 128 lanes.

Four kernel bodies share the tile geometry:

  _argmax_kernel   per-chunk top-1 (indices + values) — the CLT-k selector
  _topm_kernel     per-chunk top-m via m static masked-argmax passes (the
                   milder-rate path of the paper's §4 per-layer guidance)
  _gather_kernel   values at given per-chunk offsets (top-1 or top-m)
  _scatter_kernel  dense tile from per-chunk (offset, value) pairs

The fused residue update lives in repro.kernels.ef_update; trailing-axis
(rowwise-layout) wrappers over the same launchers live in
repro.kernels.rowwise. These flat wrappers are the 1-D public API
(``repro.backends`` is the dispatch layer that picks between them and the jnp
oracles in repro.core.chunked).

Validated against repro.kernels.ref in interpret mode (CPU) over a shape/dtype
sweep — see tests/test_kernels.py and tests/test_backends.py — and compiled
for a described TPU v5e at full width by tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "BLOCK_CHUNKS",
    "chunk_argmax_pallas",
    "chunk_topm_pallas",
    "chunk_gather_pallas",
    "chunk_scatter_pallas",
]

# Default tile geometry: (BLOCK_CHUNKS, chunk) tiles; BLOCK_CHUNKS rows of the
# chunk view are processed per grid step. The per-row (index, value) arrays
# are 1-D, and XLA lays a long 1-D TPU array out in 1024-element tiles; Mosaic
# refuses a 1-D block that is not a multiple of that tile, so every block
# height is a multiple of 1024 (autotune.CANDIDATE_BLOCKS). A 1024 x 64 fp32
# tile is 512 KiB in VMEM (64-lane rows pad to 128 lanes), well inside the
# 16 MiB default scoped limit with double buffering.
BLOCK_CHUNKS = 1024


# ---------------------------------------------------------------------------
# kernel bodies (one (block_chunks, chunk) tile per grid step)
# ---------------------------------------------------------------------------


def lane_pick(x, idx):
    """Values of ``x`` (..., C) at lane offsets ``idx`` (...,): a one-hot
    compare-and-select followed by a lane reduction.

    Mosaic has no in-kernel gather, so every per-chunk read at a data-
    dependent offset takes this form. Exactly one lane survives the select,
    so the sum is exact in any float dtype.
    """
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    zero = jnp.zeros((), x.dtype)
    return jnp.sum(jnp.where(cols == idx[..., None], x, zero), axis=-1)


def lane_argmax(mag):
    """Per-row arg-max of ``mag`` (..., C) over lanes, as a max and a min.

    Returns what ``jnp.argmax`` returns — the lowest lane among equal
    maxima, or the first NaN — so indices match the jnp oracles bitwise.
    Mosaic's own argmax lowering breaks exact ties differently on the chip.
    """
    cols = jax.lax.broadcasted_iota(jnp.int32, mag.shape, mag.ndim - 1)
    top = jnp.max(mag, axis=-1, keepdims=True)
    hit = (mag == top) | (mag != mag)  # mag != mag: NaN ranks first
    return jnp.min(jnp.where(hit, cols, mag.shape[-1]), axis=-1)


def _argmax_kernel(x_ref, idx_ref, val_ref):
    """x: (B, C) tile -> idx/val: (B,) per-chunk magnitude arg-max."""
    x = x_ref[...]
    idx = lane_argmax(jnp.abs(x))
    idx_ref[...] = idx
    val_ref[...] = lane_pick(x, idx)


def _topm_kernel(x_ref, idx_ref, val_ref, *, m: int):
    """x: (B, C) tile -> idx/val: (B, m) per-chunk top-m by magnitude.

    m static masked-argmax passes. Ties break toward the lower lane, matching
    ``jax.lax.top_k`` (so indices are bitwise-comparable to the jnp oracle).
    """
    x = x_ref[...]
    mag = jnp.abs(x)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    neg = jnp.full((), -1.0, mag.dtype)
    for j in range(m):
        ij = lane_argmax(mag)
        idx_ref[:, j] = ij
        val_ref[:, j] = lane_pick(x, ij)
        mag = jnp.where(cols == ij[:, None], neg, mag)


def _gather_kernel(x_ref, idx_ref, val_ref):
    """x: (B, C), idx: (B,) or (B, m) -> values at per-chunk offsets."""
    x = x_ref[...]
    idx = idx_ref[...]
    if idx.ndim == 1:
        val_ref[...] = lane_pick(x, idx)
    else:
        for j in range(idx.shape[1]):  # top-m: m is small and static
            val_ref[:, j] = lane_pick(x, idx[:, j])


def _scatter_kernel(vals_ref, idx_ref, out_ref):
    """vals/idx: (B,) or (B, m) -> out: (B, C) dense tile, zeros elsewhere.

    Lane-iota one-hot compare — the scatter form that never materializes a
    row iota over n_chunks (int32-overflow-safe for >2^31-element tensors,
    same reasoning as core.chunked.chunk_scatter).
    """
    vals = vals_ref[...]
    idx = idx_ref[...]
    cols = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    zero = jnp.zeros((), vals.dtype)
    if idx.ndim == 1:
        out_ref[...] = jnp.where(cols == idx[:, None], vals[:, None], zero)
    else:
        z = jnp.zeros(out_ref.shape, vals.dtype)
        for j in range(idx.shape[1]):  # top-m: m is small and static
            z = z + jnp.where(cols == idx[:, j : j + 1], vals[:, j : j + 1], zero)
        out_ref[...] = z


# ---------------------------------------------------------------------------
# row launchers: (rows, chunk) 2-D in, grid/padding handled here. Shared by
# the flat wrappers below and the trailing-axis wrappers in kernels.rowwise.
# ---------------------------------------------------------------------------


def _padded_rows(n_rows: int, block_chunks: int) -> int:
    return -(-n_rows // block_chunks) * block_chunks


def _pad_rows(x2d: jnp.ndarray, block_chunks: int) -> jnp.ndarray:
    pad = _padded_rows(x2d.shape[0], block_chunks) - x2d.shape[0]
    if pad:
        widths = ((0, pad),) + ((0, 0),) * (x2d.ndim - 1)
        x2d = jnp.pad(x2d, widths)
    return x2d


def row_select(x2d, *, topm, interpret, block_chunks):
    """(rows, chunk) -> per-row top-m (idx, vals); (rows,) when topm == 1."""
    n_rows, chunk = x2d.shape
    xp = _pad_rows(x2d, block_chunks)
    rows = xp.shape[0]
    grid = rows // block_chunks
    if topm == 1:
        kernel = _argmax_kernel
        out_block, out_shape = (block_chunks,), (rows,)
    else:
        kernel = functools.partial(_topm_kernel, m=topm)
        out_block, out_shape = (block_chunks, topm), (rows, topm)
    idx, val = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_chunks, chunk), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec(out_block, (lambda i: (i,)) if topm == 1 else (lambda i: (i, 0))),
            pl.BlockSpec(out_block, (lambda i: (i,)) if topm == 1 else (lambda i: (i, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, jnp.int32),
            jax.ShapeDtypeStruct(out_shape, x2d.dtype),
        ],
        interpret=interpret,
    )(xp)
    return idx[:n_rows], val[:n_rows]


def row_gather(x2d, idx, *, interpret, block_chunks):
    """(rows, chunk), idx (rows,) or (rows, m) -> values shaped like idx."""
    n_rows, chunk = x2d.shape
    xp = _pad_rows(x2d, block_chunks)
    idxp = _pad_rows(idx, block_chunks)
    rows = xp.shape[0]
    grid = rows // block_chunks
    if idx.ndim == 1:
        aux_block, out_shape = (block_chunks,), (rows,)
        aux_map = lambda i: (i,)  # noqa: E731
    else:
        aux_block, out_shape = (block_chunks, idx.shape[1]), (rows, idx.shape[1])
        aux_map = lambda i: (i, 0)  # noqa: E731
    val = pl.pallas_call(
        _gather_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_chunks, chunk), lambda i: (i, 0)),
            pl.BlockSpec(aux_block, aux_map),
        ],
        out_specs=pl.BlockSpec(aux_block, aux_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, x2d.dtype),
        interpret=interpret,
    )(xp, idxp)
    return val[:n_rows]


def row_scatter(vals, idx, chunk, *, interpret, block_chunks):
    """vals/idx (rows,) or (rows, m) -> (rows, chunk) dense tiles."""
    n_rows = vals.shape[0]
    valp = _pad_rows(vals, block_chunks)
    idxp = _pad_rows(idx, block_chunks)
    rows = valp.shape[0]
    grid = rows // block_chunks
    if idx.ndim == 1:
        aux_block = (block_chunks,)
        aux_map = lambda i: (i,)  # noqa: E731
    else:
        aux_block = (block_chunks, idx.shape[1])
        aux_map = lambda i: (i, 0)  # noqa: E731
    out = pl.pallas_call(
        _scatter_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(aux_block, aux_map),
            pl.BlockSpec(aux_block, aux_map),
        ],
        out_specs=pl.BlockSpec((block_chunks, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), vals.dtype),
        interpret=interpret,
    )(valp, idxp)
    return out[:n_rows]


def _flat_view(x: jnp.ndarray, chunk: int):
    """Flat (n,) -> ((n_chunks, chunk) zero-padded view, n_chunks)."""
    n = x.shape[-1]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    return jnp.pad(x.reshape(-1), (0, pad)).reshape(n_chunks, chunk), n_chunks


# ---------------------------------------------------------------------------
# flat (1-D buffer) public wrappers
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "block_chunks"))
def chunk_argmax_pallas(
    x: jnp.ndarray, chunk: int, *, interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Per-chunk (indices, values) of a flat array. Returns ((n_chunks,) i32,
    (n_chunks,) x.dtype). interpret=True evaluates the kernel body with XLA
    on any device; interpret=False compiles it with Mosaic for a TPU.
    """
    xp, n_chunks = _flat_view(x, chunk)
    idx, val = row_select(xp, topm=1, interpret=interpret, block_chunks=block_chunks)
    return idx, val


@functools.partial(
    jax.jit, static_argnames=("chunk", "topm", "interpret", "block_chunks")
)
def chunk_topm_pallas(
    x: jnp.ndarray, chunk: int, topm: int, *, interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Per-chunk top-m (indices, values), each (n_chunks, topm); indices
    bitwise match ``core.chunked.chunk_topm_indices`` (descending magnitude,
    ties to the lower offset)."""
    xp, n_chunks = _flat_view(x, chunk)
    idx, val = row_select(xp, topm=topm, interpret=interpret, block_chunks=block_chunks)
    return idx, val


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "block_chunks"))
def chunk_gather_pallas(
    x: jnp.ndarray, idx: jnp.ndarray, chunk: int, *, interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Gather per-chunk values of flat ``x`` at offsets ``idx`` ((n_chunks,)
    or (n_chunks, m))."""
    xp, n_chunks = _flat_view(x, chunk)
    return row_gather(xp, idx, interpret=interpret, block_chunks=block_chunks)


@functools.partial(
    jax.jit, static_argnames=("chunk", "size", "interpret", "block_chunks")
)
def chunk_scatter_pallas(
    vals: jnp.ndarray, idx: jnp.ndarray, chunk: int, size: int, *,
    interpret: bool = True, block_chunks: int = BLOCK_CHUNKS,
):
    """Dense flat (size,) array with per-chunk ``vals`` at offsets ``idx``."""
    out = row_scatter(vals, idx, chunk, interpret=interpret, block_chunks=block_chunks)
    return out.reshape(-1)[:size]
