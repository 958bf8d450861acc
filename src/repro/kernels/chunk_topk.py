"""Pallas TPU kernels: chunk-wise magnitude selection, gather and scatter.

This is the paper's compute hot spot: Table 1 prices ScaleCom's compressor at
~3 FLOPs/element of "chunk-wise sort" (GPU quasi-sort, [39]); the leader runs it
over its full error-feedback gradient every step and every worker runs the
gather at the selected offsets.

TPU adaptation (DESIGN.md §2): instead of porting a GPU bitonic sorting network,
the chunked top-1 selection is phrased as a masked arg-max over a 2-D VMEM
tile: no data-dependent control flow, MXU not needed. Reads at a
data-dependent lane offset are a one-hot compare-and-select followed by a lane
sum (``lane_pick``) or an in-vreg lane gather: Mosaic lowers no general
in-kernel gather. ``block_chunks`` — the chunks one grid step covers, in either
geometry below — is a static tuning knob swept by ``repro.backends.autotune``;
it must be a multiple of 1024 (see ``BLOCK_CHUNKS``).

Two tile geometries, chosen from static shapes (``lane_dense``):

  rows        the buffer viewed as (n_chunks, chunk), one chunk a tile row,
              per-chunk work as lane reductions. A chunk of 64 fills half of
              a row's 128 lanes, so the (n_chunks, 64) view is a padded
              relayout copy of the buffer in HBM and every tile moves twice
              its bytes; the launchers zero-pad the rows to a block multiple.
  lane-dense  the buffer viewed as (size/128, 128), 128/chunk chunks a row —
              a bitcast of the flat buffer, no copy. Selection transposes
              128-row slabs so each chunk runs down the sublanes of one lane
              and its arg-max is elementwise across vregs; the EF update and
              scatter spread per-chunk offsets over their lanes with lane
              gathers. Per-chunk (index, value) blocks stay 1-D and
              lane-dense in HBM; grids are ragged (``pl.cdiv``), so nothing
              is padded or sliced around the launch.

Kernel bodies (rows geometry, then lane-dense):

  _argmax_kernel   per-chunk top-1 (indices + values) — the CLT-k selector
  _topm_kernel     per-chunk top-m via m static masked-argmax passes (the
                   milder-rate path of the paper's §4 per-layer guidance)
  _gather_kernel   values at given per-chunk offsets (top-1 or top-m)
  _scatter_kernel  dense tile from per-chunk (offset, value) pairs
  _dense_select_kernel, _dense_gather_kernel, _dense_scatter_kernel
                   the same three jobs on lane-dense tiles, top-m included

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
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "BLOCK_CHUNKS",
    "lane_dense",
    "tile_view",
    "chunk_argmax_pallas",
    "chunk_topm_pallas",
    "chunk_gather_pallas",
    "chunk_scatter_pallas",
]

# Default chunks a grid step covers: a (BLOCK_CHUNKS, chunk) tile of the rows
# geometry, a (BLOCK_CHUNKS * chunk / 128, 128) lane-dense tile. The
# per-chunk (index, value) arrays are 1-D, and XLA lays a long 1-D TPU array
# out in 1024-element tiles; Mosaic refuses a 1-D block that is not a
# multiple of that tile, so every block is a multiple of 1024 chunks
# (autotune.CANDIDATE_BLOCKS). A 1024 x 64 fp32 rows tile is 512 KiB in VMEM
# (64-lane rows pad to 128 lanes), its lane-dense twin 256 KiB; both sit well
# inside the 16 MiB default scoped limit with double buffering.
BLOCK_CHUNKS = 1024

# Default for lane-dense launches: 4096 chunks of 64 make a 1 MiB tile, so
# the fixed cost of a grid step is a small share of the tile's DMA.
DENSE_BLOCK_CHUNKS = 4096

LANES = 128

# Smallest chunk the lane-dense tiles take: selection transposes 128-row
# slabs, and a block of 1024 chunks spans 1024 * chunk / 128 rows.
MIN_DENSE_CHUNK = 16


def lane_dense(chunk: int, width: int, size: int, dtype) -> bool:
    """Whether a chunk-aligned (..., width) buffer of ``size`` elements is
    streamed as lane-dense (size/128, 128) tiles.

    Static shapes decide, so there is no knob: fp32 data, a chunk of 16 to
    64 lanes that divides 128 (a 128-lane chunk fills its rows already),
    a trailing dim that is a multiple of 128 (so the view keeps every chunk
    inside one row) and whole (8, 128) tiles (so the view of the flat
    buffer is a bitcast). Everything else keeps the (n_chunks, chunk) rows.
    """
    return (
        jnp.dtype(dtype) == jnp.float32
        and MIN_DENSE_CHUNK <= chunk < LANES
        and LANES % chunk == 0
        and width % LANES == 0
        and size % (8 * LANES) == 0
    )


def tile_view(x: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """(..., Cp) with Cp % chunk == 0 -> the 2-D view the row launchers
    stream: (size/128, 128) where ``lane_dense`` holds, else
    (size/chunk, chunk). Both are row-major, so chunk c of the view is chunk
    c of the buffer's row-major chunk order."""
    if lane_dense(chunk, x.shape[-1], x.size, x.dtype):
        return x.reshape(-1, LANES)
    return x.reshape(-1, chunk)


# ---------------------------------------------------------------------------
# rows geometry: one (block_chunks, chunk) tile per grid step
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
# lane-dense geometry: one (block_chunks * chunk / 128, 128) tile per grid
# step, S = 128 / chunk chunks to a row. Chunk S*r + j of the block is row r,
# lanes [chunk*j, chunk*(j+1)). Its per-chunk blocks are 1-D (block_chunks,)
# refs, read and written as (block_chunks / 128, 128) compact arrays whose
# [q, p] entry is chunk 128*q + p: the chunks of tile rows chunk*q ...
# chunk*(q+1) - 1. Values move as int32 bits, so every pick is exact.
# ---------------------------------------------------------------------------


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _load_compact(ref):
    """1-D per-chunk block -> (block_chunks / 128, 128) compact array."""
    return ref[...].reshape(-1, LANES)


def _store_compact(ref, o, scratch):
    """Write a compact int32 array to a 1-D per-chunk block.

    Mosaic reshapes a (q, 128) value to 1-D only in the layout a load gives
    it, so the array goes through a VMEM scratch of the compact shape.
    """
    scratch[...] = o
    flat = scratch[...].reshape(-1)
    if ref.dtype != jnp.int32:
        flat = jax.lax.bitcast_convert_type(flat, ref.dtype)
    ref[...] = flat


def _rows_of(o, chunk):
    """(q, 128) compact -> (q * chunk, 128): tile row r holds the compact
    row its chunks sit in, o[r // chunk]."""
    q = o.shape[0]
    return jnp.broadcast_to(o[:, None, :], (q, chunk, LANES)).reshape(q * chunk, LANES)


def _spread(o_rows, chunk):
    """Per-chunk values onto every lane of their chunk.

    Tile row r = chunk*q + a holds chunks S*r + j, which sit in compact row
    q at lanes S*a + j; one in-vreg lane gather fetches them.
    """
    shape = o_rows.shape
    src = (LANES // chunk) * (_iota(shape, 0) % chunk) + _iota(shape, 1) // chunk
    return jnp.take_along_axis(o_rows, src, axis=1, mode="promise_in_bounds")


def _pick(xb, o_rows, chunk):
    """Compact values of the int32 tile ``xb`` at per-chunk lane offsets.

    out[q, p] = xb[chunk*q + p//S, chunk*(p%S) + o[q, p]]: a lane gather in
    every tile row, then the row each output lane belongs to is kept and
    summed down the chunk rows of its compact row (one term is non-zero).
    """
    shape = xb.shape
    s = LANES // chunk
    lanes = _iota(shape, 1)
    h = jnp.take_along_axis(
        xb, chunk * (lanes % s) + o_rows, axis=1, mode="promise_in_bounds"
    )
    h = jnp.where(_iota(shape, 0) % chunk == lanes // s, h, 0)
    return jnp.sum(h.reshape(-1, chunk, LANES), axis=1)


def _interleave(r, chunk):
    """(slabs, S, 128) per-slab results, r[v, j, x] for chunk j of tile row
    128*v + x -> compact (slabs * S, 128) in chunk order.

    Compact row v*S + q holds chunk j of row 128*v + chunk*q + a at lane
    S*a + j: each of the S result rows is lane-gathered into place for
    every q and the rows are merged under a lane mask.
    """
    slabs, s, _ = r.shape
    shape = (slabs, s, s, LANES)  # [v, q, j, p]
    p = _iota(shape, 3)
    src = (chunk * _iota(shape, 1) + p // s).reshape(-1, LANES)
    g = jnp.take_along_axis(
        jnp.broadcast_to(r[:, None], shape).reshape(-1, LANES), src,
        axis=1, mode="promise_in_bounds",
    )
    g = jnp.where((_iota(shape, 2) == p % s).reshape(-1, LANES), g, 0)
    return jnp.sum(g.reshape(slabs * s, s, LANES), axis=1)


def _dense_select_kernel(x_ref, *refs, chunk: int, topm: int):
    """x: lane-dense tile -> topm idx blocks, topm value blocks (1-D).

    Each 128-row slab is transposed so a chunk runs down the sublanes of one
    lane: t[v, j, i, x] is element i of chunk j in tile row 128*v + x. The
    arg-max is then a max and a min down those sublanes — elementwise across
    vregs — over int32 keys: |x|'s bits order like |x|, and clamping them at
    one NaN pattern ranks every NaN first and alike, so the lowest lane wins
    among equal maxima or NaNs, as ``lane_argmax`` defines.
    """
    idx_refs, val_refs, scratch = refs[:topm], refs[topm : 2 * topm], refs[-1]
    s = LANES // chunk
    slabs = x_ref.shape[0] // LANES
    t = jnp.swapaxes(_bits(x_ref[...]).reshape(slabs, LANES, LANES), 1, 2)
    t = t.reshape(slabs, s, chunk, LANES)
    key = jnp.minimum(t & 0x7FFFFFFF, 0x7F800001)
    sub = _iota(t.shape, 2)
    for j in range(topm):  # masked-argmax passes, ties to the lower lane
        top = jnp.max(key, axis=2, keepdims=True)
        pick = jnp.min(jnp.where(key == top, sub, chunk), axis=2, keepdims=True)
        hit = sub == pick
        val = jnp.sum(jnp.where(hit, t, 0), axis=2)
        pick = pick.reshape(slabs, s, LANES)
        _store_compact(idx_refs[j], _interleave(pick, chunk), scratch)
        _store_compact(val_refs[j], _interleave(val, chunk), scratch)
        if j + 1 < topm:
            key = jnp.where(hit, -1, key)


def _dense_gather_kernel(x_ref, *refs, chunk: int, topm: int):
    """x: lane-dense tile, topm idx blocks -> topm value blocks."""
    idx_refs, val_refs, scratch = refs[:topm], refs[topm : 2 * topm], refs[-1]
    xb = _bits(x_ref[...])
    for j in range(topm):
        at = _rows_of(_load_compact(idx_refs[j]) & (chunk - 1), chunk)
        _store_compact(val_refs[j], _pick(xb, at, chunk), scratch)


def _dense_scatter_kernel(*refs, chunk: int, topm: int):
    """topm value blocks, topm idx blocks -> lane-dense tile, zeros elsewhere."""
    val_refs, idx_refs, out_ref = refs[:topm], refs[topm : 2 * topm], refs[-1]
    lane = _iota(out_ref.shape, 1) % chunk
    out = jnp.zeros(out_ref.shape, jnp.int32)
    for j in range(topm):  # top-m: selected offsets are distinct
        at = _spread(_rows_of(_load_compact(idx_refs[j]) & (chunk - 1), chunk), chunk)
        val = _spread(_rows_of(_bits(_load_compact(val_refs[j])), chunk), chunk)
        out = jnp.where(at == lane, val, out)
    out_ref[...] = jax.lax.bitcast_convert_type(out, out_ref.dtype)


def dense_block(n: int, block_chunks: int) -> int:
    """Chunks a lane-dense grid step covers for n chunks: ``block_chunks``,
    cut to the 1024-multiple that holds all n when that is smaller."""
    return min(block_chunks, -(-n // BLOCK_CHUNKS) * BLOCK_CHUNKS)


def dense_specs(chunk: int, block_chunks: int):
    """(tile spec, 1-D per-chunk spec, compact scratch) of a lane-dense launch."""
    return (
        pl.BlockSpec((block_chunks * chunk // LANES, LANES), lambda i: (i, 0)),
        pl.BlockSpec((block_chunks,), lambda i: (i,)),
        pltpu.VMEM((block_chunks // LANES, LANES), jnp.int32),
    )


def compact_len(n: int) -> int:
    """Length of a lane-dense launch's 1-D per-chunk arrays for n chunks.

    XLA tiles a 1-D array of 1024 elements or more by 1024, like the
    blocks; a shorter one gets a smaller tile that no block matches, so it
    is zero-padded to one block (a tensor under 1024 chunks: a few KiB).
    """
    return max(n, BLOCK_CHUNKS)


def split_picks(a: jnp.ndarray, topm: int):
    """(n,) or (n, topm) per-chunk array -> topm 1-D arrays of
    ``compact_len(n)``."""
    parts = [a] if topm == 1 else [a[:, j] for j in range(topm)]
    pad = compact_len(a.shape[0]) - a.shape[0]
    return [jnp.pad(p, (0, pad)) for p in parts] if pad else parts


def join_picks(parts, n: int):
    """Inverse of ``split_picks``: the first n entries, (n,) or (n, topm)."""
    parts = [p[:n] for p in parts] if parts[0].shape[0] != n else parts
    return parts[0] if len(parts) == 1 else jnp.stack(parts, axis=-1)


def _dense_select(x2d, chunk, topm, interpret, block_chunks):
    n = x2d.shape[0] * (LANES // chunk)
    n_out = compact_len(n)
    block_chunks = dense_block(n, block_chunks)
    tile, per_chunk, scratch = dense_specs(chunk, block_chunks)
    outs = pl.pallas_call(
        functools.partial(_dense_select_kernel, chunk=chunk, topm=topm),
        grid=(pl.cdiv(n, block_chunks),),
        in_specs=[tile],
        out_specs=[per_chunk] * (2 * topm),
        out_shape=[jax.ShapeDtypeStruct((n_out,), jnp.int32)] * topm
        + [jax.ShapeDtypeStruct((n_out,), x2d.dtype)] * topm,
        scratch_shapes=[scratch],
        interpret=interpret,
    )(x2d)
    return join_picks(outs[:topm], n), join_picks(outs[topm:], n)


def _dense_gather(x2d, idx, chunk, interpret, block_chunks):
    topm = 1 if idx.ndim == 1 else idx.shape[1]
    n = x2d.shape[0] * (LANES // chunk)
    block_chunks = dense_block(n, block_chunks)
    tile, per_chunk, scratch = dense_specs(chunk, block_chunks)
    vals = pl.pallas_call(
        functools.partial(_dense_gather_kernel, chunk=chunk, topm=topm),
        grid=(pl.cdiv(n, block_chunks),),
        in_specs=[tile] + [per_chunk] * topm,
        out_specs=[per_chunk] * topm,
        out_shape=[jax.ShapeDtypeStruct((compact_len(n),), x2d.dtype)] * topm,
        scratch_shapes=[scratch],
        interpret=interpret,
    )(x2d, *split_picks(idx, topm))
    return join_picks(vals, n)


def _dense_scatter(vals, idx, chunk, interpret, block_chunks):
    topm = 1 if idx.ndim == 1 else idx.shape[1]
    n = idx.shape[0]
    block_chunks = dense_block(n, block_chunks)
    tile, per_chunk, _ = dense_specs(chunk, block_chunks)
    return pl.pallas_call(
        functools.partial(_dense_scatter_kernel, chunk=chunk, topm=topm),
        grid=(pl.cdiv(n, block_chunks),),
        in_specs=[per_chunk] * (2 * topm),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n * chunk // LANES, LANES), vals.dtype),
        interpret=interpret,
    )(*split_picks(vals, topm), *split_picks(idx, topm))


# ---------------------------------------------------------------------------
# row launchers: a 2-D ``tile_view`` in — (rows, chunk), or lane-dense
# (rows, 128) when its width exceeds the chunk — grid/padding handled here.
# Shared by the flat wrappers below and the trailing-axis wrappers in
# kernels.rowwise. Per-chunk outputs are (n_chunks,) or (n_chunks, topm).
# ---------------------------------------------------------------------------


def _padded_rows(n_rows: int, block_chunks: int) -> int:
    return -(-n_rows // block_chunks) * block_chunks


def _pad_rows(x2d: jnp.ndarray, block_chunks: int) -> jnp.ndarray:
    pad = _padded_rows(x2d.shape[0], block_chunks) - x2d.shape[0]
    if pad:
        widths = ((0, pad),) + ((0, 0),) * (x2d.ndim - 1)
        x2d = jnp.pad(x2d, widths)
    return x2d


def row_select(x2d, chunk, *, topm, interpret, block_chunks):
    """Tile view -> per-chunk top-m (idx, vals); (n_chunks,) when topm == 1."""
    if x2d.shape[1] != chunk:
        return _dense_select(x2d, chunk, topm, interpret, block_chunks)
    n_rows = x2d.shape[0]
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


def row_gather(x2d, idx, chunk, *, interpret, block_chunks):
    """Tile view, idx (n_chunks,) or (n_chunks, m) -> values shaped like idx."""
    if x2d.shape[1] != chunk:
        return _dense_gather(x2d, idx, chunk, interpret, block_chunks)
    n_rows = x2d.shape[0]
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


def row_scatter(vals, idx, chunk, width, *, interpret, block_chunks):
    """vals/idx (n_chunks,) or (n_chunks, m) -> the dense tile view of
    ``width`` (chunk, or 128 for lane-dense tiles)."""
    if width != chunk:
        return _dense_scatter(vals, idx, chunk, interpret, block_chunks)
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


def _flat_view(x: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Flat (n,) -> its tile view, zero-padded to a chunk multiple."""
    pad = (-x.shape[-1]) % chunk
    if pad:
        x = jnp.pad(x.reshape(-1), (0, pad))
    return tile_view(x.reshape(-1), chunk)


def scatter_width(chunk: int, width: int, size: int, dtype) -> int:
    """Tile width a scatter into a (..., width) buffer of ``size`` writes."""
    return LANES if lane_dense(chunk, width, size, dtype) else chunk


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
    return row_select(
        _flat_view(x, chunk), chunk, topm=1, interpret=interpret,
        block_chunks=block_chunks,
    )


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
    return row_select(
        _flat_view(x, chunk), chunk, topm=topm, interpret=interpret,
        block_chunks=block_chunks,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "block_chunks"))
def chunk_gather_pallas(
    x: jnp.ndarray, idx: jnp.ndarray, chunk: int, *, interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Gather per-chunk values of flat ``x`` at offsets ``idx`` ((n_chunks,)
    or (n_chunks, m))."""
    return row_gather(
        _flat_view(x, chunk), idx, chunk, interpret=interpret,
        block_chunks=block_chunks,
    )


@functools.partial(
    jax.jit, static_argnames=("chunk", "size", "interpret", "block_chunks")
)
def chunk_scatter_pallas(
    vals: jnp.ndarray, idx: jnp.ndarray, chunk: int, size: int, *,
    interpret: bool = True, block_chunks: int = BLOCK_CHUNKS,
):
    """Dense flat (size,) array with per-chunk ``vals`` at offsets ``idx``."""
    cp = idx.shape[0] * chunk
    out = row_scatter(
        vals, idx, chunk, scatter_width(chunk, cp, cp, vals.dtype),
        interpret=interpret, block_chunks=block_chunks,
    )
    return out.reshape(-1)[:size]
