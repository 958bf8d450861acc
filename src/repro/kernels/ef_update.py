"""Pallas TPU kernel: fused error-feedback residue update (beyond-paper).

Per step, for each worker and each chunk c of its error-feedback gradient
ef = m + g, ScaleCom needs:

    vals[c]   = ef[c, idx[c]]                    (contribution to the reduce)
    m'[c, j]  = m[c, j] + beta*(g[c, j] - vals[c]*[j == idx[c]])   (Eq. 5)

Unfused HLO runs 3+ passes over the gradient (add, gather, scatter, axpy) —
each HBM-bandwidth bound. This kernel does one read of (m, g, idx) and one
write of (m', vals) per tile: ~2.3x less HBM traffic for the residue update,
which matters because the residue array is n_workers x P — the largest state
in the system (measured sweep: benchmarks/bench_kernels.py). Tiles follow
chunk_topk's two geometries — (block_chunks, chunk) rows, or lane-dense
(block_chunks * chunk / 128, 128) tiles where ``chunk_topk.lane_dense``
holds, with the shared per-chunk offsets spread over their chunk's lanes by
in-vreg lane gathers; ``block_chunks`` is autotuned by
repro.backends.autotune.

``beta`` is a *static* kernel parameter, closed over with functools.partial
and folded into the tile arithmetic at compile time. (It used to be passed as
a (1,) VMEM operand with a degenerate BlockSpec, which does not tile on real
TPU — sub-(8,128) blocks of a 1-D operand have no legal layout; scalars
belong in SMEM or, as here, in the kernel closure since beta is a per-run
config constant.)

Top-m per chunk (idx (n_chunks, m)) is fused the same way: m static one-hot
accumulation passes, matching chunk_topk._scatter_kernel.

Validated against the pure-jnp oracle in tests/test_kernels.py and, through
the backend dispatch layer, tests/test_backends.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.chunk_topk import (
    BLOCK_CHUNKS,
    _bits,
    _flat_view,
    _iota,
    _load_compact,
    _pad_rows,
    _pick,
    _rows_of,
    _spread,
    _store_compact,
    compact_len,
    dense_block,
    dense_specs,
    join_picks,
    split_picks,
)

__all__ = ["ef_update_pallas"]


def _ef_update_kernel(m_ref, g_ref, idx_ref, m_out_ref, val_ref, *, beta: float):
    m = m_ref[...]
    g = g_ref[...]
    idx = idx_ref[...]
    ef = m + g
    cols = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    zero = jnp.zeros((), ef.dtype)
    if idx.ndim == 1:
        own = jnp.where(cols == idx[:, None], ef, zero)
        val_ref[...] = jnp.sum(own, axis=-1)
    else:
        own = jnp.zeros(m.shape, ef.dtype)
        for j in range(idx.shape[1]):  # top-m: selected offsets are distinct
            own_j = jnp.where(cols == idx[:, j : j + 1], ef, zero)
            val_ref[:, j] = jnp.sum(own_j, axis=-1)
            own = own + own_j
    # ghat_own = vals scattered at idx; m' = m + beta*(g - ghat_own)
    m_out_ref[...] = m + beta * (g - own)


def _dense_ef_update_kernel(m_ref, g_ref, *refs, beta: float, chunk: int, topm: int):
    """Lane-dense tiles of m, g + topm idx blocks -> m' tile, topm value blocks."""
    idx_refs, m_out_ref = refs[:topm], refs[topm]
    val_refs, scratch = refs[topm + 1 : 2 * topm + 1], refs[-1]
    m = m_ref[...]
    g = g_ref[...]
    ef = m + g
    efb = _bits(ef)
    lane = _iota(m.shape, 1) % chunk
    hit = None
    for j in range(topm):  # top-m: selected offsets are distinct
        at = _rows_of(_load_compact(idx_refs[j]) & (chunk - 1), chunk)
        _store_compact(val_refs[j], _pick(efb, at, chunk), scratch)
        own = _spread(at, chunk) == lane
        hit = own if hit is None else hit | own
    # ghat_own = vals scattered at idx; m' = m + beta*(g - ghat_own)
    m_out_ref[...] = m + beta * (g - jnp.where(hit, ef, jnp.zeros((), ef.dtype)))


def _dense_ef_update(m2d, g2d, idx, beta, chunk, interpret, block_chunks):
    topm = 1 if idx.ndim == 1 else idx.shape[1]
    n = idx.shape[0]
    block_chunks = dense_block(n, block_chunks)
    tile, per_chunk, scratch = dense_specs(chunk, block_chunks)
    outs = pl.pallas_call(
        functools.partial(
            _dense_ef_update_kernel, beta=float(beta), chunk=chunk, topm=topm
        ),
        grid=(pl.cdiv(n, block_chunks),),
        in_specs=[tile, tile] + [per_chunk] * topm,
        out_specs=[tile] + [per_chunk] * topm,
        out_shape=[jax.ShapeDtypeStruct(m2d.shape, m2d.dtype)]
        + [jax.ShapeDtypeStruct((compact_len(n),), m2d.dtype)] * topm,
        scratch_shapes=[scratch],
        interpret=interpret,
    )(m2d, g2d, *split_picks(idx, topm))
    return outs[0], join_picks(outs[1:], n)


def row_ef_update(m2d, g2d, idx, beta, chunk, *, interpret, block_chunks):
    """Tile views of m/g + per-chunk idx -> (m' in the same view, vals);
    grid/padding here.

    Shared by the flat wrapper below and kernels.rowwise.ef_update_trailing.
    """
    if m2d.shape[1] != chunk:
        return _dense_ef_update(m2d, g2d, idx, beta, chunk, interpret, block_chunks)
    n_rows = m2d.shape[0]
    mp = _pad_rows(m2d, block_chunks)
    gp = _pad_rows(g2d, block_chunks)
    idxp = _pad_rows(idx, block_chunks)
    rows = mp.shape[0]
    grid = rows // block_chunks
    if idx.ndim == 1:
        aux_block, val_shape = (block_chunks,), (rows,)
        aux_map = lambda i: (i,)  # noqa: E731
    else:
        aux_block, val_shape = (block_chunks, idx.shape[1]), (rows, idx.shape[1])
        aux_map = lambda i: (i, 0)  # noqa: E731
    m_new, vals = pl.pallas_call(
        functools.partial(_ef_update_kernel, beta=float(beta)),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_chunks, chunk), lambda i: (i, 0)),
            pl.BlockSpec((block_chunks, chunk), lambda i: (i, 0)),
            pl.BlockSpec(aux_block, aux_map),
        ],
        out_specs=[
            pl.BlockSpec((block_chunks, chunk), lambda i: (i, 0)),
            pl.BlockSpec(aux_block, aux_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, chunk), m2d.dtype),
            jax.ShapeDtypeStruct(val_shape, m2d.dtype),
        ],
        interpret=interpret,
    )(mp, gp, idxp)
    return m_new[:n_rows], vals[:n_rows]


@functools.partial(
    jax.jit, static_argnames=("beta", "chunk", "interpret", "block_chunks")
)
def ef_update_pallas(
    m: jnp.ndarray,
    g: jnp.ndarray,
    idx: jnp.ndarray,
    beta: float,
    chunk: int,
    *,
    interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Fused low-pass residue update for one worker's flat tensors.

    m, g: (size,) fp32; idx: (n_chunks,) or (n_chunks, m) int32 shared indices.
    beta is static (baked into the kernel). Returns (m_new (size,), vals).
    """
    n = m.shape[-1]
    m_new, vals = row_ef_update(
        _flat_view(m, chunk), _flat_view(g, chunk), idx, beta, chunk,
        interpret=interpret, block_chunks=block_chunks,
    )
    return m_new.reshape(-1)[:n], vals
