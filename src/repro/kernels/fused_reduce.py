"""Pallas TPU kernel: the single-launch fused reduce (select → EF → scatter).

The pallas backend's per-tensor inner loop used to be three kernel launches —
worker-stacked chunk select, fused Eq. 5 residue update, ĝ scatter — plus the
``ef = m + g`` materialization in between, each pass re-streaming the same
chunk tiles from HBM (~7 passes over the G×P worker-stacked bytes per step;
see ``analysis.perfmodel.reduce_hbm_passes``). This kernel runs all three
phases over ONE VMEM-resident tile per grid step:

  phase 1  top-m index select over the worker-stacked EF gradients
           (clt_k: masked-argmax passes over the leader's EF rows, picked by
           a one-hot worker mask — bitwise-identical to
           ``compressors.leader_pick`` over the 3-launch select; true_topk:
           the same passes over the worker mean)
  phase 2  residue (EF) update with codec-aware write-back — the m' tile the
           kernel writes is exactly what ``codec.encode`` consumes (for the
           fp32 codec the encode is a reshape, so this write IS the stored
           residue; lossy codecs re-quantize downstream, same as 3-launch)
  phase 3  ĝ scatter of the worker-mean values at the shared index set

so ef never exists in HBM and (m, g) are read once: ~3 passes instead of ~7.

Tiles are (G, block_chunks, chunk): the FULL worker axis rides in every tile
because both selection modes need all workers of a chunk row resident
(leader pick / worker mean). ``block_chunks`` comes from the autotune cache
("fused_reduce" op, falling back to the ef_update op's tuned tile).

Double-buffered DMA: the grid iterates over row blocks and every operand's
BlockSpec maps grid step i to a disjoint HBM slab, which is exactly the shape
Pallas's grid pipelining automates — the (i+1)-th tile's HBM→VMEM copies are
issued while the i-th tile's phases compute, no manual ``make_async_copy``
needed (see the pipelining section of the Pallas TPU guide). The kernel body
stays pure tile math.

The leader is a *traced* scalar (t mod G changes every step); it enters as a
(G, chunk) int32 one-hot mask operand — 2-D so it tiles legally on real TPU
(1-D operands with degenerate BlockSpecs do not; same lesson as ef_update's
static beta). The kernel sums the worker tile under that mask, which yields
the leader's EF rows exactly, and selects from them: the in-tile form of
``leader_pick``, since only the leader's candidates are ever kept.

Validated against the composed 3-op path (bitwise indices, allclose values)
in tests/test_backends.py; the 1-launch property is asserted by the
launch-count tripwire in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunk_topk import BLOCK_CHUNKS, _padded_rows, lane_argmax

__all__ = ["FUSABLE_MODES", "fused_reduce_trailing", "row_fused_reduce"]

# Selection modes the fused kernel implements. local_topk (per-worker index
# sets) and random_k (counter-PRNG draws, not reproducible in-tile) fall back
# to the 3-launch path — backends.base.fused_reduce documents the contract.
FUSABLE_MODES = ("clt_k", "true_topk")

# Scoped VMEM the fused kernel may use. The default 16 MiB holds the
# double-buffered (G, block_chunks, chunk) m/g/m' tiles for only a few
# workers (64-lane chunk rows pad to 128 lanes: 24 MiB at G=8 with 1024-row
# blocks); a TPU v5e core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 100 * 2**20


def _fused_kernel(
    m_ref, g_ref, wmask_ref, idx_ref, val_ref, m_out_ref, ghat_ref,
    *, beta: float, topm: int, mode: str,
):
    """One (G, B, C) tile through all three phases (see module docstring).

    Every reduction runs over the lane axis of a (B, C) or (G, B, C) tile
    or over the untiled worker axis, so Mosaic never has to relayout a
    per-row result against a worker-broadcast operand.
    """
    m = m_ref[...]          # (G, B, C)
    g = g_ref[...]
    ef = m + g              # lives only in VMEM — never materialized in HBM
    zero = jnp.zeros((), ef.dtype)

    # --- phase 1: shared top-m index select ------------------------------
    # the (B, C) tile the shared index set is chosen from: the worker mean
    # (true_topk) or the leader's own EF rows (clt_k)
    if mode == "true_topk":
        sel = jnp.mean(ef, axis=0)
    else:
        lead = wmask_ref[...][:, None, :] != 0                      # (G, 1, C)
        sel = jnp.sum(jnp.where(lead, ef, zero), axis=0)
    mag = jnp.abs(sel)
    cols2 = jax.lax.broadcasted_iota(jnp.int32, mag.shape, 1)
    neg = jnp.full((), -1.0, mag.dtype)
    picks = []
    for _ in range(topm):  # masked-argmax passes, ties to the lower lane
        ij = lane_argmax(mag)                                       # (B,)
        picks.append(ij)
        mag = jnp.where(cols2 == ij[:, None], neg, mag)

    # --- phase 2: gather + Eq. 5 residue update (codec-aware write-back) --
    # (one-hot select + lane sum: Mosaic has no in-kernel gather)
    cols3 = jax.lax.broadcasted_iota(jnp.int32, ef.shape, 2)
    own = None
    for j, ij in enumerate(picks):  # top-m: selected offsets are distinct
        own_j = jnp.where(cols3 == ij[None, :, None], ef, zero)
        vals_j = jnp.sum(own_j, axis=-1)                            # (G, B)
        if topm == 1:
            idx_ref[...] = ij
            val_ref[...] = vals_j
        else:
            idx_ref[:, j] = ij
            val_ref[:, :, j] = vals_j
        own = own_j if own is None else own + own_j
    m_out_ref[...] = m + beta * (g - own)

    # --- phase 3: ĝ scatter of the k-value worker mean --------------------
    # own is ef at the selected lanes and zero elsewhere, so its worker mean
    # is the scatter of mean(vals) — without a per-row relayout
    ghat_ref[...] = jnp.mean(own, axis=0)


def _pad_rows3(x3, block_chunks: int):
    """Zero-pad the row axis (axis 1) of a (G, rows, ...) stack."""
    pad = _padded_rows(x3.shape[1], block_chunks) - x3.shape[1]
    if pad:
        widths = ((0, 0), (0, pad)) + ((0, 0),) * (x3.ndim - 2)
        x3 = jnp.pad(x3, widths)
    return x3


def row_fused_reduce(m3, g3, wmask, beta, *, topm, mode, interpret, block_chunks):
    """(G, rows, chunk) m/g + (G, chunk) leader one-hot -> all four outputs.

    Grid over row blocks with the full worker axis resident per tile; padded
    rows are all-zero (argmax 0, value 0, ghat 0 — sliced off below). Returns
    (idx (rows[, topm]), vals (G, rows[, topm]), m_new (G, rows, chunk),
    ghat (rows, chunk)).
    """
    G, n_rows, chunk = m3.shape
    mp = _pad_rows3(m3, block_chunks)
    gp = _pad_rows3(g3, block_chunks)
    rows = mp.shape[1]
    grid = rows // block_chunks
    data_spec = pl.BlockSpec((G, block_chunks, chunk), lambda i: (0, i, 0))
    if topm == 1:
        idx_block, idx_shape = (block_chunks,), (rows,)
        idx_map = lambda i: (i,)  # noqa: E731
        val_block, val_shape = (G, block_chunks), (G, rows)
        val_map = lambda i: (0, i)  # noqa: E731
    else:
        idx_block, idx_shape = (block_chunks, topm), (rows, topm)
        idx_map = lambda i: (i, 0)  # noqa: E731
        val_block, val_shape = (G, block_chunks, topm), (G, rows, topm)
        val_map = lambda i: (0, i, 0)  # noqa: E731
    idx, vals, m_new, ghat = pl.pallas_call(
        functools.partial(
            _fused_kernel, beta=float(beta), topm=topm, mode=mode
        ),
        grid=(grid,),
        in_specs=[
            data_spec,
            data_spec,
            pl.BlockSpec((G, chunk), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(idx_block, idx_map),
            pl.BlockSpec(val_block, val_map),
            data_spec,
            pl.BlockSpec((block_chunks, chunk), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(idx_shape, jnp.int32),
            jax.ShapeDtypeStruct(val_shape, m3.dtype),
            jax.ShapeDtypeStruct((G, rows, chunk), m3.dtype),
            jax.ShapeDtypeStruct((rows, chunk), m3.dtype),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(mp, gp, wmask)
    return idx[:n_rows], vals[:, :n_rows], m_new[:, :n_rows], ghat[:n_rows]


@functools.partial(
    jax.jit,
    static_argnames=("beta", "chunk", "topm", "mode", "interpret", "block_chunks"),
)
def fused_reduce_trailing(
    m: jnp.ndarray,
    g: jnp.ndarray,
    leader: jnp.ndarray,
    beta: float,
    chunk: int,
    topm: int = 1,
    mode: str = "clt_k",
    *,
    interpret: bool = True,
    block_chunks: int = BLOCK_CHUNKS,
):
    """Single-launch fused reduce along the trailing axis.

    m, g: (G, ..., Cp) worker-stacked with Cp % chunk == 0 (pre-padded —
    core.chunked.pad_to_chunks); leader: traced int32 scalar, the clt_k
    leader rank (ignored for mode="true_topk"); beta/topm/mode static.

    Returns (idx, vals, m_new, ghat):
      idx    (..., Cp/chunk[, topm])       shared index set (no worker axis)
      vals   (G, ..., Cp/chunk[, topm])    per-worker values at idx
      m_new  (G, ..., Cp)                  Eq. 5 residue update
      ghat   (..., Cp)                     dense scatter of the value mean
    """
    if mode not in FUSABLE_MODES:
        raise ValueError(
            f"fused kernel supports modes {FUSABLE_MODES}, got {mode!r} "
            "(other compressors take the 3-launch path)"
        )
    cp = m.shape[-1]
    if cp % chunk:
        raise ValueError(
            f"trailing-axis kernels need the last dim pre-padded to the chunk "
            f"size (got {cp} % {chunk} != 0); call core.chunked.pad_to_chunks "
            f"first"
        )
    G = m.shape[0]
    lead = m.shape[1:-1]
    ncr = cp // chunk
    wmask = jnp.broadcast_to(
        (jnp.arange(G) == leader).astype(jnp.int32)[:, None], (G, chunk)
    )
    idx, vals, m_new, ghat = row_fused_reduce(
        m.reshape(G, -1, chunk),
        g.reshape(G, -1, chunk),
        wmask,
        beta,
        topm=topm,
        mode=mode,
        interpret=interpret,
        block_chunks=block_chunks,
    )
    tail = () if topm == 1 else (topm,)
    return (
        idx.reshape(lead + (ncr,) + tail),
        vals.reshape((G,) + lead + (ncr,) + tail),
        m_new.reshape(m.shape),
        ghat.reshape(lead + (cp,)),
    )
