"""Explicit shard_map CLT-k all-reduce — the paper's Remark 3 ("naturally
extends to ring all-reduce settings") as a manual-collective backend.

The primary runtime (repro.training.train_step) expresses ScaleCom in pure
GSPMD; this module is the dual formulation with hand-written collectives
inside ``shard_map`` (through the compat layer): each device holds ITS
worker's error-feedback state
and gradient shard, and the only collectives are

    psum(masked index row)   — the leader's O(k) index broadcast
    psum(gathered values)/n  — the k-element compressed ring all-reduce

On TPU ``lax.psum`` lowers to the ring/tree all-reduce of the target platform,
which is exactly the paper's integration point. Useful for (a) validating the
GSPMD path against an independent implementation (tests/test_distributed.py)
and (b) deployments that prefer manual collectives over compiler-inferred ones.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.compat import jax_compat
from repro.core import chunked
from repro.core.compressors import CompressorConfig

Array = jnp.ndarray

__all__ = ["clt_ring_reduce", "make_ring_reducer"]


def clt_ring_reduce(
    g_local: Array,
    m_local: Array,
    t: Array,
    cfg: CompressorConfig,
    beta: float,
    axis_name: str,
) -> Tuple[Array, Array]:
    """One tensor through Algorithm 1, called INSIDE shard_map over
    ``axis_name`` (one ScaleCom worker per device along that axis).

    g_local/m_local: this worker's flat (size,) gradient / residue.
    Returns (ghat_dense, m_new) — ghat identical on every worker (psum'd).
    """
    n = jax_compat.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    leader = jnp.mod(t, n)
    size = g_local.shape[-1]

    ef = m_local + g_local
    my_idx = chunked.chunk_argmax(ef, cfg.chunk)
    # O(k) index broadcast: only the leader contributes, psum distributes
    idx = jax.lax.psum(jnp.where(me == leader, my_idx, 0), axis_name)
    vals = chunked.chunk_gather(ef, idx, cfg.chunk)
    # the compressed ring all-reduce: k values, constant in n
    vmean = jax.lax.psum(vals, axis_name) / n
    ghat = chunked.chunk_scatter(vmean, idx, cfg.chunk, size)
    own = chunked.chunk_scatter(vals, idx, cfg.chunk, size)
    m_new = m_local + beta * (g_local - own)
    return ghat, m_new


def make_ring_reducer(mesh, axis_name: str, cfg: CompressorConfig, beta: float):
    """shard_map-wrapped reducer over worker-stacked (n, size) tensors.

    Maps the leading worker dim onto ``axis_name``; inside, each device sees
    its own (size,) row and runs the manual Algorithm 1.
    """
    P = jax_compat.P

    def per_device(g_row, m_row, t):
        ghat, m_new = clt_ring_reduce(
            g_row[0], m_row[0], t, cfg, beta, axis_name
        )
        return ghat[None], m_new[None]

    return jax_compat.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name, None), P()),
        out_specs=(P(axis_name, None), P(axis_name, None)),
    )
