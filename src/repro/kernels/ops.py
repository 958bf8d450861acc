"""1-D convenience wrappers over the flat Pallas kernels.

The execution mode follows the default JAX platform: on a TPU the kernels are
compiled by Mosaic; on any other platform (the CPU tests) they run in
interpret mode, where XLA evaluates the kernel bodies — the same math, checked
against repro.kernels.ref by tests/test_kernels.py. Native compilation is
checked by tests/test_tpu_compile.py and on the chip by ``chip_smoke.py``.

These are the thin 1-D convenience entry points. Production dispatch —
jnp-vs-pallas selection, autotuned tile geometry, batched worker axes, and the
rowwise layout — goes through ``repro.backends`` (resolve_backend), which is
what ``scalecom_reduce`` uses.
"""

from __future__ import annotations

import jax

from repro.kernels import chunk_topk as _ct
from repro.kernels import ef_update as _ef

__all__ = [
    "chunk_argmax",
    "chunk_select",
    "chunk_topm",
    "chunk_gather",
    "chunk_scatter",
    "ef_update",
    "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def chunk_select(x, chunk: int):
    """Per-chunk (indices, values) magnitude selection of a flat array."""
    return _ct.chunk_argmax_pallas(x, chunk, interpret=not on_tpu())


def chunk_argmax(x, chunk: int):
    """Indices only (the CLT-k leader's selection pass)."""
    return _ct.chunk_argmax_pallas(x, chunk, interpret=not on_tpu())[0]


def chunk_topm(x, chunk: int, topm: int):
    """Per-chunk top-m (indices, values), each (n_chunks, topm)."""
    return _ct.chunk_topm_pallas(x, chunk, topm, interpret=not on_tpu())


def chunk_gather(x, idx, chunk: int):
    return _ct.chunk_gather_pallas(x, idx, chunk, interpret=not on_tpu())


def chunk_scatter(vals, idx, chunk: int, size: int):
    """Dense flat (size,) with per-chunk values at idx, zeros elsewhere."""
    return _ct.chunk_scatter_pallas(vals, idx, chunk, size, interpret=not on_tpu())


def ef_update(m, g, idx, beta: float, chunk: int):
    """Fused low-pass residue update: (m_new, vals)."""
    return _ef.ef_update_pallas(m, g, idx, beta, chunk, interpret=not on_tpu())
