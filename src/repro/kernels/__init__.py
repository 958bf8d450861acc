"""Pallas TPU kernels for ScaleCom's compute hot spot (chunk-wise selection,
Table 1: ~3 FLOPs/element) and the fused residue update.

Each kernel: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd 1-D wrapper
with CPU interpret fallback), ref.py (pure-jnp oracle), rowwise.py (the
trailing-axis launchers every backend op routes through — one surface for
both the flat and the layout-preserving layouts, top-1 and top-m). Production
dispatch goes through repro.backends (resolve_backend); the chunks a grid
step covers are the constants of chunk_topk (``BLOCK_CHUNKS``,
``DENSE_BLOCK_CHUNKS``).
"""
