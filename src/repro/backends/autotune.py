"""Tile-geometry autotuner for the Pallas kernel backend.

``block_chunks`` is the number of chunks one grid step of a chunk kernel
covers, in either tile geometry (kernels.chunk_topk): a (block_chunks,
chunk) tile of the rows geometry, a (block_chunks * chunk / 128, 128)
lane-dense tile. The right value depends on chunk size, dtype itemwidth
(bf16 tiles are (16,128) vs fp32 (8,128)), problem size, and the device
generation's VMEM budget. This module sweeps the candidate values on the
live device and caches the winner on disk keyed by device kind, so the
sweep runs once per (device, op, chunk, dtype, size-bucket) and every later
process start is a dict lookup.

Cache file: ``$SCALECOM_AUTOTUNE_CACHE`` if set, else
``~/.cache/scalecom/autotune.json``. Entries are plain JSON so they can be
shipped with a container image or inspected by hand:

    {"TPU v5e|select|c64|float32|nc16384": 512, ...}

``best_block_chunks`` is the cheap read path the PallasBackend consults on
every launch (never triggers timing; returns the kernel default on a miss).
``autotune`` is the explicit write path (benchmarks/bench_kernels.py and the
--autotune flag of repro.launch.train drive it). On CPU the kernels run in
interpret mode, so timings there rank Python overhead, not HBM traffic —
autotune still functions (it is how the cache plumbing is tested) but the
numbers only mean something on a real accelerator.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "CANDIDATE_BLOCKS",
    "autotune",
    "autotune_params",
    "best_block_chunks",
    "cache_path",
    "clear_cache",
]

# Tile heights to sweep: multiples of the 1024-element tile XLA gives the
# kernels' 1-D per-row outputs on TPU (Mosaic refuses any other 1-D block;
# see chunk_topk.BLOCK_CHUNKS, which is included by construction).
CANDIDATE_BLOCKS: Tuple[int, ...] = (1024, 2048, 4096)

_OPS = ("select", "ef_update", "fused_reduce")

# Tile-geometry fallback chain: an op with no cache entry of its own borrows
# the tuned tile of the op it most resembles before giving up to the kernel
# default. fused_reduce streams the same chunks per grid step as ef_update
# (just with the worker axis resident), so an ef_update sweep is a far
# better prior than the untuned default.
_TILE_FALLBACK = {"fused_reduce": "ef_update"}

_cache: Optional[Dict[str, int]] = None  # in-process mirror of the file


def cache_path() -> str:
    env = os.environ.get("SCALECOM_AUTOTUNE_CACHE", "").strip()
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "scalecom", "autotune.json"
    )


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def _bucket(n_chunks: int) -> int:
    """Power-of-two size bucket: tile choice is insensitive to ±2x size."""
    return 1 << max(0, n_chunks - 1).bit_length()


def _key(op: str, chunk: int, dtype, n_chunks: int) -> str:
    return f"{_device_kind()}|{op}|c{chunk}|{jnp.dtype(dtype).name}|nc{_bucket(n_chunks)}"


def _load() -> Dict[str, int]:
    """Read the on-disk cache into the in-process mirror.

    Tolerant of a corrupt/truncated/mistyped JSON file (e.g. a concurrent
    writer on a filesystem without atomic rename, or a hand-edit gone wrong):
    any parse failure degrades to an empty cache — ``best_block_chunks``
    falls back to the kernel default and ``autotune`` re-sweeps — instead of
    poisoning every launch with an exception.
    """
    global _cache
    if _cache is None:
        try:
            with open(cache_path()) as f:
                _cache = {str(k): int(v) for k, v in json.load(f).items()}
        except (OSError, ValueError, TypeError, AttributeError):
            _cache = {}
    return _cache


def _store(key: str, block: int) -> None:
    cache = _load()
    cache[key] = block
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # Atomic publish: write a private temp file, then os.replace it over
        # the cache. Concurrent training processes sharing
        # $SCALECOM_AUTOTUNE_CACHE then never observe a truncated JSON —
        # last-writer-wins on whole files, and readers either see the old
        # complete cache or the new complete cache.
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS: keep the in-process cache only


def clear_cache() -> None:
    """Drop the in-process mirror (tests; the file is left alone)."""
    global _cache
    _cache = None


def best_block_chunks(
    op: str, n_chunks: int, chunk: int, dtype, default: Optional[int] = None
) -> int:
    """Cached chunks a grid step of ``op`` covers, or ``default`` on a miss
    (the kernels' ``BLOCK_CHUNKS`` unless given: the launch's geometry may
    want its own, see ``PallasBackend._block``).

    Cheap enough for the per-launch dispatch path: one dict lookup after the
    first call (two on a fallback-chain hop — see ``_TILE_FALLBACK``; e.g.
    "fused_reduce" with no entry of its own borrows "ef_update"'s tuned
    tile). Never times anything — run ``autotune`` to populate. Unknown op
    names raise: a typo here would otherwise silently pin the default tile
    forever, which is exactly the failure mode the cache exists to avoid.
    """
    from repro.kernels.chunk_topk import BLOCK_CHUNKS

    if op not in _OPS:
        raise ValueError(f"unknown autotune op {op!r}; known ops: {_OPS}")
    if default is None:
        default = BLOCK_CHUNKS
    cache = _load()
    got = cache.get(_key(op, chunk, dtype, n_chunks))
    if got is None and op in _TILE_FALLBACK:
        got = cache.get(_key(_TILE_FALLBACK[op], chunk, dtype, n_chunks))
    if got is None:
        return default
    # Guard against stale caches written with a candidate set we no longer
    # ship — fall back to the default rather than an untested geometry.
    return got if got in CANDIDATE_BLOCKS else default


def _time_once(fn, *args, iters: int = 3) -> float:
    jax.block_until_ready(fn(*args))  # compile / warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(
    op: str,
    size: int,
    chunk: int,
    dtype=jnp.float32,
    *,
    candidates: Tuple[int, ...] = CANDIDATE_BLOCKS,
    interpret: Optional[bool] = None,
    iters: int = 3,
    seed: int = 0,
) -> int:
    """Sweep ``candidates`` for ``op`` at (size, chunk, dtype); cache winner.

    op: "select" (chunk_argmax), "ef_update" (fused residue update), or
    "fused_reduce" (the single-launch select→EF→scatter kernel; swept on a
    4-worker stack, clt_k mode, and keyed by the TOTAL launch rows —
    workers × chunk rows — matching PallasBackend._block's convention).
    Returns the winning block_chunks (also written to the on-disk cache under
    the current device kind).
    """
    if op not in _OPS:
        raise ValueError(f"op must be one of {_OPS}, got {op!r}")
    from repro.kernels import chunk_topk, ef_update, fused_reduce

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_chunks = -(-size // chunk)
    key = jax.random.PRNGKey(seed)
    key_rows = n_chunks
    if op == "fused_reduce":
        workers = 4
        key_rows = workers * n_chunks
        mw = jax.random.normal(key, (workers, n_chunks * chunk)).astype(dtype)
        gw = jax.random.normal(
            jax.random.fold_in(key, 1), (workers, n_chunks * chunk)
        ).astype(dtype)
        leader = jnp.zeros((), jnp.int32)
    else:
        x = jax.random.normal(key, (size,)).astype(dtype)
        if op == "ef_update":
            g = jax.random.normal(
                jax.random.fold_in(key, 1), (size,)
            ).astype(dtype)
            idx = jnp.zeros((n_chunks,), jnp.int32)

    best_block, best_t = None, float("inf")
    for block in candidates:
        if op == "select":
            fn = lambda a: chunk_topk.chunk_argmax_pallas(  # noqa: E731
                a, chunk, interpret=interpret, block_chunks=block
            )
            t = _time_once(fn, x, iters=iters)
        elif op == "fused_reduce":
            fn = lambda mm, gg, ll: fused_reduce.fused_reduce_trailing(  # noqa: E731
                mm, gg, ll, 0.1, chunk, 1, "clt_k",
                interpret=interpret, block_chunks=block,
            )
            t = _time_once(fn, mw, gw, leader, iters=iters)
        else:
            fn = lambda mm, gg, ii: ef_update.ef_update_pallas(  # noqa: E731
                mm, gg, ii, 0.1, chunk, interpret=interpret, block_chunks=block
            )
            t = _time_once(fn, x, g, idx, iters=iters)
        if t < best_t:
            best_block, best_t = block, t
    _store(_key(op, chunk, dtype, key_rows), best_block)
    return best_block


def autotune_params(
    params, chunk: int, *, min_size: int = 0, dtype=jnp.float32, **kw
) -> Dict[str, int]:
    """Sweep both hot-path ops for every distinct size bucket of a parameter
    pytree (what ``repro.launch.train --autotune`` drives). Tensors below
    ``min_size`` are reduced densely and skipped. Returns {bucketed key: win}.
    """
    import numpy as np

    sizes = sorted(
        {
            _bucket(-(-s // chunk)) * chunk
            for s in (
                int(np.prod(p.shape)) if p.ndim else 1
                for p in jax.tree_util.tree_leaves(params)
            )
            if s >= min_size
        }
    )
    out: Dict[str, int] = {}
    for op in _OPS:
        for size in sizes:
            out[f"{op}|n{size}"] = autotune(op, size, chunk, dtype, **kw)
    return out
