"""Grouped-query attention: training, prefill (returns KV cache), and
single-token decode (full or ring-buffer window cache).

Training takes one of two paths, chosen from what the trace can observe
(``attention_train``):

  * the blockwise causal kernel (``kernels.flash_attention``), for causal
    self-attention over positions 0..S-1 (``in_order``: the caller built
    them so, and the order is known statically) with no window, on a TPU,
    in a program on one device (GSPMD cannot partition a Mosaic kernel),
    when S is whole kernel blocks and more than one (``flash_applies``).
    Score tiles stay in VMEM, key blocks above the diagonal are skipped,
    and the backward runs from the saved log-sum-exp;
  * ``attention_core`` everywhere else (short sequences, a window,
    cross-attention, arbitrary positions, prefill, the CPU, a mesh): query
    chunks scanned under a rematerialized body (Rabe-Staats), masking by
    absolute position, so the (B, H, S, S) score tensor never
    materializes; peak extra memory is (B, H, q_chunk, S).

Both compute in float32 with the matmul operands at the default precision.

Cache layout: {"k": (B, C, KV, hd), "v": (B, C, KV, hd), "slot_pos": (C,) int32}
where slot_pos[j] is the absolute position held in slot j (-1 = empty). Full
caches use slot j == position j; sliding-window caches are ring buffers
(slot = pos % C). Masking is always derived from slot_pos, so both layouts share
one decode path — and a sequence-sharded cache (slots over "model") works
transparently under GSPMD (flash-decode-style sequence parallelism).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention
from repro.kernels import ops as kernel_ops
from repro.models import common, mla
from repro.distributed.sharding import constrain

Array = jnp.ndarray

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_attention(cfg, store: common.ParamStore, stacked: int = 0, prefix: str = "attn"):
    if cfg.kv_lora_rank:
        mla.init_mla(cfg, store, stacked=stacked, prefix=prefix)
        return
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    store.dense(f"{prefix}_wq", (D, H * hd), ("embed", "heads"), stacked=stacked)
    store.dense(f"{prefix}_wk", (D, KV * hd), ("embed", "kv"), stacked=stacked)
    store.dense(f"{prefix}_wv", (D, KV * hd), ("embed", "kv"), stacked=stacked)
    store.dense(f"{prefix}_wo", (H * hd, D), ("heads", "embed"), stacked=stacked)
    if cfg.qkv_bias:
        store.zeros(f"{prefix}_bq", (H * hd,), ("heads",), stacked=stacked)
        store.zeros(f"{prefix}_bk", (KV * hd,), ("kv",), stacked=stacked)
        store.zeros(f"{prefix}_bv", (KV * hd,), ("kv",), stacked=stacked)


def _project_qkv(cfg, p, x, kv_x, positions, kv_positions, dtype, rope, prefix):
    if cfg.kv_lora_rank:
        return mla.project_qkv(cfg, p, x, positions, dtype, rope, prefix)
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p[f"{prefix}_wq"].astype(dtype)
    k = kv_x @ p[f"{prefix}_wk"].astype(dtype)
    v = kv_x @ p[f"{prefix}_wv"].astype(dtype)
    if cfg.qkv_bias:
        q = q + p[f"{prefix}_bq"].astype(dtype)
        k = k + p[f"{prefix}_bk"].astype(dtype)
        v = v + p[f"{prefix}_bv"].astype(dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, kv_x.shape[1], KV, hd)
    v = v.reshape(B, kv_x.shape[1], KV, hd)
    if rope:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# core: q-chunked masked attention
# ---------------------------------------------------------------------------


def attention_core(
    q: Array,
    k: Array,
    v: Array,
    q_pos: Array,
    k_pos: Array,
    *,
    causal: bool,
    window: Optional[int],
    q_chunk: int = 512,
) -> Array:
    """q, k: (B, S, H, hd), (B, T, KV, hd); v: (B, T, KV, vd); *_pos absolute
    positions (S,) / (T,).

    Returns (B, S, H, vd). Scans q chunks with a checkpointed body so backward
    recomputes scores instead of storing (B, H, S, T).
    """
    B, S, H, hd = q.shape
    T, KV, vd = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    scale = hd**-0.5
    q_chunk = min(q_chunk, S)
    pad = (-S) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    n_chunks = q.shape[1] // q_chunk
    qg = q.reshape(B, n_chunks, q_chunk, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    qpos_c = q_pos.reshape(n_chunks, q_chunk)

    def body(_, inp):
        qc, qp = inp  # (B, KV, G, qc, hd), (qc,)
        s = jnp.einsum("bkgqd,btkd->bkgqt", qc, k).astype(jnp.float32) * scale
        mask = jnp.ones((qp.shape[0], T), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= qp[:, None]
        if window is not None:
            mask &= (qp[:, None] - k_pos[None, :]) < window
        mask &= (k_pos[None, :] >= 0) & (qp[:, None] >= 0)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        o = jnp.einsum("bkgqt,btkd->bkgqd", w, v)
        return None, o

    body = jax.checkpoint(body)
    _, out = jax.lax.scan(body, None, (qg, qpos_c))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, n_chunks * q_chunk, H, vd)
    return out[:, :S]


# ---------------------------------------------------------------------------
# train / prefill / decode entry points
# ---------------------------------------------------------------------------


def flash_applies(seq: int, *, causal: bool, window: Optional[int], cross: bool) -> bool:
    """Whether training attention over positions 0..seq-1 takes the blockwise
    kernel: causal self-attention with no window, over whole kernel blocks
    and more than one, on a TPU, in a program on one device (GSPMD cannot
    partition a Mosaic kernel over a mesh)."""
    return (
        causal and window is None and not cross and flash_attention.fits(seq)
        and kernel_ops.on_tpu() and jax.device_count() == 1
    )


def attention_train(
    cfg,
    p,
    x: Array,
    positions: Array,
    *,
    dtype,
    causal: bool = True,
    window: Optional[int] = None,
    kv_x: Optional[Array] = None,
    kv_positions: Optional[Array] = None,
    rope: bool = True,
    prefix: str = "attn",
    in_order: bool = False,
) -> Array:
    """Full-sequence attention (training / encoding). positions: (S,).
    ``in_order``: the caller built ``positions`` as 0..S-1, which the
    blockwise kernel needs to know statically; an arbitrary positions array
    keeps the masked scan."""
    cross = kv_x is not None
    B, S = x.shape[:2]
    if in_order and flash_applies(S, causal=causal, window=window, cross=cross):
        def project(p, x, positions):
            return _project_qkv(cfg, p, x, x, positions, positions, dtype, rope, prefix)

        # the attention's own weights alone: the backward makes q, k and v
        # again from them
        own = {name: w for name, w in p.items() if name.startswith(f"{prefix}_")}
        out = flash_attention.projected_attention(project, (own, x, positions)).astype(dtype)
    else:
        kv_src = kv_x if cross else x
        kv_pos = kv_positions if cross else positions
        q, k, v = _project_qkv(cfg, p, x, kv_src, positions, kv_pos, dtype,
                               rope and not cross, prefix)
        out = attention_core(q, k, v, positions, kv_pos,
                             causal=causal and not cross, window=window)
    out = out.reshape(B, S, -1)
    return out @ p[f"{prefix}_wo"].astype(dtype)


def _cache_heads(cfg) -> Tuple[int, int, int]:
    """(key/value heads, key head dim, value head dim) a cache holds."""
    if cfg.kv_lora_rank:  # the expanded heads, not the latent
        return cfg.n_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    return cfg.n_kv_heads, cfg.hd, cfg.hd


def init_cache(cfg, batch: int, capacity: int, dtype) -> Dict[str, Array]:
    KV, hd, vd = _cache_heads(cfg)
    return {
        "k": jnp.zeros((batch, capacity, KV, hd), dtype),
        "v": jnp.zeros((batch, capacity, KV, vd), dtype),
        "slot_pos": jnp.full((capacity,), -1, jnp.int32),
    }


def attention_prefill(
    cfg, p, x, positions, cache, *, dtype, window=None, rope=True, prefix="attn"
) -> Tuple[Array, Dict[str, Array]]:
    """Run full-sequence attention AND populate the cache (capacity >= S)."""
    q, k, v = _project_qkv(cfg, p, x, x, positions, positions, dtype, rope, prefix)
    out = attention_core(q, k, v, positions, positions, causal=True, window=window)
    B, S = x.shape[:2]
    C = cache["k"].shape[1]
    if C == S:
        new_cache = {"k": k, "v": v, "slot_pos": positions.astype(jnp.int32)}
    else:
        # keep the last C positions (ring layout: slot = pos % C)
        keep = min(C, S)
        ks, vs = k[:, S - keep:], v[:, S - keep:]
        pos_tail = positions[S - keep:]
        slots = jnp.mod(pos_tail, C)
        new_cache = {
            "k": cache["k"].at[:, slots].set(ks),
            "v": cache["v"].at[:, slots].set(vs),
            "slot_pos": cache["slot_pos"].at[slots].set(pos_tail.astype(jnp.int32)),
        }
    out = out.reshape(B, S, -1)
    return out @ p[f"{prefix}_wo"].astype(dtype), new_cache


def attention_decode(
    cfg,
    p,
    x: Array,
    pos: Array,
    cache: Dict[str, Array],
    *,
    dtype,
    window: Optional[int] = None,
    update_cache: bool = True,
    rope: bool = True,
    causal: bool = True,
    prefix: str = "attn",
) -> Tuple[Array, Dict[str, Array]]:
    """One-token decode. x: (B, 1, D); pos: scalar absolute position.

    With update_cache=False (cross-attention) the cache is read-only and
    causal=False attends to every populated slot (encoder memory).
    """
    B = x.shape[0]
    H = cfg.n_heads
    KV, hd, _ = _cache_heads(cfg)
    G = H // KV
    pos_arr = jnp.reshape(pos, (1,)).astype(jnp.int32)
    if update_cache:
        q, k_new, v_new = _project_qkv(
            cfg, p, x, x, pos_arr, pos_arr, dtype, rope, prefix
        )
        C = cache["k"].shape[1]
        slot = jnp.mod(pos, C)
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1),
            "slot_pos": jax.lax.dynamic_update_slice_in_dim(
                cache["slot_pos"], pos_arr, slot, axis=0
            ),
        }
    else:
        q = x @ p[f"{prefix}_wq"].astype(dtype)
        if cfg.qkv_bias:
            q = q + p[f"{prefix}_bq"].astype(dtype)
        q = q.reshape(B, 1, H, hd)
        if rope:
            q = common.apply_rope(q, pos_arr, cfg.rope_theta)
    k, v, spos = cache["k"], cache["v"], cache["slot_pos"]
    qg = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k).astype(jnp.float32) * hd**-0.5
    valid = spos >= 0
    if causal:
        valid &= spos <= pos
    if window is not None:
        valid &= (pos - spos) < window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("bkgqt,btkd->bqkgd", w, v).reshape(B, 1, -1)
    return o @ p[f"{prefix}_wo"].astype(dtype), cache
