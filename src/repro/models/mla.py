"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434), with no
query compression: the projections into per-head queries, keys and values.

Keys and values come from one latent of ``kv_lora_rank`` values a token
(RMS-normalised) and one rotary key of ``qk_rope_head_dim`` shared by every
head; queries and keys are ``qk_nope_head_dim`` content dims followed by
``qk_rope_head_dim`` rotary dims, values ``v_head_dim``. The attention itself
runs over the expanded heads, a query and key head (nope + rope) wider than
a value head, by ``attention.attention_train``'s rule: the blockwise causal
kernel (``kernels.flash_attention``) for a long sequence in training on a
TPU, ``attention.attention_core`` otherwise. Rotary positions rotate the
two halves of the rotary dims (``common.apply_rope``); the published code
de-interleaves them first, which on seeded weights is a fixed permutation of
the rotary columns.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.models import common

Array = jnp.ndarray

# the published code's RMSNorm default, which the config's rms_norm_eps does
# not set for the latent's norm
KV_NORM_EPS = 1e-6


def init_mla(cfg, store: common.ParamStore, stacked: int = 0, prefix: str = "attn"):
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    store.dense(f"{prefix}_wq", (D, H * (nope + rope)), ("embed", "heads"), stacked=stacked)
    store.dense(f"{prefix}_wkv_a", (D, R + rope), ("embed", None), stacked=stacked)
    store.ones(f"{prefix}_kv_norm_scale", (R,), (None,), stacked=stacked)
    store.dense(f"{prefix}_wkv_b", (R, H * (nope + vd)), (None, "heads"), stacked=stacked)
    store.dense(f"{prefix}_wo", (H * vd, D), ("heads", "embed"), stacked=stacked)


def project_qkv(cfg, p, x: Array, positions: Array, dtype, rope: bool = True, prefix="attn"):
    """x: (B, S, D) -> q, k: (B, S, H, nope + rope), v: (B, S, H, v_head_dim)."""
    B, S, _ = x.shape
    H, R = cfg.n_heads, cfg.kv_lora_rank
    nope, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p[f"{prefix}_wq"].astype(dtype)).reshape(B, S, H, nope + rd)
    kv_a = x @ p[f"{prefix}_wkv_a"].astype(dtype)
    latent = common.rmsnorm(kv_a[..., :R], p[f"{prefix}_kv_norm_scale"], KV_NORM_EPS)
    kv = (latent @ p[f"{prefix}_wkv_b"].astype(dtype)).reshape(B, S, H, nope + cfg.v_head_dim)
    q_pe, k_pe = q[..., nope:], kv_a[..., R:].reshape(B, S, 1, rd)
    if rope:
        q_pe = common.apply_rope(q_pe, positions, cfg.rope_theta)
        k_pe = common.apply_rope(k_pe, positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rd))], axis=-1)
    return q, k, kv[..., nope:]
