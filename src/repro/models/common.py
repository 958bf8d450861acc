"""Shared model components: initializers with logical sharding axes, norms,
RoPE, SwiGLU MLP, embeddings, and the vocab-sharded chunked cross-entropy.

No flax — parameters are plain pytrees. Every created parameter carries a tuple of
*logical axis names* in a parallel pytree; repro.distributed.sharding maps logical
axes onto mesh axes per sharding policy (tp / fsdp) with divisibility checks.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray
Pytree = Any

# logical axis vocabulary -----------------------------------------------------
# "vocab"    — vocabulary dim                (sharded over model)
# "embed"    — d_model dim                   (replicated under tp, data under fsdp)
# "heads"    — flattened heads*head_dim dim  (sharded over model)
# "kv"       — flattened kv_heads*head_dim   (sharded over model if divisible)
# "mlp"      — d_ff dim                      (sharded over model)
# "experts"  — expert dim                    (sharded over model: expert parallel)
# "layers"   — stacked layer dim             (never sharded)
# None       — replicated


class ParamStore:
    """Collects (param, logical_axes) pairs during init.

    abstract=True emits jax.ShapeDtypeStruct leaves instead of allocating —
    used by the dry-run to build parameter trees for trillion-param configs
    without touching memory.
    """

    def __init__(self, key: Optional[Array], param_dtype=jnp.float32, abstract: bool = False):
        self._key = key
        self.params: Dict[str, Any] = {}
        self.axes: Dict[str, Any] = {}
        self.param_dtype = param_dtype
        self.abstract = abstract

    def next_key(self) -> Optional[Array]:
        if self.abstract:
            return None
        self._key, sub = jax.random.split(self._key)
        return sub

    def add(self, name: str, value, axes: Tuple[Optional[str], ...]):
        assert len(axes) == len(value.shape), (name, axes, value.shape)
        self.params[name] = value
        self.axes[name] = axes

    def _make(self, name, full, ax, maker):
        if self.abstract:
            self.add(name, jax.ShapeDtypeStruct(full, self.param_dtype), ax)
        else:
            self.add(name, maker().astype(self.param_dtype), ax)

    def dense(self, name, shape, axes, scale: Optional[float] = None, stacked: int = 0):
        """Normal(0, scale) init; scale defaults to 1/sqrt(fan_in)."""
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        s = scale if scale is not None else fan_in**-0.5
        full = ((stacked,) if stacked else ()) + tuple(shape)
        ax = (("layers",) if stacked else ()) + tuple(axes)
        self._make(name, full, ax,
                   lambda: jax.random.normal(self.next_key(), full, jnp.float32) * s)

    def zeros(self, name, shape, axes, stacked: int = 0):
        full = ((stacked,) if stacked else ()) + tuple(shape)
        ax = (("layers",) if stacked else ()) + tuple(axes)
        self._make(name, full, ax, lambda: jnp.zeros(full, jnp.float32))

    def ones(self, name, shape, axes, stacked: int = 0):
        full = ((stacked,) if stacked else ()) + tuple(shape)
        ax = (("layers",) if stacked else ()) + tuple(axes)
        self._make(name, full, ax, lambda: jnp.ones(full, jnp.float32))

    def subtree(self, name: str):
        sub = ParamStore(self.next_key(), self.param_dtype, abstract=self.abstract)
        self.params[name] = sub.params
        self.axes[name] = sub.axes
        return sub


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


def rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(cfg, x: Array, p: Dict[str, Array], prefix: str) -> Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p[f"{prefix}_scale"], p[f"{prefix}_bias"], cfg.norm_eps or 1e-5)
    return rmsnorm(x, p[f"{prefix}_scale"], cfg.norm_eps or 1e-6)


def init_norm(cfg, store: ParamStore, prefix: str, d: int, stacked: int = 0):
    store.ones(f"{prefix}_scale", (d,), ("embed",), stacked=stacked)
    if cfg.norm == "layernorm":
        store.zeros(f"{prefix}_bias", (d,), ("embed",), stacked=stacked)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, n_heads, hd); positions: (..., S) int."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def sinusoidal_positions_at(pos: Array, d: int) -> Array:
    """Single-position sinusoidal embedding, (1, 1, d). pos: scalar int."""
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / jnp.power(10_000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[None, None, :]


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_swiglu(store: ParamStore, d: int, f: int, stacked: int = 0):
    store.dense("mlp_gate", (d, f), ("embed", "mlp"), stacked=stacked)
    store.dense("mlp_up", (d, f), ("embed", "mlp"), stacked=stacked)
    store.dense("mlp_down", (f, d), ("mlp", "embed"), stacked=stacked)


def swiglu(p: Dict[str, Array], x: Array, dtype) -> Array:
    g = x @ p["mlp_gate"].astype(dtype)
    u = x @ p["mlp_up"].astype(dtype)
    return (jax.nn.silu(g) * u) @ p["mlp_down"].astype(dtype)


def init_gelu_mlp(store: ParamStore, d: int, f: int, stacked: int = 0, bias: bool = True):
    store.dense("mlp_up", (d, f), ("embed", "mlp"), stacked=stacked)
    store.dense("mlp_down", (f, d), ("mlp", "embed"), stacked=stacked)
    if bias:
        store.zeros("mlp_up_b", (f,), ("mlp",), stacked=stacked)
        store.zeros("mlp_down_b", (d,), ("embed",), stacked=stacked)


def gelu_mlp(p: Dict[str, Array], x: Array, dtype) -> Array:
    h = x @ p["mlp_up"].astype(dtype)
    if "mlp_up_b" in p:
        h = h + p["mlp_up_b"].astype(dtype)
    h = jax.nn.gelu(h)
    o = h @ p["mlp_down"].astype(dtype)
    if "mlp_down_b" in p:
        o = o + p["mlp_down_b"].astype(dtype)
    return o


# ---------------------------------------------------------------------------
# embedding + vocab-sharded chunked cross-entropy
# ---------------------------------------------------------------------------


def init_embeddings(cfg, store: ParamStore):
    store.dense("tok_embed", (cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=1.0)
    if not cfg.tie_embeddings:
        store.dense("lm_head", (cfg.d_model, cfg.vocab), ("embed", "vocab"))


def embed_tokens(p: Pytree, tokens: Array, dtype) -> Array:
    return p["tok_embed"].astype(dtype)[tokens]


def lm_logits(p: Pytree, x: Array, dtype) -> Array:
    w = p["lm_head"] if "lm_head" in p else p["tok_embed"].T
    return x @ w.astype(dtype)


def chunked_xent(
    p: Pytree, h: Array, labels: Array, mask: Array, chunk: int, dtype
) -> Array:
    """Cross-entropy over a model-sharded vocab, scanning sequence chunks so the
    full (B, S, V) logits tensor never materializes. h: (B, S, D)."""
    B, S, D = h.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n_chunks = h.shape[1] // chunk
    hc = h.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        hx, lx, mx = inp
        logits = lm_logits(p, hx, dtype).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mx
        return carry + jnp.sum(nll), None

    body = jax.checkpoint(body)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc, mc))
    return total / jnp.maximum(jnp.sum(mask), 1.0)
