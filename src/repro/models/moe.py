"""Mixture-of-Experts FFN: top-k routing, dropless dispatch, grouped matmuls.

A layer may hold a share of the router's experts (expert parallelism, one
share a device): it routes every token over all ``cfg.n_router`` experts and
computes the part of the output that its own ``cfg.n_experts``, from
``cfg.first_expert`` on, give. Holding every expert is the share of one.

Dispatch is dropless. The (token, choice) pairs are sorted by held expert,
pairs routed elsewhere last, and one grouped matmul (``grouped_matmul``, over
``jax.lax.ragged_dot``) per projection runs each held expert over its own
rows; rows past the held groups are set to zero, forward and backward. The
buffers are (tokens x topk) rows whatever the routing, so no token is dropped
however uneven it is.

Routers:
- ``softmax``: softmax scores pick and weigh the experts; the load-balance
  (GShard) and router-z (ST-MoE) losses are returned for the model to add;
- ``sigmoid``: DeepSeek-V3's ``noaux_tc``. Sigmoid scores plus a correction
  bias (``router_bias``) pick the experts, the scores alone weigh them; no
  auxiliary loss.

Either way the chosen weights are normalised to sum to 1 (every configuration
here does: Moonlight's ``norm_topk_prob``) and scaled by ``routed_scale``.
Shared experts (``n_shared_experts``) are one SwiGLU every token passes
through, added once.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common

Array = jnp.ndarray


def init_moe(cfg, store: common.ParamStore, stacked: int = 0):
    D, F, E, R = cfg.d_model, cfg.expert_ff, cfg.n_experts, cfg.n_router
    store.dense("router", (D, R), ("embed", None), scale=0.02, stacked=stacked)
    if cfg.router == "sigmoid":
        store.zeros("router_bias", (R,), (None,), stacked=stacked)
    store.dense("expert_gate", (E, D, F), ("experts", "embed", "mlp"), stacked=stacked)
    store.dense("expert_up", (E, D, F), ("experts", "embed", "mlp"), stacked=stacked)
    store.dense("expert_down", (E, F, D), ("experts", "mlp", "embed"), stacked=stacked)
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        store.dense("shared_gate", (D, Fs), ("embed", "mlp"), stacked=stacked)
        store.dense("shared_up", (D, Fs), ("embed", "mlp"), stacked=stacked)
        store.dense("shared_down", (Fs, D), ("mlp", "embed"), stacked=stacked)


def route(cfg, p, xt: Array, dtype) -> Tuple[Array, Array, Dict[str, Array]]:
    """xt: (T, D) -> (choice (T, K), weights (T, K), auxiliary losses)."""
    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)
    aux: Dict[str, Array] = {}
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, choice = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32), cfg.moe_topk)
        weights = jnp.take_along_axis(scores, choice, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, choice = jax.lax.top_k(probs, cfg.moe_topk)
        # load-balance (GShard) and router z (ST-MoE) losses, over every expert
        routed = jnp.bincount(choice.reshape(-1), length=cfg.n_router) / choice.size
        aux["moe_lb_loss"] = cfg.n_router * jnp.sum(jnp.mean(probs, axis=0) * routed)
        aux["moe_z_loss"] = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if cfg.routed_scale != 1.0:
        weights = weights * cfg.routed_scale
    return choice, weights, aux


# The TPU compiler takes a grouped matmul with no batch dimension only, and the
# train step vmaps the loss over its learners: batched, each call runs in a
# loop over the batch (one trip for one learner). Differentiated through
# custom_vjp, as custom_vmap itself cannot be.
_ragged_dot = jax.custom_batching.sequential_vmap(jax.lax.ragged_dot)


@jax.custom_batching.sequential_vmap
def _ragged_dot_vjp(x: Array, w: Array, sizes: Array, g: Array):
    return jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)[1](g)


def _in_groups(y: Array, sizes: Array) -> Array:
    """``y`` with its rows past ``sum(sizes)`` set to zero: on the TPU the
    grouped matmul leaves them unwritten, so they hold whatever the buffer
    held before."""
    return jnp.where(jnp.arange(y.shape[0])[:, None] < jnp.sum(sizes), y, 0)


@jax.custom_vjp
def grouped_matmul(x: Array, w: Array, sizes: Array) -> Array:
    """(M, K) @ (G, K, N) -> (M, N): rows ``sum(sizes[:g])`` on take group
    g's matrix, in order; rows past ``sum(sizes)`` come out zero, and so do
    their rows of the gradient of ``x``."""
    return _in_groups(_ragged_dot(x, w, sizes), sizes)


def _grouped_matmul_fwd(x, w, sizes):
    return _in_groups(_ragged_dot(x, w, sizes), sizes), (x, w, sizes)


def _grouped_matmul_bwd(res, g):
    x, w, sizes = res
    dx, dw = _ragged_dot_vjp(x, w, sizes, g)
    return _in_groups(dx, sizes), dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _swiglu(x: Array, gate: Array, up: Array, down: Array, dtype) -> Array:
    h = jax.nn.silu(x @ gate.astype(dtype)) * (x @ up.astype(dtype))
    return h @ down.astype(dtype)


def moe_ffn(cfg, p, x: Array, *, dtype) -> Tuple[Array, Dict[str, Array]]:
    """x: (B, S, D) -> (B, S, D), aux dict: the router's losses and the
    counters ``moe_routed_here`` (share of the (token, choice) pairs that
    landed on held experts) and ``moe_load_max`` (the fullest held expert's
    rows over the held experts' mean)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_topk
    T = B * S
    xt = x.reshape(T, D)
    choice, weights, aux = route(cfg, p, xt, dtype)

    # dispatch: pairs sorted by held expert; pairs routed elsewhere go last
    local = choice.reshape(-1) - cfg.first_expert  # (T*K,)
    held = (local >= 0) & (local < E)
    group = jnp.where(held, local, E)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=E + 1)[:E].astype(jnp.int32)
    rows = xt[order // K]  # the token of each sorted pair

    h = jax.nn.silu(grouped_matmul(rows, p["expert_gate"].astype(dtype), sizes))
    h = h * grouped_matmul(rows, p["expert_up"].astype(dtype), sizes)
    y = grouped_matmul(h, p["expert_down"].astype(dtype), sizes)

    # combine: each pair's output back in (token, choice) order, weighted
    unsort = jnp.zeros_like(order).at[order].set(jnp.arange(T * K, dtype=order.dtype))
    per_choice = y[unsort].reshape(T, K, D)
    w = jnp.where(held.reshape(T, K), weights, 0.0).astype(dtype)
    out = jnp.sum(per_choice * w[..., None], axis=1)
    if cfg.n_shared_experts:
        out = out + _swiglu(xt, p["shared_gate"], p["shared_up"], p["shared_down"], dtype)

    n_held = jnp.sum(sizes)
    aux["moe_routed_here"] = n_held / (T * K)
    aux["moe_load_max"] = jnp.max(sizes) * E / jnp.maximum(n_held, 1)
    return out.reshape(B, S, D), aux
