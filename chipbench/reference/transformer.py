"""Plain reference: a decoder-only transformer trained by ScaleCom CLT-k.

Written from the published descriptions, with no kernels and nothing
imported from the program under test. It computes in float32 with every
matmul at XLA's default precision, the precision a float32 configuration
states when it sets none (on a TPU, one bfloat16 pass with float32
accumulation), and its rotary angles in float32:

- the model: pre-norm blocks of causal grouped-query attention with rotary
  positions (split-half rotation) and an MLP (GELU with the tanh
  approximation, or SwiGLU), a final norm, and the output head (tied to the
  token embedding or not); the loss is the mean next-token cross-entropy
  over the unmasked positions of each worker's batch;
- the reduce: ScaleCom Algorithm 1 with the CLT-k compressor (Chen et al.,
  NeurIPS 2020). Each tensor of at least ``min_size`` elements is flattened
  in row-major order into chunks of ``chunk`` elements (the last chunk
  zero-padded); the leader worker ``t mod n`` picks in every chunk the
  element of largest magnitude of its ``m + g``, the first on a tie; every
  worker contributes its own ``m + g`` there and the reduced gradient is the
  worker mean, scattered back; each residue becomes ``m + beta (g - own)``
  (Eq. 5), where ``own`` is the worker's own contribution. Smaller tensors are
  averaged densely and keep no residue;
- the optimizer: SGD with momentum, ``v = mu v + g_hat``, ``p = p - lr v``.

Parameters are a nested dict whose names and shapes are those every
implementation of this family must accept (layers stacked on a leading
axis), so one tensor here is one tensor there, and chunking it gives the
same chunks. The weights come from ``init_params`` and the seed alone.

Memory: the loss and gradient run over blocks of batch rows, attention over
blocks of queries under ``jax.checkpoint``, so that a long sequence fits
beside the optimizer state; the blocks change the order of float32 sums and
nothing else.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# XLA's default: what a float32 configuration that names no precision runs
PRECISION = None


def seed_key(seed: int):
    """A PRNG key from a seed of any size up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _dims(m: dict):
    D, H = m["d_model"], m["n_heads"]
    return D, m["n_layers"], m["vocab"], H, m["n_kv_heads"], m["d_ff"], m.get("head_dim") or D // H


def init_params(m: dict, key) -> dict:
    """Seeded float32 weights: matrices N(0, 1/fan_in), norm scales near 1,
    biases and the token embedding small and non-zero."""
    D, L, V, H, KV, F, hd = _dims(m)
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def norm(prefix, shape):
        out = {f"{prefix}_scale": 1.0 + normal(shape, 0.02)}
        if m["norm"] == "layernorm":
            out[f"{prefix}_bias"] = normal(shape, 0.02)
        return out

    blocks = {
        **norm("ln_attn", (L, D)),
        "attn_wq": normal((L, D, H * hd), D**-0.5),
        "attn_wk": normal((L, D, KV * hd), D**-0.5),
        "attn_wv": normal((L, D, KV * hd), D**-0.5),
        "attn_wo": normal((L, H * hd, D), (H * hd) ** -0.5),
        **norm("ln_mlp", (L, D)),
    }
    if m["qkv_bias"]:
        blocks["attn_bq"] = normal((L, H * hd), 0.02)
        blocks["attn_bk"] = normal((L, KV * hd), 0.02)
        blocks["attn_bv"] = normal((L, KV * hd), 0.02)
    if m["mlp"] == "swiglu":
        blocks["mlp_gate"] = normal((L, D, F), D**-0.5)
        blocks["mlp_up"] = normal((L, D, F), D**-0.5)
        blocks["mlp_down"] = normal((L, F, D), F**-0.5)
    else:
        blocks["mlp_up"] = normal((L, D, F), D**-0.5)
        blocks["mlp_up_b"] = normal((L, F), 0.02)
        blocks["mlp_down"] = normal((L, F, D), F**-0.5)
        blocks["mlp_down_b"] = normal((L, D), 0.02)
    params = {"tok_embed": normal((V, D), 0.02), "blocks": blocks, **norm("ln_final", (D,))}
    if not m["tie_embeddings"]:
        params["lm_head"] = normal((D, V), D**-0.5)
    return params


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _norm(m, x, p, prefix):
    eps = m["norm_eps"]
    if m["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p[f"{prefix}_scale"] + p[f"{prefix}_bias"]
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p[f"{prefix}_scale"]


def _rope(x, theta):
    """Rotary positions on (B, S, heads, hd), rotating the two halves of hd;
    the angles are float32, as the configuration computes."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=PRECISION)


def _attend(q, k, v, q0):
    """Causal softmax attention of queries at positions q0.. over all keys."""
    s = _mm("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    qpos = q0 + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(jnp.arange(k.shape[1])[None, :] <= qpos, s, -jnp.inf)
    return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def _block(m, p, x, q_block):
    D, L, V, H, KV, F, hd = _dims(m)
    B, S, _ = x.shape
    xn = _norm(m, x, p, "ln_attn")
    q = _mm("bsd,de->bse", xn, p["attn_wq"])
    k = _mm("bsd,de->bse", xn, p["attn_wk"])
    v = _mm("bsd,de->bse", xn, p["attn_wv"])
    if m["qkv_bias"]:
        q, k, v = q + p["attn_bq"], k + p["attn_bk"], v + p["attn_bv"]
    q = _rope(q.reshape(B, S, H, hd), m["rope_theta"])
    k = _rope(k.reshape(B, S, KV, hd), m["rope_theta"])
    # query head h reads key/value head h // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v.reshape(B, S, KV, hd), H // KV, axis=2)
    attend = jax.checkpoint(_attend, static_argnums=(3,))
    o = jnp.concatenate(
        [attend(q[:, s0 : s0 + q_block], k, v, s0) for s0 in range(0, S, q_block)],
        axis=1,
    )
    x = x + _mm("bse,ed->bsd", o.reshape(B, S, H * hd), p["attn_wo"])
    xn = _norm(m, x, p, "ln_mlp")
    if m["mlp"] == "swiglu":
        g = _mm("bsd,df->bsf", xn, p["mlp_gate"])
        u = _mm("bsd,df->bsf", xn, p["mlp_up"])
        return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["mlp_down"])
    h = _gelu_tanh(_mm("bsd,df->bsf", xn, p["mlp_up"]) + p["mlp_up_b"])
    return x + _mm("bsf,fd->bsd", h, p["mlp_down"]) + p["mlp_down_b"]


def nll_sum(m, params, tokens, labels, mask, q_block):
    """Summed next-token negative log-likelihood over the unmasked positions."""
    x = params["tok_embed"][tokens]
    for layer in range(m["n_layers"]):
        p = jax.tree.map(lambda a: a[layer], params["blocks"])
        x = jax.checkpoint(lambda p, x: _block(m, p, x, q_block))(p, x)
    x = _norm(m, x, params, "ln_final")
    head = params["tok_embed"].T if m["tie_embeddings"] else params["lm_head"]
    logits = _mm("bsd,dv->bsv", x, head)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask)


# ---------------------------------------------------------------------------
# ScaleCom CLT-k and SGD with momentum
# ---------------------------------------------------------------------------


def _chunks(x, chunk):
    """(n, *shape) -> (n, n_chunks, chunk), flattened row-major, zero-padded."""
    flat = x.reshape(x.shape[0], -1)
    pad = (-flat.shape[1]) % chunk
    return jnp.pad(flat, ((0, 0), (0, pad))).reshape(x.shape[0], -1, chunk)


def clt_k(g, m, t, chunk, beta):
    """One tensor through Algorithm 1. g, m: (n, *shape) per-worker gradient
    and residue. Returns (g_hat (*shape), new residue (n, *shape))."""
    n, shape, size = g.shape[0], g.shape[1:], int(np.prod(g.shape[1:]))
    ef = _chunks(m + g, chunk)
    leader = jnp.take(ef, jnp.mod(t, n), axis=0)
    idx = jnp.argmax(jnp.abs(leader), axis=-1)  # first lane on a tie
    onehot = jax.nn.one_hot(idx, chunk, dtype=ef.dtype)[None]
    own = ef * onehot  # each worker's contribution, in place
    unchunk = lambda c: c.reshape(c.shape[0], -1)[:, :size].reshape((c.shape[0],) + shape)
    g_hat = unchunk(jnp.mean(own, axis=0, keepdims=True))[0]
    return g_hat, m + beta * (g - unchunk(own))


def leaf_norms(tree) -> jnp.ndarray:
    """Frobenius norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)])


def bf16_share(tree) -> jnp.ndarray:
    """Share of the non-zero values in ``tree`` that bfloat16 holds exactly.

    A float32 gradient holds about one such value in 2**16; a gradient
    computed through bfloat16 activations and casts holds them throughout."""
    leaves = jax.tree.leaves(tree)
    exact = sum(jnp.sum((x != 0) & (x.astype(jnp.bfloat16).astype(x.dtype) == x)) for x in leaves)
    nonzero = sum(jnp.sum(x != 0) for x in leaves)
    return exact / jnp.maximum(nonzero, 1)


def grad_bf16_share(residues, beta: float) -> jnp.ndarray:
    """Mean over tensors of the share of each learner's first gradient values
    that bfloat16 holds, read from the residues after one step from zero.

    After that step each residue is ``beta (g - own)`` (Eq. 5 with m = 0), so
    ``g`` is the residue over ``beta`` wherever it is not zero. The product
    and the quotient each round in float32, so a value within 2 float32 units
    in the last place of a bfloat16 value counts as one. A float32 gradient
    holds about 5 such values in 2**16; a tensor whose gradient is computed
    in bfloat16 holds them throughout. Unlike ``bf16_share`` of the reduced
    gradient, it sees each learner before the mean across learners, and each
    tensor with the same weight however few values it has."""
    shares = []
    for x in jax.tree.leaves(residues):
        g = x / beta
        low = jax.lax.bitcast_convert_type(g, jnp.uint32) & 0xFFFF
        nonzero = g != 0
        near = nonzero & ((low <= 2) | (low >= 0xFFFE))
        shares.append(jnp.sum(near) / jnp.maximum(jnp.sum(nonzero), 1))
    return jnp.mean(jnp.stack(shares))


class Reference:
    """The reference's training steps for one configuration and traffic mix."""

    def __init__(self, model: dict, mix: dict):
        if mix["compressor"] != "clt_k" or mix.get("topm", 1) != 1:
            raise ValueError("the reference implements CLT-k with one value per chunk")
        if mix["optimizer"] != "sgdm" or mix["residue_dtype"] != "fp32":
            raise ValueError("the reference implements SGD momentum on fp32 residues")
        self.m, self.mix = model, mix
        q_block = min(mix["seq"], mix.get("reference_q_block", 1024))

        def grad_block(params, tokens, labels, mask, count):
            f = lambda p: nll_sum(model, p, tokens, labels, mask, q_block) / count
            return jax.value_and_grad(f)(params)

        def update(params, mom, residues, grads, t):
            """grads: per-worker (n, *shape) leaves."""
            g_hat, new_res = {}, {}
            flat_g, tdef = jax.tree_util.tree_flatten_with_path(grads)
            outs = []
            for path, g in flat_g:
                key = jax.tree_util.keystr(path)
                if key in residues:
                    gh, new_res[key] = clt_k(g, residues[key], t, mix["chunk"], mix["beta"])
                else:
                    gh = jnp.mean(g, axis=0)
                outs.append(gh)
            g_hat = jax.tree_util.tree_unflatten(tdef, outs)
            mu, lr = mix.get("momentum", 0.9), mix["lr"]
            mom = jax.tree.map(lambda v, g: mu * v + g, mom, g_hat)
            params = jax.tree.map(lambda p, v: p - lr * v, params, mom)
            return params, mom, new_res, g_hat

        self._init = jax.jit(lambda key: init_params(model, key))
        self._grad_block = jax.jit(grad_block)
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
        self._norms = jax.jit(leaf_norms)
        self._bf16_share = jax.jit(bf16_share)
        self._grad_bf16_share = jax.jit(lambda r: grad_bf16_share(r, mix["beta"]))
        self._delta_norms = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))

    def init(self, seed: int):
        return self._init(seed_key(seed))

    def _loss_and_grads(self, params, batch):
        """Per-worker mean loss and gradients, over blocks of batch rows."""
        rows = self.mix.get("reference_rows", self.mix["local_batch"])
        n, b = batch["tokens"].shape[:2]
        losses, grads = [], []
        for w in range(n):
            count = float(np.sum(batch["mask"][w]))
            total, acc = 0.0, None
            for r0 in range(0, b, rows):
                sl = slice(r0, r0 + rows)
                loss, g = self._grad_block(
                    params, batch["tokens"][w, sl], batch["labels"][w, sl],
                    batch["mask"][w, sl], count,
                )
                total += float(loss)
                acc = g if acc is None else self._add(acc, g)
            losses.append(total)
            grads.append(acc)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *grads)
        return float(np.mean(losses)), stacked

    def readings(self, seed: int, batch_of: Callable[[int], dict], steps: int) -> Dict:
        """Losses of ``steps`` steps, leaf norms of the first step's dense
        gradient and reduced gradient, the reduced gradient's share of
        values that bfloat16 holds exactly and the learners' gradients'
        (``grad_bf16_share``), and leaf norms of the
        parameters' change after the last step. Leaves are in
        ``jax.tree.leaves`` order."""
        params = self.init(seed)
        n = self.mix["workers"]
        mom = jax.tree.map(jnp.zeros_like, params)
        residues = {
            jax.tree_util.keystr(p): jnp.zeros((n,) + x.shape, jnp.float32)
            for p, x in jax.tree_util.tree_flatten_with_path(params)[0]
            if x.size >= self.mix["min_size"]
        }
        out: Dict[str, List] = {"loss": []}
        for t in range(steps):
            loss, grads = self._loss_and_grads(params, batch_of(t))
            out["loss"].append(loss)
            if t == 0:
                out["grad_norms"] = np.asarray(self._norms(jax.tree.map(lambda g: jnp.mean(g, 0), grads)))
            params, mom, residues, g_hat = self._update(params, mom, residues, grads, t)
            if t == 0:
                out["ghat_norms"] = np.asarray(self._norms(g_hat))
                out["ghat_bf16_share"] = float(self._bf16_share(g_hat))
                out["grad_bf16_share"] = float(self._grad_bf16_share(residues))
            del grads, g_hat
        del mom, residues
        out["delta_norms"] = np.asarray(self._delta_norms(params, self.init(seed)))
        out["leaves"] = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
        return out
