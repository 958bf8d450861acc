"""Plain reference: a DeepSeek-V3-style decoder (Moonlight-16B-A3B) at one
chip's share of an expert-parallel deployment, trained by ScaleCom CLT-k.

Written from the published description (DeepSeek-V3, arXiv:2412.19437, and
the ``deepseek_v3`` modelling code that Moonlight's config names), with no
kernels and nothing imported from the program under test. It computes in
float32 with every matmul at XLA's default precision, as
``chipbench/reference/transformer.py`` does, whose CLT-k, sgdm and readings
it reuses; only the loss and the weights are its own:

- pre-norm blocks (RMSNorm) of multi-head latent attention with no query
  compression: per head, queries of ``qk_nope_head_dim`` content dims and
  ``qk_rope_head_dim`` rotary dims from one projection; keys and values from
  a latent of ``kv_lora_rank`` values a token, RMS-normalised (eps 1e-6) and
  projected up, with one rotary key of ``qk_rope_head_dim`` shared by every
  head; causal softmax attention scaled by (nope + rope) ** -0.5. Rotary
  positions rotate the two halves of the rotary dims;
- the first ``first_dense_layers`` blocks end in a SwiGLU MLP of ``d_ff``;
  the rest in a mixture of experts: sigmoid scores of ``router_experts``
  experts, the ``moe_topk`` of largest score plus ``router_bias`` chosen, each
  weighted by its score (without the bias) over the chosen scores' sum, times
  ``routed_scale``; of the routed experts only the ``n_experts`` held here,
  from ``first_expert`` on, are computed: each over every token, weighted by
  that token's weight for it (zero where it was not chosen), so nothing is
  dispatched and nothing is dropped; ``n_shared_experts`` shared experts, one
  SwiGLU of ``n_shared_experts * expert_d_ff``, are added once;
- a final RMSNorm and an untied head over the vocabulary held here.

Memory: as the transformer reference, over blocks of batch rows and of
queries, each layer under ``jax.checkpoint``.

Parameters are the nested dict ``init_params`` makes (``dense_blocks`` and
``blocks`` stacked over their layers), the tree the program must match leaf
for leaf. ``router_bias`` starts non-zero so that a program that ignores it,
or weighs by it, reads apart from this one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import transformer
from chipbench.reference.transformer import (  # noqa: F401  (the harness reads these here)
    PRECISION,
    bf16_share,
    grad_bf16_share,
    leaf_norms,
    seed_key,
)

# the published modelling code's RMSNorm default, used by the latent's norm
KV_NORM_EPS = 1e-6
ROUTER_BIAS_STD = 0.02


def init_params(m: dict, key) -> dict:
    """Seeded float32 weights: matrices N(0, 1/fan_in), the router N(0, 0.02),
    its bias N(0, 0.02), norm scales near 1, the token embedding small."""
    D, V, H = m["d_model"], m["vocab"], m["n_heads"]
    R, nope, rope, vd = m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    E, Fe, Fs = m["n_experts"], m["expert_d_ff"], m["n_shared_experts"] * m["expert_d_ff"]
    n_dense, n_moe = m["first_dense_layers"], m["n_layers"] - m["first_dense_layers"]
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def block(L):
        return {
            "ln_attn_scale": 1.0 + normal((L, D), 0.02),
            "attn_wq": normal((L, D, H * (nope + rope)), D**-0.5),
            "attn_wkv_a": normal((L, D, R + rope), D**-0.5),
            "attn_kv_norm_scale": 1.0 + normal((L, R), 0.02),
            "attn_wkv_b": normal((L, R, H * (nope + vd)), R**-0.5),
            "attn_wo": normal((L, H * vd, D), (H * vd) ** -0.5),
            "ln_mlp_scale": 1.0 + normal((L, D), 0.02),
        }

    dense = dict(block(n_dense), **{
        "mlp_gate": normal((n_dense, D, m["d_ff"]), D**-0.5),
        "mlp_up": normal((n_dense, D, m["d_ff"]), D**-0.5),
        "mlp_down": normal((n_dense, m["d_ff"], D), m["d_ff"] ** -0.5),
    })
    moe = dict(block(n_moe), **{
        "router": normal((n_moe, D, m["router_experts"]), 0.02),
        "router_bias": normal((n_moe, m["router_experts"]), ROUTER_BIAS_STD),
        "expert_gate": normal((n_moe, E, D, Fe), D**-0.5),
        "expert_up": normal((n_moe, E, D, Fe), D**-0.5),
        "expert_down": normal((n_moe, E, Fe, D), Fe**-0.5),
        "shared_gate": normal((n_moe, D, Fs), D**-0.5),
        "shared_up": normal((n_moe, D, Fs), D**-0.5),
        "shared_down": normal((n_moe, Fs, D), Fs**-0.5),
    })
    return {
        "tok_embed": normal((V, D), 0.02),
        "dense_blocks": dense,
        "blocks": moe,
        "ln_final_scale": 1.0 + normal((D,), 0.02),
        "lm_head": normal((D, V), D**-0.5),
    }


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=PRECISION)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary positions on (B, S, heads, d), rotating the two halves of d."""
    S, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, q0):
    """Causal softmax attention of queries at positions q0.. over all keys."""
    s = _mm("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    qpos = q0 + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(jnp.arange(k.shape[1])[None, :] <= qpos, s, -jnp.inf)
    return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _mla(m, p, x, q_block):
    B, S, _ = x.shape
    H, R = m["n_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = _mm("bsd,de->bse", x, p["attn_wq"]).reshape(B, S, H, nope + rope)
    kv_a = _mm("bsd,de->bse", x, p["attn_wkv_a"])
    latent = _rms(kv_a[..., :R], p["attn_kv_norm_scale"], KV_NORM_EPS)
    kv = _mm("bsr,re->bse", latent, p["attn_wkv_b"]).reshape(B, S, H, nope + vd)
    k_rope = _rope(kv_a[..., R:].reshape(B, S, 1, rope), m["rope_theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], m["rope_theta"])], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))], axis=-1)
    v = kv[..., nope:]
    attend = jax.checkpoint(_attend, static_argnums=(3,))
    o = jnp.concatenate(
        [attend(q[:, s0 : s0 + q_block], k, v, s0) for s0 in range(0, S, q_block)], axis=1
    )
    return _mm("bse,ed->bsd", o.reshape(B, S, H * vd), p["attn_wo"])


def _swiglu(x, gate, up, down):
    return _mm("bsf,fd->bsd", jax.nn.silu(_mm("bsd,df->bsf", x, gate)) * _mm("bsd,df->bsf", x, up), down)


def _moe(m, p, x):
    """The held experts' part of the routed output, plus the shared experts."""
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", x, p["router"]))
    _, choice = jax.lax.top_k(scores + p["router_bias"], m["moe_topk"])
    w = jnp.take_along_axis(scores, choice, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * m["routed_scale"]
    held = m["first_expert"] + jnp.arange(m["n_experts"])
    # (B, S, E): each token's weight for each held expert, zero where not chosen
    gates = jnp.sum(jnp.where(choice[..., None] == held, w[..., None], 0.0), axis=-2)
    h = jax.nn.silu(_mm("bsd,edf->bsef", x, p["expert_gate"])) * _mm("bsd,edf->bsef", x, p["expert_up"])
    y = _mm("bsef,efd->bsed", h, p["expert_down"])
    routed = jnp.sum(y * gates[..., None], axis=-2)
    return routed + _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])


def _block(m, p, x, q_block, moe):
    eps = m["norm_eps"]
    x = x + _mla(m, p, _rms(x, p["ln_attn_scale"], eps), q_block)
    xn = _rms(x, p["ln_mlp_scale"], eps)
    if moe:
        return x + _moe(m, p, xn)
    return x + _swiglu(xn, p["mlp_gate"], p["mlp_up"], p["mlp_down"])


def nll_sum(m, params, tokens, labels, mask, q_block):
    """Summed next-token negative log-likelihood over the unmasked positions."""
    x = params["tok_embed"][tokens]
    for stack, moe in (("dense_blocks", False), ("blocks", True)):
        for layer in range(params[stack]["ln_attn_scale"].shape[0]):
            p = jax.tree.map(lambda a: a[layer], params[stack])
            x = jax.checkpoint(lambda p, x, moe=moe: _block(m, p, x, q_block, moe))(p, x)
    x = _rms(x, params["ln_final_scale"], m["norm_eps"])
    logits = _mm("bsd,dv->bsv", x, params["lm_head"])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask)


# ---------------------------------------------------------------------------
# model FLOPs, the numerators of the utilization and roofline metrics
# ---------------------------------------------------------------------------


def _mla_matmul_params(m: dict) -> int:
    D, H, R = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return D * H * (nope + rope) + D * (R + rope) + R * H * (nope + vd) + H * vd * D


def mla_flops_per_token(m: dict, seq: int) -> float:
    """Latent attention of every layer in one training token: 6 x its
    projections' weights, plus the score and value products at sequence
    ``seq`` (6 L H S (qk + v) head dims; PaLM, arXiv:2204.02311, App. B)."""
    L, H = m["n_layers"], m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return 6.0 * L * _mla_matmul_params(m) + 6.0 * L * H * seq * (qk + m["v_head_dim"])


def moe_flops_per_token(m: dict) -> float:
    """The expert layers in one training token: 6 x (router, the routed
    experts a token is expected to send here, ``moe_topk`` x held / router
    width, and the shared experts)."""
    D, E = m["d_model"], m["router_experts"]
    expert = 3 * D * m["expert_d_ff"]
    per_layer = D * E + m["moe_topk"] * m["n_experts"] / E * expert + m["n_shared_experts"] * expert
    return 6.0 * (m["n_layers"] - m["first_dense_layers"]) * per_layer


def train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one training token: latent attention, the expert
    layers, the leading dense layers' SwiGLU and the head (6 N_matmul plus
    attention's products; recomputation not counted)."""
    D = m["d_model"]
    dense = 6.0 * m["first_dense_layers"] * 3 * D * m["d_ff"]
    return mla_flops_per_token(m, seq) + moe_flops_per_token(m) + dense + 6.0 * D * m["vocab"]


class Reference(transformer.Reference):
    """The transformer reference's CLT-k, sgdm and readings over this model's
    weights and loss."""

    def __init__(self, model: dict, mix: dict):
        super().__init__(model, mix)
        q_block = min(mix["seq"], mix.get("reference_q_block", 1024))

        def grad_block(params, tokens, labels, mask, count):
            f = lambda p: nll_sum(model, p, tokens, labels, mask, q_block) / count
            return jax.value_and_grad(f)(params)

        self._init = jax.jit(lambda key: init_params(model, key))
        self._grad_block = jax.jit(grad_block)
