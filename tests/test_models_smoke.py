"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned family runs one forward/train step on CPU — output shapes + no NaNs —
plus prefill/decode cache-consistency for every decode path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import build_model

ARCHS = list(registry.ASSIGNED_ARCHS)
B, S = 2, 32


def _batch(cfg, key, with_labels=True):
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if with_labels:
        # labels apart from tokens: a tied head scores a token's own id highest
        b["labels"] = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab)
        b["mask"] = jnp.ones((B, S))
    if cfg.arch_type == "vlm":
        b["vision"] = jax.random.normal(key, (B, cfg.vision_tokens, cfg.d_model))
    if cfg.is_encdec:
        b["frames"] = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model))
    return b


@pytest.fixture(scope="module")
def models():
    cache = {}
    key = jax.random.PRNGKey(0)
    for name in ARCHS:
        cfg = registry.smoke(name)
        m = build_model(cfg, compute_dtype="float32", loss_chunk=16)
        params, axes = m.init(key)
        cache[name] = (cfg, m, params, axes)
    return cache


@pytest.mark.parametrize("name", ARCHS)
def test_forward_loss_finite(models, name):
    cfg, m, params, _ = models[name]
    loss, aux = jax.jit(m.loss)(params, _batch(cfg, jax.random.PRNGKey(1)))
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    if cfg.n_experts:
        # every routed pair lands on a held expert when all are held
        assert float(aux["moe_routed_here"]) == 1.0
        if cfg.router == "softmax":
            assert "moe_lb_loss" in aux and np.isfinite(float(aux["moe_lb_loss"]))
        else:  # noaux_tc: the bias balances the load, no auxiliary loss
            assert "moe_lb_loss" not in aux


@pytest.mark.parametrize("name", ARCHS)
def test_train_step_updates_and_finite(models, name):
    """One SGD step decreases nothing pathological: grads finite, params move."""
    cfg, m, params, _ = models[name]
    batch = _batch(cfg, jax.random.PRNGKey(2))
    g = jax.jit(jax.grad(lambda p: m.loss(p, batch)[0]))(params)
    leaves = jax.tree.leaves(g)
    assert all(np.isfinite(np.asarray(x)).all() for x in leaves)
    gnorm = float(
        jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
    )
    assert gnorm > 0


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_decode_consistency(models, name):
    """decode_step(token T) after prefill(tokens[:T]) must reproduce the
    prefill logits of the T+1-length prompt — exercises every cache layout.
    MoE dispatch is dropless, so a token's output does not depend on the
    tokens batched with it."""
    cfg, m, params, _ = models[name]
    key = jax.random.PRNGKey(3)
    batch = _batch(cfg, key, with_labels=False)
    toks = batch["tokens"]
    prefix = dict(batch, tokens=toks[:, : S - 1])
    full = dict(batch, tokens=toks)
    ctx = (cfg.vision_tokens if cfg.arch_type == "vlm" else 0) + S - 1
    cap = ctx + 8
    logits_full, _ = jax.jit(lambda p, b: m.prefill(p, b, cap))(params, full)
    logits_pre, state = jax.jit(lambda p, b: m.prefill(p, b, cap))(params, prefix)
    logits_dec, _ = jax.jit(m.decode_step)(
        params, state, toks[:, S - 1], jnp.int32(ctx)
    )
    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_full), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("name", ["starcoder2-3b", "qwen2.5-14b"])
def test_sliding_window_decode_variant(models, name):
    """long_500k path: dense archs decode with a ring-buffer window cache."""
    cfg = registry.smoke(name)
    m = build_model(cfg, compute_dtype="float32", decode_window=16)
    params, _ = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)}
    logits, state = jax.jit(lambda p, b: m.prefill(p, b, S + 8))(params, batch)
    assert state["kv"]["k"].shape[2] == 16  # ring capacity == window
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(3):
        logits, state = jax.jit(m.decode_step)(params, state, tok, jnp.int32(S + i))
        assert np.isfinite(np.asarray(logits)).all()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


def test_window_ring_cache_matches_full_for_short_context():
    """Within the window, ring-cache decode == full-cache decode."""
    cfg = registry.smoke("starcoder2-3b")
    mw = build_model(cfg, compute_dtype="float32", decode_window=S + 8)
    mf = build_model(cfg, compute_dtype="float32")
    params, _ = mf.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)}
    lw, sw = jax.jit(lambda p, b: mw.prefill(p, b, S + 8))(params, batch)
    lf, sf = jax.jit(lambda p, b: mf.prefill(p, b, S + 8))(params, batch)
    np.testing.assert_allclose(np.asarray(lw), np.asarray(lf), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name", ["rwkv6-3b", "recurrentgemma-2b"])
def test_recurrent_state_is_context_length_independent(models, name):
    """SSM/hybrid decode state size must not grow with seq_len (long_500k)."""
    cfg, m, params, _ = models[name]
    s1 = jax.eval_shape(lambda: m.init_decode_state(B, 64))
    s2 = jax.eval_shape(lambda: m.init_decode_state(B, 4096))
    n1 = sum(np.prod(x.shape) for x in jax.tree.leaves(s1))
    n2 = sum(np.prod(x.shape) for x in jax.tree.leaves(s2))
    if name == "rwkv6-3b":
        assert n1 == n2  # pure SSM: exactly constant
    else:
        assert n2 <= n1 * 40  # hybrid: bounded by local window, not seq_len


def test_param_counts_match_analytic():
    """ArchConfig.param_count() tracks actual init within 10% (smoke scale)."""
    for name in ["phi3-medium-14b", "starcoder2-3b", "qwen2.5-14b", "moonlight-16b-a3b"]:
        cfg = registry.smoke(name)
        m = build_model(cfg, compute_dtype="float32")
        params, _ = m.init(jax.random.PRNGKey(0))
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        est = cfg.param_count()
        assert abs(actual - est) / actual < 0.10, (name, actual, est)


def test_moonlight_counts_are_the_published_sizes():
    """16B-A3B: every expert of the 26 MoE layers held, 6 of 64 a token."""
    cfg = registry.arch("moonlight-16b-a3b")
    assert cfg.param_count() == 15_960_110_208
    assert cfg.active_param_count() == 2_914_776_192
    assert cfg._layer_kinds() == ("attn",) + ("moe",) * 26
