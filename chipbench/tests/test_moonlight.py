"""Moonlight-16B-A3B at one chip's share: the program against its plain
reference (``chipbench/reference/moonlight.py``) at a small Moonlight-shaped
size on the CPU, every matmul in float32 (``default_matmul_precision
("highest")``), and the configuration's pinned sizes and counts.

Tolerances: the program and the reference compute the same float32 sums in
different orders (fused projections, chunked attention and loss, grouped
against dense experts), which parts them by a few float32 rounding units a
value: 1e-5 of the loss, 1e-4 of a gradient leaf's largest value, 1e-5 of
an expert layer's output (values of order 1). The bfloat16 control and a
tensor left out of the reduce are read through the harness, under the
cell's own limits.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import peaks, run
from chipbench.reference import moonlight as ref
from chipbench.traffic.synthetic import Traffic
from repro.configs.base import ArchConfig
from repro.models import build_model, moe

WORKLOAD = "moonlight.clt_k.s8192"
SMALL = dict(
    run.resolve(WORKLOAD)["config"]["model"],
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96, vocab=256, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_experts=4, first_expert=2,
    router_experts=8, moe_topk=3, expert_d_ff=32,
)
SEED = 2**31 + 29


def arch(model):
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(name="small", **{k: v for k, v in model.items() if k in fields})


def batch(model, rows=2, seq=32, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, model["vocab"])
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:], "mask": jnp.ones((rows, seq))}


def moe_input(model, tokens=64, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, tokens, model["d_model"]))


def moe_params(model, seed=SEED):
    """One MoE layer's weights of the reference, unstacked."""
    params = ref.init_params(model, ref.seed_key(seed))
    return jax.tree.map(lambda a: a[0], params["blocks"])


def test_loss_and_gradients_match_the_reference():
    with jax.default_matmul_precision("highest"):
        params = ref.init_params(SMALL, ref.seed_key(SEED))
        b = batch(SMALL)
        model = build_model(arch(SMALL), compute_dtype="float32", loss_chunk=8)
        (loss, aux), grads = jax.value_and_grad(model.loss, has_aux=True)(params, b)
        want, want_grads = jax.value_and_grad(
            lambda p: ref.nll_sum(SMALL, p, b["tokens"], b["labels"], b["mask"], 16) / b["mask"].sum()
        )(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert 0 < float(aux["moe_routed_here"]) < 1  # 4 of 8 experts held
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        w = np.asarray(_at(want_grads, path))
        scale = np.abs(w).max()
        if jax.tree_util.keystr(path).endswith("['router_bias']"):
            assert scale == 0 and np.abs(np.asarray(g)).max() == 0  # picks, never weighs
            continue
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(g), w, rtol=0, atol=1e-4 * scale, err_msg=str(path))


def _at(tree, path):
    for key in path:
        tree = tree[key.key]
    return tree


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts held in 4 shares of 4: the shares' routed parts, with the
    shared experts counted once, give the uncut layer, the program's and the
    reference's."""
    uncut = dict(SMALL, n_experts=16, router_experts=16, first_expert=0)
    p = moe_params(uncut)
    x = moe_input(uncut)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe.moe_ffn(arch(uncut), p, x, dtype=jnp.float32)
        total = 0.0
        for i in range(4):
            share = dict(uncut, n_experts=4, first_expert=4 * i, n_shared_experts=2 if i == 0 else 0)
            held = dict(p, **{k: p[k][4 * i : 4 * i + 4] for k in ("expert_gate", "expert_up", "expert_down")})
            out, aux = moe.moe_ffn(arch(share), held, x, dtype=jnp.float32)
            total = total + out
        want = ref._moe(uncut, p, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=0, atol=1e-5)


def test_router_bias_picks_the_experts_but_does_not_weigh_them():
    cfg = arch(SMALL)
    p = moe_params(SMALL)
    xt = moe_input(SMALL)[0]
    with jax.default_matmul_precision("highest"):
        choice, weights, aux = moe.route(cfg, p, xt, jnp.float32)
        plain, _, _ = moe.route(cfg, dict(p, router_bias=jnp.zeros_like(p["router_bias"])), xt, jnp.float32)
        scores = jax.nn.sigmoid(xt @ p["router"])
    assert aux == {}  # noaux_tc: no auxiliary loss
    assert np.any(np.asarray(choice) != np.asarray(plain))  # the bias moves the choice
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * SMALL["routed_scale"]
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want), rtol=1e-6)


def test_skewed_routing_drops_no_token():
    """A bias that sends every token to the same 3 held experts: one expert
    gets every token, and the output still matches the reference's."""
    p = moe_params(SMALL)
    skew = jnp.zeros(SMALL["router_experts"]).at[2:5].set(10.0)
    p = dict(p, router_bias=skew)
    x = moe_input(SMALL, tokens=128)
    with jax.default_matmul_precision("highest"):
        out, aux = moe.moe_ffn(arch(SMALL), p, x, dtype=jnp.float32)
        want = ref._moe(SMALL, p, x)
    assert float(aux["moe_routed_here"]) == 1.0
    assert float(aux["moe_load_max"]) == pytest.approx(4 / 3)  # 3 of 4 held, T rows each
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0, atol=1e-5)


def test_rows_the_grouped_matmul_leaves_unwritten_do_not_leak(monkeypatch):
    """On the TPU ``ragged_dot`` leaves the rows past its groups unwritten
    (garbage), in its output and in the gradient of its rows. With those rows
    NaN, the layer's output and every gradient are unchanged and finite."""
    p = moe_params(SMALL)
    x = moe_input(SMALL)

    def past_groups_nan(y, sizes):
        return jnp.where(jnp.arange(y.shape[0])[:, None] < sizes.sum(), y, jnp.nan)

    def grads():
        def loss(p, x):
            out, _ = moe.moe_ffn(arch(SMALL), p, x, dtype=jnp.float32)
            return jnp.sum(out**2)

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1))(p, x)

    want = grads()
    unwritten = jax.lax.ragged_dot
    monkeypatch.setattr(moe, "_ragged_dot", lambda x, w, s: past_groups_nan(unwritten(x, w, s), s))
    vjp = moe._ragged_dot_vjp
    monkeypatch.setattr(moe, "_ragged_dot_vjp",
                        lambda x, w, s, g: (past_groups_nan(vjp(x, w, s, g)[0], s), vjp(x, w, s, g)[1]))
    got = grads()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def small_cell(**config):
    res = run.resolve(WORKLOAD)
    res["config"] = dict(res["config"], model=SMALL, **config)
    res["mix"] = dict(res["mix"], local_batch=4, seq=32, warmup_steps=1, reference_rows=2)
    return res


def _route_ignoring_the_bias(cfg, p, xt, dtype, route=moe.route):
    return route(cfg, dict(p, router_bias=jnp.zeros_like(p["router_bias"])), xt, dtype)


def _route_weighing_by_the_bias(cfg, p, xt, dtype, route=moe.route):
    choice, _, aux = route(cfg, p, xt, dtype)
    biased = jax.nn.sigmoid(xt @ p["router"].astype(dtype)) + p["router_bias"]
    w = jnp.take_along_axis(biased, choice, axis=-1)
    return choice, w / w.sum(-1, keepdims=True) * cfg.routed_scale, aux


@pytest.mark.parametrize("kind", ["sound", "control_bf16", "dropped_leaf",
                                  "ignores_router_bias", "weighs_by_router_bias"])
def test_a_harness_run_against_the_reference(monkeypatch, kind):
    """``run.build`` + ``program_readings`` against ``Reference.readings``
    through a whole run (the look for a chip skipped), under the cell's
    limits: sound, correct; the program's bfloat16 path, a tensor left out
    of the reduce, and a router that ignores its correction bias or weighs
    the experts by it, not."""
    from chipbench import faults
    from repro.training import TrainLoop

    res = small_cell(compute_dtype="bfloat16") if kind == "control_bf16" else small_cell()
    if kind == "dropped_leaf":
        monkeypatch.setattr(TrainLoop, "step", faults.wrap(TrainLoop.step, kind))
    if kind == "ignores_router_bias":
        monkeypatch.setattr(moe, "route", _route_ignoring_the_bias)
    if kind == "weighs_by_router_bias":
        monkeypatch.setattr(moe, "route", _route_weighing_by_the_bias)
    result = run.run_cell(res, SEED, 0.2, False, chip=False)
    assert result["correct"] is (kind == "sound"), result["checks"]
    assert result["failed"] == 0


def test_the_configuration_is_the_chips_share_of_moonlight():
    res = run.resolve(WORKLOAD)
    model = res["config"]["model"]
    assert arch(model).param_count() == 568_484_608
    shapes = jax.eval_shape(lambda k: ref.init_params(model, k), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 568_484_608
    assert ref.train_flops_per_token(model, 8192) == 2_912_157_696
    assert ref.mla_flops_per_token(model, 8192) == 1_671_168_000
    assert ref.moe_flops_per_token(model) == 574_095_360
    traffic = Traffic(res["mix"], model["vocab"], 1)
    work = run.work(res, shapes, traffic)
    assert work["flops_per_step"] == traffic.tokens_per_step * 2_912_157_696
    assert res["config"]["published"]["num_hidden_layers"] == 27
    for key in res["config"]["reduced"]:  # each cut shows under the source's name
        assert res["config"][key] == model[key] != res["config"]["published"][key]


def test_readers_of_the_blocks_split():
    res = run.resolve(WORKLOAD)
    rec = {"mix": res["mix"], "model": res["config"]["model"], "tokens_per_step": 8192,
           "peaks": peaks.PEAKS["TPU v5 lite"], "chips": 1, "traced_steps": 4,
           "scopes": {"stage_s": {"fwd_bwd/moe": 0.8, "fwd_bwd/mla": 2.0}}}
    got = run.read_metrics([{"name": n, "unit": "u"} for n in
                            ("moe_ms", "mla_ms", "moe_roofline", "mla_roofline")], rec)
    assert got["moe_ms"]["value"] == pytest.approx(200.0)
    assert got["mla_ms"]["value"] == pytest.approx(500.0)
    assert got["moe_roofline"]["value"] == pytest.approx(100 * 574_095_360 * 8192 / 0.2 / 197e12)
    assert got["mla_roofline"]["value"] == pytest.approx(100 * 1_671_168_000 * 8192 / 0.5 / 197e12)
    # a program without the blocks' scopes, as the parent's: nothing to read
    rec["scopes"] = {"stage_s": {"fwd_bwd/attn": 1.0}}
    assert run.read_metrics([{"name": "moe_roofline", "unit": "%"}], rec) == {}
