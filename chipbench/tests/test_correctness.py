"""The comparison that decides ``correct``, driven through a whole run on the
CPU at a tiny size: a sound run passes, and every fault planted under the
timed path fails, under the limits of the cell ``paper.clt_k.b32s128``. The
look for a chip is skipped; nothing else is. The control, the program with
its own bfloat16 compute path switched on, fails them too.
"""

import pytest

from chipbench import faults, run

TINY = {
    "arch_type": "dense", "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
    "d_ff": 256, "vocab": 512, "norm": "layernorm", "norm_eps": 1e-5, "mlp": "gelu_tanh",
    "qkv_bias": True, "rope_theta": 10000.0, "tie_embeddings": True,
}
SEED = 2**31 + 17


def tiny(model=TINY, **config):
    res = run.resolve("paper.clt_k.b32s128")
    res["config"] = dict(res["config"], name="tiny", model=model, **config)
    res["mix"] = dict(res["mix"], local_batch=8, seq=32, warmup_steps=1, reference_rows=8)
    return res


def test_sound_run_is_correct():
    result = run.run_cell(tiny(), SEED, 0.2, False, chip=False)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"setup_s", "tokens_per_s"} <= set(result["metrics"])


def test_sound_run_with_untied_head_is_correct():
    result = run.run_cell(tiny(dict(TINY, tie_embeddings=False)), SEED, 0.2, False, chip=False)
    assert result["correct"], result["checks"]


def test_control_in_bfloat16_is_not_correct():
    result = run.run_cell(tiny(compute_dtype="bfloat16"), SEED, 0.2, False, chip=False)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("fault", faults.applicable(1))
def test_fault_is_not_correct(monkeypatch, fault):
    from repro.training import TrainLoop

    monkeypatch.setattr(TrainLoop, "step", faults.wrap(TrainLoop.step, fault))
    result = run.run_cell(tiny(), SEED, 0.2, False, chip=False)
    assert not result["correct"], result["checks"]


def test_compare_leaves_out_roundoff_leaves():
    ref = {"loss": [2.0], "grad_norms": [1.0, 1.0, 1e-9], "ghat_norms": [1.0, 1.0, 1e-9],
           "delta_norms": [1.0, 1.0, 1e-9], "ghat_bf16_share": 0.0, "grad_bf16_share": 0.0}
    prog = dict(ref, delta_norms=[1.0, 1.0, 5e-9])
    assert run.compare(prog, ref)["delta_norm_gap"] == 0.0
    prog = dict(ref, delta_norms=[1.0, 0.5, 1e-9])
    assert run.compare(prog, ref)["delta_norm_gap"] == 0.5
    assert run.judge(run.compare(prog, ref), {})[0] is False  # no limit, not correct


def test_bf16_share_tells_float32_from_bfloat16_values():
    import jax
    import jax.numpy as jnp

    share = run.reference_module({"reference": "transformer"}).bf16_share
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,), jnp.float32)
    tree = {"a": x.at[:1024].set(0.0), "b": x[:8]}
    assert float(share(tree)) < 0.01
    rounded = jax.tree.map(lambda v: v.astype(jnp.bfloat16).astype(jnp.float32), tree)
    assert float(share(rounded)) == 1.0
    assert float(share({"a": jnp.zeros(4)})) == 0.0


def test_judge_leaves_out_a_null_limit_and_refuses_a_missing_one():
    gaps = dict.fromkeys(run.CHECKS, 0.5)
    limits = dict.fromkeys(run.CHECKS, 1.0)
    assert run.judge(gaps, limits)[0] is True
    assert run.judge(gaps, dict(limits, loss_gap=0.1))[0] is False
    assert run.judge(gaps, dict(limits, loss_gap=None))[0] is True  # not compared
    del limits["loss_gap"]
    assert run.judge(gaps, limits)[0] is False


def test_grad_bf16_share_reads_learners_gradients_from_residues():
    import jax
    import jax.numpy as jnp

    ref = run.reference_module({"reference": "transformer"})
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 4096), jnp.float32)
    selected = jnp.arange(4096) % 64 == 0  # each learner's own value: no residue

    def residue(grad):
        return jnp.where(selected, 0.0, 0.1 * grad)

    assert float(ref.grad_bf16_share({"a": residue(g)}, 0.1)) < 0.01
    rounded = g.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(ref.grad_bf16_share({"a": residue(rounded)}, 0.1)) == 1.0
    # tensors weigh alike: one bfloat16 tensor of two reads a half
    both = {"a": residue(g), "b": residue(rounded)[:, :128]}
    assert float(ref.grad_bf16_share(both, 0.1)) == pytest.approx(0.5, abs=0.01)
