"""A cell on several chips: one learner per chip (``chipbench/workers.py``).

On four virtual CPU devices, in a subprocess so that this process keeps its
one CPU device (as ``tests/test_distributed.py`` runs them), the harness
runs the cell ``paper.clt_k.b32s128.dp4`` at a tiny size: a sound run is
correct, its first 3 steps match the single-device stacked step of the same
four learners, and a whole run of the control (the program's own bfloat16
path) or with each fault the cell can have planted under ``TrainLoop.step``
is not correct. A one-chip cell's program is the
plain ``TrainLoop``.
"""

import os
import re
import subprocess
import sys
import textwrap

from chipbench import run

TINY = {
    "arch_type": "dense", "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
    "d_ff": 256, "vocab": 512, "norm": "layernorm", "norm_eps": 1e-5, "mlp": "gelu_tanh",
    "qkv_bias": True, "rope_theta": 10000.0, "tie_embeddings": True,
}
SIZE = dict(local_batch=8, seq=32, warmup_steps=1, reference_rows=8)

FOUR_LEARNERS = f"""
    import jax
    from chipbench import faults, run
    from repro.training import TrainLoop

    assert len(jax.devices()) == 4
    seed = 2**31 + 17
    res = run.resolve("paper.clt_k.b32s128.dp4")
    res["config"] = dict(res["config"], name="tiny", model={TINY!r})
    res["mix"] = dict(res["mix"], **{SIZE!r})

    prog = run.build(res)
    state, traffic = run.start(res, prog, seed)
    specs = {{str(x.sharding.spec) for x in jax.tree.leaves(state.sc_state.residues)}}
    assert specs == {{"PartitionSpec('data',)"}}, specs
    state, sharded, _ = run.program_readings(res, prog, state, traffic, seed)
    assert prog.loop._compressed._cache_size() == 1

    stacked = dict(res, cell=dict(res["cell"], chips=1))
    one = run.build(stacked)
    state, traffic = run.start(stacked, one, seed)
    _, single, _ = run.program_readings(stacked, one, state, traffic, seed)
    gaps = run.compare(sharded, dict(single, grad_norms=single["ghat_norms"]))
    assert all(g < 1e-4 for g in gaps.values()), gaps

    result = run.run_cell(res, seed, 0.2, False, chip=False)
    assert result["correct"] and result["device"]["count"] == 4, result
    control = dict(res, config=dict(res["config"], compute_dtype="bfloat16"))
    result = run.run_cell(control, seed, 0.2, False, chip=False)
    assert not result["correct"], result["checks"]
    step = TrainLoop.step
    for fault in faults.applicable(4):
        TrainLoop.step = faults.wrap(step, fault)
        result = run.run_cell(res, seed, 0.2, False, chip=False)
        TrainLoop.step = step
        assert not result["correct"], (fault, result["checks"])
    print("FOUR LEARNERS OK")
"""


def test_four_learners_on_four_devices():
    root = run.ROOT
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(FOUR_LEARNERS)],
                         capture_output=True, text=True, env=env, timeout=600, cwd=root)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-8000:]}"
    assert "FOUR LEARNERS OK" in out.stdout


def test_one_chip_program_is_the_plain_train_loop():
    import jax

    from repro.training import TrainLoop

    res = run.resolve("paper.clt_k.b32s128")
    res["config"] = dict(res["config"], name="tiny", model=TINY)
    res["mix"] = dict(res["mix"], **SIZE)
    prog = run.build(res)
    assert type(prog.loop) is TrainLoop and prog.put is jax.device_put
    plain = TrainLoop(model=prog.loop.model, optimizer=prog.loop.optimizer,
                      schedule=prog.loop.schedule, sc_cfg=prog.loop.sc_cfg, n_workers=1)
    state, traffic = run.start(res, prog, 5)
    batch = prog.put(traffic.batch(0))

    def program(loop):
        """The module's computations, without the source locations of the
        calls that built and compiled it."""
        text = loop.compiled(state, batch, 0).as_text()
        return [re.sub(r", metadata=\{[^}]*\}", "", line) for line in text.splitlines()
                if line.startswith(("%", "ENTRY", "  "))]

    assert program(prog.loop) == program(plain)
