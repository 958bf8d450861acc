"""Distributed correctness on 8 (or 4) host devices — run in subprocesses so
the main pytest process keeps the single real CPU device (per the dry-run
isolation rule). Asserts:

  1. the sharded ScaleCom train step is numerically identical to the
     single-device run (same worker count, no mesh),
  2. the lowered HLO's only gradient all-reduce payloads are k-sized —
     the paper's O(1) communication property, checked structurally, and
  3. with one learner a device the worker mean sums per-chunk values
     fenced to their learner, and the reduce stages no fence otherwise.
"""

import os
import re
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_sharded_step_matches_single_device():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import registry
        from repro.core.compressors import CompressorConfig
        from repro.core.scalecom import ScaleComConfig
        from repro.data import make_batches
        from repro.models import build_model
        from repro.optim import make_optimizer, schedule
        from repro.training import init_train_state
        from repro.training.train_step import build_train_step
        from repro.compat import jax_compat
        from repro.distributed.sharding import specs_for_axes
        from repro.launch.mesh import make_test_mesh

        n = 4
        cfg = registry.smoke("starcoder2-3b")
        model = build_model(cfg, compute_dtype="float32", loss_chunk=16)
        sc = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=16), beta=0.1, min_size=512)
        opt = make_optimizer("sgdm")
        state, axes = init_train_state(model, opt, sc, jax.random.PRNGKey(0), n_workers=n)
        batch = jax.tree.map(jnp.asarray, next(make_batches(cfg.vocab, n, 2, 32, seed=1)))

        # reference: no mesh, plain jit
        step_ref = jax.jit(build_train_step(model, opt, schedule.constant(0.05), sc, n_workers=n))
        s_ref, m_ref = step_ref(state, batch)

        # sharded: mesh (4 data, 2 model), worker axis on data
        mesh = make_test_mesh((4, 2))
        pspecs = specs_for_axes(state.params, axes, "tp", mesh)
        wshard = jax.tree.map(lambda s: NamedSharding(mesh, P("data", *s)), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        step_sh = build_train_step(model, opt, schedule.constant(0.05), sc,
                                   n_workers=n, worker_axis="data", worker_shardings=wshard)
        with jax_compat.set_mesh(mesh):
            s_sh, m_sh = jax.jit(step_sh)(state, batch)
        for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_sh.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5)
        assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3
        print("SHARDED == SINGLE-DEVICE OK", float(m_ref["loss"]))
    """))


@pytest.mark.slow
def test_no_dense_gradient_allreduce_in_hlo():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import registry
        from repro.core.compressors import CompressorConfig
        from repro.core.scalecom import ScaleComConfig
        from repro.data import make_batches
        from repro.models import build_model
        from repro.optim import make_optimizer, schedule
        from repro.training import init_train_state
        from repro.training.train_step import build_train_step
        from repro.distributed.sharding import specs_for_axes
        from repro.compat import jax_compat
        from repro.launch.mesh import make_test_mesh
        from repro.analysis.hlo import analyze_module

        # pure-DP mesh: all cross-worker traffic is gradient traffic
        n = 8
        cfg = registry.smoke("starcoder2-3b")
        model = build_model(cfg, compute_dtype="float32", loss_chunk=16)
        opt = make_optimizer("sgdm")
        sched = schedule.constant(0.05)
        mesh = make_test_mesh((8,), ("data",))
        batch = jax.tree.map(jnp.asarray, next(make_batches(cfg.vocab, n, 1, 32, seed=1)))

        def lower(mode, sc):
            state, axes = init_train_state(model, opt, sc, jax.random.PRNGKey(0), n_workers=n)
            pspecs = specs_for_axes(state.params, axes, "tp", mesh)
            ws = jax.tree.map(lambda s: NamedSharding(mesh, P("data", *s)), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
            fn = build_train_step(model, opt, sched, sc, n_workers=n,
                                  worker_axis="data",
                                  worker_shardings=ws if mode=="scalecom" else None,
                                  mode=mode)
            # commit input shardings so the dense baseline actually
            # distributes (uncommitted args would replicate -> no collectives)
            rep = NamedSharding(mesh, P())
            dsh = NamedSharding(mesh, P("data"))
            state_sh = jax.tree.map(
                lambda x: dsh if (hasattr(x, "ndim") and x.ndim and x.shape[0] == n) else rep,
                state)
            batch_sh = jax.tree.map(lambda x: dsh, batch)
            with jax_compat.set_mesh(mesh):
                return jax.jit(fn, in_shardings=(state_sh, batch_sh)).lower(state, batch).compile()

        sc_c = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=64), beta=0.1, min_size=512)
        sc_d = ScaleComConfig(compressor=CompressorConfig("none"))
        comp = analyze_module(lower("scalecom", sc_c).as_text())
        dense = analyze_module(lower("dense", sc_d).as_text())
        from repro.analysis.hlo import collective_summary
        cs, ds = collective_summary(comp), collective_summary(dense)
        print("scalecom bytes:", cs["total_bytes"], "dense bytes:", ds["total_bytes"])
        # compressed gradient traffic must be far below dense all-reduce
        assert cs["total_bytes"] < ds["total_bytes"] / 10, (cs, ds)
    """))


@pytest.mark.slow
def test_ring_backend_matches_gspmd_path():
    """The shard_map ring backend (paper Remark 3) and the GSPMD worker-axis
    path implement the same Algorithm 1 — cross-validated numerically."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.compressors import CompressorConfig
        from repro.core.scalecom import ScaleComConfig, scalecom_reduce
        from repro.core.state import init_state
        from repro.compat import jax_compat
        from repro.distributed.ring import make_ring_reducer
        from repro.launch.mesh import make_test_mesh

        n, size, chunk, beta = 8, 4096, 16, 0.3
        mesh = make_test_mesh((8,), ("data",))
        cfg = CompressorConfig("clt_k", chunk=chunk)
        g = jax.random.normal(jax.random.PRNGKey(0), (n, size))
        m = jax.random.normal(jax.random.PRNGKey(1), (n, size))

        # GSPMD path
        sc = ScaleComConfig(compressor=cfg, beta=beta, min_size=1)
        state = init_state({"w": jnp.zeros((size,))}, n, min_size=1)
        state.residues["['w']"]["q"] = m
        ghat1, st1, _ = jax.jit(lambda g, s: scalecom_reduce(g, s, sc))({"w": g}, state)

        # explicit shard_map ring path
        reducer = make_ring_reducer(mesh, "data", cfg, beta)
        with jax_compat.set_mesh(mesh):
            ghat_rows, m_new = jax.jit(reducer)(g, m, jnp.int32(0))
        np.testing.assert_allclose(np.asarray(ghat_rows[0]), np.asarray(ghat1["w"]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(m_new),
                                   np.asarray(st1.residues["['w']"]["q"]),
                                   rtol=1e-5, atol=1e-6)
        print("RING == GSPMD OK")
    """))


# ---------------------------------------------------------------------------
# one learner a chip: the worker mean all-reduces the k per-chunk values
# ---------------------------------------------------------------------------

CHUNK = 64
LEARNERS = 4

# Builds the worker-sharded step of the paper transformer's shape at smoke
# widths (jnp kernels, clt_k, chunk 64) on 4 virtual CPU devices, one learner
# each, and writes its lowered and compiled text; then runs two reduce steps
# on the same learners' gradients sharded and unsharded and keeps both
# results.
_WORKER_SHARDED = """
    import dataclasses, os, sys
    import jax, jax.numpy as jnp, numpy as np
    from repro.compat.jax_compat import NamedSharding, P, make_mesh
    from repro.configs import registry
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig, scalecom_reduce
    from repro.core.state import ScaleComState
    from repro.data import make_batches
    from repro.models import build_model
    from repro.optim import make_optimizer, schedule
    from repro.training import init_train_state
    from repro.training.train_step import build_train_step

    out, n, chunk = sys.argv[1], {n}, {chunk}
    cfg = registry.smoke("paper-transformer-base")
    model = build_model(cfg, compute_dtype="float32", loss_chunk=16)
    sc = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=chunk), beta=0.1,
                        min_size=1024, backend="jnp")
    opt = make_optimizer("sgdm")
    state, _ = init_train_state(model, opt, sc, jax.random.PRNGKey(0), n_workers=n)
    batch = jax.tree.map(jnp.asarray, next(make_batches(cfg.vocab, n, 2, 32, seed=1)))

    mesh = make_mesh((n,), ("data",), devices=jax.devices()[:n])
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    sc_sh = ScaleComState(jax.tree.map(lambda _: split, state.sc_state.residues), whole)
    state_sh = dataclasses.replace(jax.tree.map(lambda _: whole, state), sc_state=sc_sh)
    step = build_train_step(model, opt, schedule.constant(0.05), sc, n_workers=n,
                            worker_axis="data",
                            worker_shardings=jax.tree.map(lambda _: split, state.params))
    lowered = jax.jit(step, in_shardings=(state_sh, split)).lower(state, batch)
    with open(os.path.join(out, "lowered.txt"), "w") as f:
        f.write(lowered.as_text(debug_info=True))
    with open(os.path.join(out, "compiled.txt"), "w") as f:
        f.write(lowered.compile().as_text())

    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    grads = [jax.tree.map(lambda p, k=k: jax.random.normal(k, (n,) + p.shape), state.params)
             for k in keys]
    sharded = jax.jit(lambda g, s: scalecom_reduce(g, s, sc, worker_sharding=split)[:2],
                      in_shardings=(jax.tree.map(lambda _: split, grads[0]), sc_sh))
    stacked = jax.jit(lambda g, s: scalecom_reduce(g, s, sc)[:2])
    arrays = {{}}
    for name, fn in (("sharded", sharded), ("stacked", stacked)):
        s = state.sc_state
        for t, g in enumerate(grads):
            ghat, s = fn(g, s)
            leaves = jax.tree_util.tree_leaves_with_path((ghat, s.residues))
            for path, leaf in leaves:
                arrays[f"{{name}}/{{t}}{{jax.tree_util.keystr(path)}}"] = np.asarray(leaf)
    np.savez(os.path.join(out, "reduce.npz"), **arrays)
"""


@pytest.fixture(scope="module")
def worker_sharded(tmp_path_factory):
    """The texts and reduce results ``_WORKER_SHARDED`` writes."""
    out = tmp_path_factory.mktemp("worker_sharded")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={LEARNERS}"
    code = textwrap.dedent(_WORKER_SHARDED.format(n=LEARNERS, chunk=CHUNK))
    run = subprocess.run(
        [sys.executable, "-c", code, str(out)],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert run.returncode == 0, f"STDOUT:\n{run.stdout}\nSTDERR:\n{run.stderr}"
    return out


def _worker_mean_all_reduces(hlo: str):
    """Result shapes of the compiled module's all-reduces whose op_name is
    under ``reduce/worker_mean``, one per operand of a combined all-reduce."""
    ar = set()
    shapes = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) all-reduce(?:-start)?\(", line)
        if m:
            ar.add(m.group(1))
            if not m.group(2).startswith("(") and "/worker_mean/" in line:
                shapes.append(m.group(2))
    for line in hlo.splitlines():
        m = re.match(r"\s*%\S+ = (\S+) get-tuple-element\(%(\S+)\)", line)
        if m and m.group(2) in ar and "/worker_mean/" in line:
            shapes.append(m.group(1))
    return [tuple(int(d) for d in re.match(r"\w+\[([\d,]*)\]", s).group(1).split(",") if d)
            for s in shapes]


def test_worker_mean_reduces_fenced_per_chunk_values(worker_sharded):
    """Before XLA's passes, every worker mean of the sharded step sums a
    (G, n_chunks) operand pinned to one learner a device."""
    text = (worker_sharded / "lowered.txt").read_text()
    mean_locs = set(re.findall(r'^(#loc\d+) = loc\("[^"]*/worker_mean/reduce_sum"', text, re.M))
    fence_locs = set(re.findall(
        r'^(#loc\d+) = loc\("[^"]*/worker_mean/sharding_constraint"', text, re.M))
    assert mean_locs and fence_locs
    fences = {}
    for m in re.finditer(
        r'(%\S+) = sdy\.sharding_constraint \S+ <@mesh, \[\{"data"\}, \{\?\}\]> '
        r": tensor<(\d+)x(\d+)xf32> loc\((#loc\d+)\)", text,
    ):
        if m.group(4) in fence_locs:
            fences[m.group(1)] = (int(m.group(2)), int(m.group(3)))
    means = re.findall(
        r"= stablehlo\.reduce\((%\S+) init: \S+\) applies stablehlo\.add across "
        r"dimensions = \[0\] : \(tensor<(\d+)x(\d+)xf32>.* loc\((#loc\d+)\)", text,
    )
    means = [m for m in means if m[3] in mean_locs]
    assert means and len(means) == len(fences)
    for operand, g, n_chunks, _ in means:
        assert fences.get(operand) == (int(g), int(n_chunks)) == (LEARNERS, int(n_chunks))


def test_worker_mean_all_reduce_carries_no_chunk_tile(worker_sharded):
    """No all-reduce of the worker mean carries a trailing chunk dimension:
    each device sums its learner's lanes before the exchange."""
    shapes = _worker_mean_all_reduces((worker_sharded / "compiled.txt").read_text())
    assert shapes
    assert all(len(s) == 1 and s[-1] != CHUNK for s in shapes), shapes


@pytest.mark.parametrize("t", [0, 1])
def test_worker_sharded_reduce_matches_stacked(worker_sharded, t):
    """ĝ and the new residues of the sharded reduce equal the single-device
    stacked reduce's, in each of two steps."""
    import numpy as np

    with np.load(worker_sharded / "reduce.npz") as z:
        names = [k.removeprefix("sharded/") for k in z.files if k.startswith(f"sharded/{t}")]
        assert names
        for name in names:
            np.testing.assert_allclose(z["sharded/" + name], z["stacked/" + name], rtol=1e-6)


def test_reduce_stages_no_fence_without_worker_sharding():
    """One learner, or a worker axis over one device: the reduce's lowered
    text is the text it has with no worker sharding at all."""
    import jax
    import jax.numpy as jnp

    from repro.compat.jax_compat import NamedSharding, P, make_mesh
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig, scalecom_reduce
    from repro.core.state import init_state

    sc = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=CHUNK), beta=0.1,
                        min_size=1024, backend="jnp")
    params = {"w": jnp.zeros((64, 128)), "b": jnp.zeros((4096,))}
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    one_device = NamedSharding(mesh, P("data"))
    for n in (1, 2):
        grads = jax.tree.map(lambda p: jnp.ones((n,) + p.shape), params)
        state = init_state(params, n, min_size=1024)
        texts = [
            jax.jit(lambda g, s, w=w: scalecom_reduce(g, s, sc, worker_sharding=w)[:2])
            .lower(grads, state).as_text()
            for w in (None, one_device)
        ]
        assert "sharding_constraint" not in texts[0]
        assert texts[1] == texts[0]
