#!/usr/bin/env python3
"""Bring-up check: the ScaleCom training path, natively on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the worker-sharded step on four chips

One chip. The paper transformer at its published widths (56.8M params) trains
through ``repro.launch.train`` for a few steps — 8 stacked workers, local batch
4, seq 128, clt_k with chunk 64, 2 dense warm-up steps — once with the
3-launch Pallas reduce and once with the single-launch fused kernel. Both the
dense warm-up program and the compressed program run; the compressed program
must contain Pallas kernels (``tpu_custom_call``). Then one ``scalecom_reduce``
over the full-width gradient tree runs with the pallas backend (unfused and
fused) and with the jnp backend, and the results are compared.

Four chips. One worker per chip on a ("data",) mesh: the worker-sharded
compressed step is checked against the single-device stacked step on the same
inputs, and the dense data-parallel step runs beside them. The collectives the
compiler put around the Pallas kernels are printed.

The script pins ``JAX_PLATFORMS=tpu`` before JAX is imported, so it fails
where there is no TPU, and it refuses interpret-mode Pallas. Every phase must
pass; the exit code is 0 only then, and only then is the last line of stdout
the JSON object ``{"ok": true, "device": {...}}``. Step times it prints are
one-off readings from a single run, not benchmarks.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import math
import os
import sys
import time
import traceback

os.environ["JAX_PLATFORMS"] = "tpu"
ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "paper-transformer-base"
STEPS = 6
TRAIN_ARGV = [
    "--arch", ARCH, "--full-width", "--workers", "8", "--local-batch", "4",
    "--seq", "128", "--compressor", "clt_k", "--warmup-steps", "2",
    "--steps", str(STEPS), "--log-every", "1",
]


class PhaseError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu", f"JAX found no TPU: {devs}")
    check(len(devs) >= n_chips, f"need {n_chips} TPU chips, JAX sees {len(devs)}")
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)} "
        f"(jax {jax.__version__})")
    return devs


def native_pallas(spec):
    """The resolved kernel backend, which must be Pallas compiled by Mosaic."""
    from repro.backends import resolve_backend

    be = resolve_backend(spec)
    check(be.name == "pallas", f"backend {spec!r} resolved to {be.name!r}, not pallas")
    check(not be._interp(), "the pallas backend would run in interpret mode")
    return be


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def clt_k_config(backend):
    """The reduce settings launch.train gives the paper transformer."""
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig

    return ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=64), beta=0.1, min_size=1024,
        backend=backend,
    )


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_train(train, device, *, fused: bool) -> None:
    """A few full-width steps through launch.train's loop: dense warm-up, then
    the compressed program."""
    import jax
    import numpy as np

    from repro.training import run_training

    label = "fused" if fused else "3-launch"
    args = train.parse_args(TRAIN_ARGV)
    cfg, loop, state, batches = train.build(args, fused=fused)
    native_pallas(loop.sc_cfg.backend)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    log(f"[{label}] model {cfg.name}: {n_params / 1e6:.2f}M params, d_model "
        f"{cfg.d_model}, {cfg.n_layers} layers, vocab {cfg.vocab}; "
        f"{args.workers} workers x local batch {args.local_batch} x seq {args.seq}")

    batch0 = next(batches)
    for name, fn in (("dense warm-up", loop._dense), ("compressed", loop._compressed)):
        t0 = time.perf_counter()
        compiled = fn.lower(state, batch0).compile()
        secs = time.perf_counter() - t0
        n_calls = custom_calls(compiled)
        log(f"[{label}] {name} program: trace+compile {secs:.2f} s, "
            f"tpu_custom_call x{n_calls}")
        if name == "compressed":
            check(n_calls > 0, f"[{label}] compressed step has no Pallas kernel")

    state, history = run_training(
        loop, state, itertools.chain([batch0], batches), STEPS, log=None
    )
    jax.block_until_ready(state)
    prev = 0.0
    for h in history:
        kind = "compressed" if h["step"] >= args.warmup_steps else "dense"
        log(f"[{label}] step {h['step']} ({kind}): loss {h['loss']:.6f}, "
            f"wall {h['wall_s'] - prev:.3f} s (one-off chip reading, not a benchmark)")
        prev = h["wall_s"]
    losses = [h["loss"] for h in history]
    check(len(losses) == STEPS, f"[{label}] ran {len(losses)} of {STEPS} steps")
    check(all(np.isfinite(losses)), f"[{label}] non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"[{label}] loss did not fall: {losses}")
    log(f"[{label}] loss {losses[0]:.6f} -> {losses[-1]:.6f}: finite and falling")
    log(f"[{label}] peak_bytes_in_use {peak_gib(device)}")


def phase_compare(device) -> None:
    """One scalecom_reduce over the full-width gradient tree: pallas (3-launch
    and fused) against the jnp oracles."""
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.core.scalecom import scalecom_reduce
    from repro.core.state import ScaleComState, init_state
    from repro.models import build_model

    n = 8
    model = build_model(registry.arch(ARCH), compute_dtype="float32", loss_chunk=64)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    base = clt_k_config("jnp")
    zero = jax.eval_shape(lambda: init_state(shapes, n, min_size=base.min_size))

    @jax.jit
    def inputs(key):
        leaves, tdef = jax.tree.flatten(shapes)
        keys = jax.random.split(key, len(leaves))
        g = [jax.random.normal(k, (n,) + s.shape) for k, s in zip(keys, leaves)]
        res = {
            path: {"q": 0.5 * jax.random.normal(jax.random.fold_in(key, i), enc["q"].shape)}
            for i, (path, enc) in enumerate(sorted(zero.residues.items()))
        }
        return jax.tree.unflatten(tdef, g), ScaleComState(res, jnp.asarray(3, jnp.int32))

    grads, state = inputs(jax.random.PRNGKey(1))

    def reduce(backend, fused):
        cfg = dataclasses.replace(base, backend=backend, fused=fused)
        t0 = time.perf_counter()
        fn = jax.jit(lambda g, s: scalecom_reduce(g, s, cfg)[:2]).lower(grads, state).compile()
        secs = time.perf_counter() - t0
        out = jax.block_until_ready(fn(grads, state))
        return out, secs, custom_calls(fn)

    (ref_ghat, ref_state), secs, _ = reduce("jnp", False)
    log(f"[compare] jnp reduce: trace+compile {secs:.2f} s")
    for fused in (False, True):
        label = "fused" if fused else "3-launch"
        native_pallas("pallas")
        (ghat, new_state), secs, n_calls = reduce("pallas", fused)
        check(n_calls > 0, f"[compare] {label} reduce has no Pallas kernel")
        same_idx = all(
            bool(jnp.array_equal(a != 0, b != 0))
            for a, b in zip(jax.tree.leaves(ghat), jax.tree.leaves(ref_ghat))
        )

        def worst(xs, ys, rtol=1e-5, atol=1e-6):
            return max(
                float(jnp.max(jnp.abs(x - y) - (atol + rtol * jnp.abs(y))))
                for x, y in zip(jax.tree.leaves(xs), jax.tree.leaves(ys))
            )

        ghat_excess = worst(ghat, ref_ghat)
        res_excess = worst(new_state.residues, ref_state.residues)
        ok = same_idx and ghat_excess <= 0 and res_excess <= 0
        log(f"[compare] pallas {label} vs jnp: trace+compile {secs:.2f} s, "
            f"tpu_custom_call x{n_calls}; selected indices equal: {same_idx}; "
            f"max excess over rtol=1e-5/atol=1e-6: ghat {ghat_excess:.3e}, "
            f"residues {res_excess:.3e} -> {'PASS' if ok else 'FAIL'}")
        check(ok, f"[compare] pallas {label} disagrees with jnp")
        del ghat, new_state
    log(f"[compare] peak_bytes_in_use {peak_gib(device)}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def collectives_report(label: str, compiled) -> None:
    from repro.analysis.hlo import analyze_module

    text = compiled.as_text()
    ops = analyze_module(text).collectives
    kinds = {}
    for op in ops:
        cnt, nbytes = kinds.get(op.kind, (0, 0))
        kinds[op.kind] = (cnt + op.count, nbytes + op.bytes_local * op.count)
    summary = ", ".join(
        f"{k} x{int(c)} ({b / 2**20:.1f} MiB)" for k, (c, b) in sorted(kinds.items())
    )
    log(f"[{label}] collectives: {summary or 'none'}; "
        f"tpu_custom_call x{text.count('tpu_custom_call')}")
    gathers = sorted(
        (op for op in ops if op.kind == "all-gather"),
        key=lambda op: -op.bytes_local,
    )
    for op in gathers[:8]:
        result = op.line.split("=", 1)[1].split("all-gather", 1)[0].strip()
        log(f"[{label}]   all-gather {op.bytes_local / 2**20:.2f} MiB -> {result}")


def four_chip_programs(model, opt, sched, state, batch, mesh, n: int):
    """The jitted programs of the four-chip phase, each with its input
    shardings: the single-device stacked step, the worker-sharded compressed
    step with the jnp and with the pallas kernels, and the dense
    data-parallel step. ``state``/``batch`` may be shapes."""
    import jax

    from repro.compat.jax_compat import NamedSharding, P
    from repro.core.state import ScaleComState
    from repro.training.train_step import build_train_step

    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    wshard = jax.tree.map(lambda _: data, state.params)
    replicated = jax.tree.map(lambda _: rep, state)
    state_sh = dataclasses.replace(
        replicated,
        sc_state=ScaleComState(
            jax.tree.map(lambda _: data, state.sc_state.residues), rep
        ),
    )
    batch_sh = jax.tree.map(lambda _: data, batch)

    def sharded(backend):
        step = build_train_step(
            model, opt, sched, clt_k_config(backend), n_workers=n, worker_axis="data",
            worker_shardings=wshard,
        )
        return jax.jit(step, in_shardings=(state_sh, batch_sh))

    dense = build_train_step(model, opt, sched, clt_k_config("jnp"), n_workers=n, mode="dense")
    return {
        "single-device stacked": jax.jit(
            build_train_step(model, opt, sched, clt_k_config("jnp"), n_workers=n)
        ),
        "worker-sharded": sharded("jnp"),
        "worker-sharded pallas": sharded("pallas"),
        "dense data-parallel": jax.jit(dense, in_shardings=(replicated, batch_sh)),
    }


def phase_four_chips(devices) -> None:
    """One worker per chip: the worker-sharded compressed step against the
    single-device stacked step on the same inputs, and the dense step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import jax_compat
    from repro.configs import registry
    from repro.data import make_batches
    from repro.models import build_model
    from repro.optim import make_optimizer, schedule
    from repro.training import init_train_state

    n = 4
    cfg = registry.arch(ARCH)
    model = build_model(cfg, compute_dtype="float32", loss_chunk=64)
    opt = make_optimizer("sgdm")
    sched = schedule.constant(0.05)
    state, _ = init_train_state(
        model, opt, clt_k_config("jnp"), jax.random.PRNGKey(0), n_workers=n
    )
    batch = jax.tree.map(jnp.asarray, next(make_batches(cfg.vocab, n, 4, 128, seed=1)))
    log(f"[4 chips] {cfg.name} full width, {n} workers (one per chip), "
        f"local batch 4, seq 128")
    native_pallas("auto")
    mesh = jax_compat.make_mesh((n,), ("data",), devices=devices[:n])
    progs = four_chip_programs(model, opt, sched, state, batch, mesh, n)

    # matmuls at full f32 precision in every program, so that the comparison
    # sees the partitioning and not the rounding of bf16 passes
    out = {}
    with jax.default_matmul_precision("highest"), jax_compat.set_mesh(mesh):
        for name, fn in progs.items():
            t0 = time.perf_counter()
            try:
                compiled = fn.lower(state, batch).compile()
            except NotImplementedError as e:
                # GSPMD cannot partition a Mosaic kernel: the pallas reduce on
                # a worker-sharded mesh needs a shard_map formulation
                check(name == "worker-sharded pallas", f"[{name}] {e}")
                log(f"[{name}] refused by the compiler: {e}")
                continue
            log(f"[{name}] trace+compile {time.perf_counter() - t0:.2f} s")
            collectives_report(name, compiled)
            if name != "worker-sharded pallas":
                out[name] = jax.block_until_ready(compiled(state, batch))

    s_ref, m_ref = out["single-device stacked"]
    s_sh, m_sh = out["worker-sharded"]
    m_dn = out["dense data-parallel"][1]
    for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_sh.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5)
    losses = {k: float(v[1]["loss"]) for k, v in out.items()}
    log(f"[4 chips] loss: {losses}")
    check(all(math.isfinite(v) for v in losses.values()), "[4 chips] non-finite loss")
    check(abs(losses["worker-sharded"] - float(m_ref["loss"])) < 1e-3,
          "[4 chips] worker-sharded loss differs from single-device")
    check(abs(float(m_dn["loss"]) - float(m_ref["loss"])) < 1e-3,
          "[4 chips] dense loss differs from single-device")
    log("[4 chips] worker-sharded == single-device: params allclose "
        "(rtol=2e-4, atol=1e-5), loss within 1e-3; dense loss within 1e-3")
    for i, d in enumerate(devices[:n]):
        log(f"[4 chips] chip {i} peak_bytes_in_use {peak_gib(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + jnp/pallas comparison on one chip; "
                         "4: only the worker-sharded step on four chips")
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.launch import train

        cache = train.enable_compile_cache()
        devs = require_tpu(args.chips)
        log(f"compile cache: {cache}")
        if args.chips == 1:
            phase_train(train, devs[0], fused=False)
            phase_train(train, devs[0], fused=True)
            phase_compare(devs[0])
        else:
            phase_four_chips(devs)
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
