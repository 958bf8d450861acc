#!/usr/bin/env python3
"""On-chip benchmark of the ScaleCom training step.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name from ``BENCHMARK.json``:

- ``chipbench/configs/<config>.json``: the model as it is run (``model``),
  its source, the keys ``reduced`` from it, and the name of its plain
  reference in ``chipbench/reference/``;
- ``chipbench/traffic/<traffic>.json``: the job (learners, local batch,
  sequence, compressor, optimizer, steps before the window), read by the one
  generator ``chipbench/traffic/synthetic.py``;
- ``chipbench/metrics/<metric>.py``: one reader per metric;
- ``chipbench/limits/<workload>.json``: the limit of each number that the
  correctness comparison reads.

A run pins ``JAX_PLATFORMS=tpu``; it stops with a non-zero exit and prints
no result where JAX finds no TPU, fewer chips than the cell asks for, or a
``device_kind`` missing from ``chipbench/peaks.py``. It builds the program
through the calls ``repro.launch.train.build`` makes (``build_model``, the
train state, ``TrainLoop``) with the kernel backend, layout and fusion left
to the program's own defaults unless the mix names them, and makes the
weights from ``--seed`` on the device in one jitted call. Every cell runs
one learner per chip: its mix sets ``workers`` to the cell's ``chips``, and
a cell on more than one chip places them as ``chipbench/workers.py`` says.
Set-up drives the compressed step through its first ``checked_steps``
steps, reading what the correctness comparison needs, then
``warmup_steps`` more; the window then drives ``TrainLoop.step`` for
``--seconds``, the host building batch i+1 while ``in_flight`` steps are
queued on the device (after dispatching step i it waits for step
i - in_flight), as a training loop that reads its loss now and then does.
With ``--trace 1`` the same loop runs for ``trace_steps`` steps under
``jax.profiler``, and the record the per-layer readers get holds the
trace's reduction (busy, kernel and collective time) and its split by the
step's named phases. After the window the program's state is freed and the
reference trains the same weights on the same batches for ``checked_steps``
steps on the chip; the comparison decides ``correct``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and last
``checks``, each compared number beside its limit; the same numbers are the
last lines of stderr.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, NamedTuple

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKS = ("loss_gap", "ghat_norm_gap", "delta_norm_gap", "ghat_bf16_share_gap",
          "grad_bf16_share_gap")
# the harness's own spans on the profiler's host plane
HOST_SPANS = ("window", "batch", "dispatch", "wait")
# leaves whose reference gradient is under this share of the median leaf's
# are round-off in both implementations, and are left out of the comparison
ROUNDOFF_LEAF = 1e-3


class DeviceError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the benchmark's data
# ---------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, traffic mix, limits and metrics."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    mix = load_json(os.path.join(root, "chipbench", "traffic", f"{cell['traffic']}.json"))
    if mix["workers"] != cell["chips"]:
        raise ValueError(
            f"{workload}: one learner per chip, but the mix has {mix['workers']} "
            f"workers for {cell['chips']} chips"
        )
    limits_path = os.path.join(root, "chipbench", "limits", f"{workload}.json")

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell,
        "config": load_json(os.path.join(root, entry["file"])),
        "mix": mix,
        "limits": load_json(limits_path) if os.path.exists(limits_path) else {},
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def reader(metric: str):
    return importlib.import_module(f"chipbench.metrics.{metric}").read


def reference_module(config: dict):
    return importlib.import_module(f"chipbench.reference.{config['reference']}")


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def check_devices(devices, chips: int) -> dict:
    """The peaks of the devices JAX found, or DeviceError: no TPU, fewer than
    ``chips`` of them, or a kind that chipbench/peaks.py does not know."""
    from chipbench.peaks import PEAKS

    if not devices or devices[0].platform != "tpu":
        kind = devices[0].platform if devices else "none"
        raise DeviceError(f"JAX found no TPU (platform {kind}); this benchmark runs only on one")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} TPU chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in PEAKS:
        raise DeviceError(f"device kind {kind!r} is not in chipbench/peaks.py ({sorted(PEAKS)})")
    return PEAKS[kind]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path: the program's own
    choice ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), with
    every program cached, so that only a cell's first run compiles."""
    import jax

    from repro.launch.train import enable_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


class Program(NamedTuple):
    """The program under test as a run drives it: its ``TrainLoop``, a jitted
    ``make_state(key)`` that builds its initial state with the
    configuration's seeded weights, the state's shapes, and ``put(batch)``,
    which places a host batch where the step takes it."""

    loop: Any
    make_state: Callable
    shapes: Any
    put: Callable


def build(res: dict, compute_dtype: str = None) -> Program:
    """The program of a cell. ``compute_dtype`` overrides the
    configuration's (the control). On one chip, a ``TrainLoop`` as
    ``repro.launch.train.build`` makes it; on more, one learner per chip
    (``chipbench/workers.py``)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig
    from repro.models import build_model
    from repro.optim import make_optimizer, schedule
    from repro.training import TrainLoop, TrainState, init_train_state

    config, mix, chips = res["config"], res["mix"], res["cell"]["chips"]
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    arch = ArchConfig(
        name=config["name"], **{k: v for k, v in config["model"].items() if k in fields}
    )
    model = build_model(
        arch, compute_dtype=compute_dtype or config["compute_dtype"], loss_chunk=64
    )
    sc_cfg = ScaleComConfig(
        compressor=CompressorConfig(mix["compressor"], chunk=mix["chunk"], topm=mix["topm"]),
        beta=mix["beta"],
        min_size=mix["min_size"],
        residue_dtype=mix["residue_dtype"],
        **({"backend": mix["backend"]} if "backend" in mix else {}),
    )
    opt = make_optimizer(mix["optimizer"], momentum=mix["momentum"])
    shapes = jax.eval_shape(
        lambda: init_train_state(model, opt, sc_cfg, jax.random.PRNGKey(0),
                                 n_workers=mix["workers"])[0]
    )
    job = dict(model=model, optimizer=opt, schedule=schedule.constant(mix["lr"]),
               sc_cfg=sc_cfg, n_workers=mix["workers"])
    if chips == 1:
        loop, state_sharding, put = TrainLoop(**job), None, jax.device_put
    else:
        from chipbench import workers

        state_sharding, batch_sharding = workers.placement(jax.devices()[:chips], shapes)
        loop = workers.WorkerShardedLoop(
            **job, worker_axis=workers.AXIS, state_sharding=state_sharding,
            batch_sharding=batch_sharding,
        )

        def put(batch):
            return jax.device_put(batch, batch_sharding)

    ref = reference_module(config)
    own = jax.eval_shape(lambda k: ref.init_params(config["model"], k), jax.random.PRNGKey(0))
    if jax.tree.structure(own) != jax.tree.structure(shapes.params) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(shapes.params))
    ):
        raise ValueError("the program's parameters differ from the configuration's")

    def zeros(s):
        return jnp.zeros(s.shape, s.dtype)

    def initial(key):
        return TrainState(
            params=ref.init_params(config["model"], key),
            opt_state=jax.tree.map(zeros, shapes.opt_state),
            sc_state=jax.tree.map(zeros, shapes.sc_state),
            step=zeros(shapes.step),
        )

    make_state = (
        jax.jit(initial) if state_sharding is None
        else jax.jit(initial, out_shardings=state_sharding)
    )
    return Program(loop, make_state, shapes, put)


def start(res: dict, prog: Program, seed: int):
    """The initial state and the traffic of one seed."""
    from chipbench.traffic.synthetic import Traffic

    state = prog.make_state(reference_module(res["config"]).seed_key(seed))
    return state, Traffic(res["mix"], res["config"]["model"]["vocab"], seed)


def program_readings(res, prog: Program, state, traffic, seed):
    """Drive the first ``checked_steps`` steps and read what the comparison
    needs: each step's loss, the leaf norms of the reduced gradient the
    optimizer got in step 1 (its momentum after one step, which starts at
    zero) and that gradient's share of values bfloat16 holds exactly, the
    learners' own first gradients' share of them (read from the residues),
    and the leaf norms of the parameters' change after the last checked step.
    Returns (state, readings, seconds spent on the readings alone)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = reference_module(res["config"])
    norms = jax.jit(ref.leaf_norms)
    bf16_share = jax.jit(ref.bf16_share)
    grad_share = jax.jit(lambda r: ref.grad_bf16_share(r, res["mix"]["beta"]))
    # the initial weights are made again inside the program that reads the
    # change, so that they never sit beside the state as buffers of their own
    delta = jax.jit(lambda p, key: ref.leaf_norms(
        jax.tree.map(jnp.subtract, p, ref.init_params(res["config"]["model"], key))
    ))
    out = {"loss": []}
    extra = 0.0
    for t in range(res["mix"]["checked_steps"]):
        state, metrics = prog.loop.step(state, prog.put(traffic.batch(t)), t)
        out["loss"].append(float(metrics["loss"]))
        if t == 0:
            t0 = time.perf_counter()
            out["ghat_norms"] = np.asarray(norms(state.opt_state["m"]))
            out["ghat_bf16_share"] = float(bf16_share(state.opt_state["m"]))
            out["grad_bf16_share"] = float(grad_share(state.sc_state.residues))
            extra += time.perf_counter() - t0
    t0 = time.perf_counter()
    out["delta_norms"] = np.asarray(delta(state.params, ref.seed_key(seed)))
    extra += time.perf_counter() - t0
    return state, out, extra


def drive(prog: Program, state, traffic, first: int, *, in_flight: int, seconds=None,
          steps=None, annotate=None):
    """Run the step until ``seconds`` have passed or ``steps`` have completed,
    with ``in_flight`` steps queued on the device while the host builds the
    next batch: after dispatching step i it waits for step i - in_flight.
    The window opens at the first dispatch, with the first batch on the
    device and the chip idle, and closes when the last step completes;
    ``annotate`` names it ``window`` in a trace. Returns (state, t0,
    completion times, losses, host seconds inside each dispatch)."""
    import collections
    import contextlib

    import jax

    span = annotate or (lambda name: contextlib.nullcontext())

    def feed(i):
        with span("batch"):
            return prog.put(traffic.batch(i))

    i = first
    nxt = feed(i)
    jax.block_until_ready((state, nxt))
    done, losses, dispatch = [], [], []
    pending = collections.deque()

    def wait_one():
        with span("wait"):
            losses.append(float(pending.popleft()))
        done.append(time.perf_counter())

    with span("window"):
        t0 = time.perf_counter()
        while True:
            with span("dispatch"):
                d0 = time.perf_counter()
                state, metrics = prog.loop.step(state, nxt, i)
                dispatch.append(time.perf_counter() - d0)
            pending.append(metrics["loss"])
            i += 1
            while len(pending) > in_flight:
                wait_one()
            if seconds is not None and done and done[-1] - t0 >= seconds:
                break
            if steps is not None and len(done) + len(pending) >= steps:
                break
            nxt = feed(i)
        while pending:
            wait_one()
    return state, t0, done, losses, dispatch


def traced_window(prog: Program, state, traffic, first: int, mix: dict, keep: str = None):
    """``trace_steps`` steps of the window's loop under ``jax.profiler``, and
    the text of the compiled step they run, read before the capture opens.
    Returns (state, completion times, losses, dispatch seconds, record), the
    record as ``traced_record`` makes it. ``keep`` names a directory to copy
    the trace (``trace.xplane.pb``) and the text (``step.hlo.txt``) into."""
    import jax

    from chipbench import trace as tr

    t0 = time.perf_counter()
    hlo = prog.loop.compiled(state, prog.put(traffic.batch(first)), first).as_text()
    log(f"compiled step's text read in {time.perf_counter() - t0:.2f} s")
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        state, _, done, losses, dispatch = drive(
            prog, state, traffic, first, in_flight=mix["in_flight"],
            steps=mix["trace_steps"], annotate=jax.profiler.TraceAnnotation,
        )
        jax.profiler.stop_trace()
        path = tr.find_xplane(tmp)
        events = tr.load(path, HOST_SPANS)
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, "trace.xplane.pb"))
            with open(os.path.join(keep, "step.hlo.txt"), "w") as f:
                f.write(hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return state, done, losses, dispatch, traced_record(events, hlo)


def traced_record(events: dict, hlo: str) -> dict:
    """What the per-layer readers take from a traced window (``events`` as
    ``chipbench.trace.load`` gives them, with the harness's last ``window``
    span) and the compiled step's text: ``trace``, the reduction of
    ``chipbench.trace.reduce`` with the module's collectives, and
    ``scopes``, the split of ``chipbench.scopes.scope_times`` by the step's
    named phases (``scope_s``, ``stage_s``, ``unscoped_s``)."""
    from chipbench import scopes
    from chipbench import trace as tr

    windows = [e for e in events["host"] if e[0] == "window"]
    lo, hi = windows[-1][1], windows[-1][1] + windows[-1][2]
    return {
        "trace": tr.reduce(events, (lo, hi), collectives=scopes.collective_map(hlo)),
        "scopes": scopes.scope_times(events, (lo, hi), scopes.scope_map(hlo)),
    }


def work(res: dict, params, traffic) -> dict:
    """What one step does by the benchmark's own counts, for a program whose
    parameters have the shapes ``params``: its tokens, its model FLOPs (by
    the configuration's reference module's ``train_flops_per_token`` where
    it defines one, else by ``chipbench.counts``'), and the least HBM bytes
    of its reduce; and the configuration's ``model``, from which a reader
    can count the operations and bytes of a kernel of its own."""
    import jax

    from chipbench import counts

    model, mix = res["config"]["model"], res["mix"]
    flops = getattr(reference_module(res["config"]), "train_flops_per_token",
                    counts.train_flops_per_token)
    return {
        "model": model,
        "tokens_per_step": traffic.tokens_per_step,
        "flops_per_step": traffic.tokens_per_step * flops(model, mix["seq"]),
        "reduce_bytes_per_step": counts.reduce_min_bytes(
            [
                (math.prod(p.shape), mix["workers"], p.dtype.itemsize, 4)
                for p in jax.tree.leaves(params)
            ],
            mix["min_size"],
        ),
    }


def read_metrics(metrics: list, rec: dict) -> dict:
    """{name: {value, unit}} of each metric whose reader finds something in
    the run record ``rec``."""
    out = {}
    for m in metrics:
        value = reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the limits hold.

    loss_gap: the largest relative gap of a checked step's loss.
    ghat_norm_gap, delta_norm_gap: for the first step's reduced gradient and
    for the parameters' change over the checked steps, the worst leaf's gap
    between the program's norm and the reference's, over the reference's
    norm of that leaf or of the median leaf, whichever is larger. Leaves
    whose reference gradient is round-off (under ROUNDOFF_LEAF of the median
    leaf's) are left out.
    ghat_bf16_share_gap: the gap between the program's and the reference's
    share of the first reduced gradient's non-zero values that bfloat16
    holds exactly: a float32 gradient holds almost none, one computed in
    bfloat16 most. The norms cannot see that precision: at XLA's default
    matmul precision every matmul already rounds its operands to bfloat16,
    and two float32 implementations part by as much as bfloat16 activations.
    grad_bf16_share_gap: the same gap for the learners' own first gradients,
    read from the residues, with each tensor weighed alike: on a TPU the
    compiler keeps most of a bfloat16 step's gradient tensors in float32,
    so the few that stay bfloat16 are lost among the others' values in the
    reduced gradient, and the mean across learners rounds them away.
    """
    import numpy as np

    losses = [abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])]
    if len(prog["loss"]) != len(ref["loss"]) or not all(map(math.isfinite, losses)):
        return {name: math.inf for name in CHECKS}
    g = np.asarray(ref["grad_norms"])
    keep = g >= ROUNDOFF_LEAF * np.median(g)

    def worst(p, r):
        p, r = np.asarray(p, np.float64)[keep], np.asarray(r, np.float64)[keep]
        if p.shape != r.shape or not np.all(np.isfinite(p)):
            return math.inf
        return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))

    return {
        "loss_gap": max(losses),
        "ghat_norm_gap": worst(prog["ghat_norms"], ref["ghat_norms"]),
        "delta_norm_gap": worst(prog["delta_norms"], ref["delta_norms"]),
        "ghat_bf16_share_gap": abs(prog["ghat_bf16_share"] - ref["ghat_bf16_share"]),
        "grad_bf16_share_gap": abs(prog["grad_bf16_share"] - ref["grad_bf16_share"]),
    }


def judge(gaps: dict, limits: dict):
    """(correct, checks): every number at or under its limit. A cell's
    limits name every number of CHECKS; a number whose limit is null is not
    compared in that cell, as one that cannot tell the control from sound
    runs there (PERF.md, section 4); a number with no limit at all is a
    fault of the cell, and the run is not correct."""
    checks = {
        name: {"value": gaps[name], "limit": limits.get(name)} for name in CHECKS
    }
    correct = set(CHECKS) <= set(limits) and all(
        c["limit"] is None or c["value"] <= c["limit"] for c in checks.values()
    )
    return correct, checks


def reference_readings(res, traffic, seed):
    ref = reference_module(res["config"])
    r = ref.Reference(res["config"]["model"], res["mix"])
    return r.readings(seed, traffic.batch, res["mix"]["checked_steps"])


def memory_peak_bytes(devices):
    """Device memory the process has held at its peak, on the fullest chip.

    The TPU runtime keeps buffers (parameters, optimizer state, residues,
    inputs) and the scratch memory that compiled programs reserve in two
    counters: ``peak_bytes_in_use`` alone leaves a program's temporaries out.
    The peak is the sum of both counters' peaks. None where the backend
    reports neither."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(res: dict, seed: int, seconds: float, trace: bool, *, chip: bool = True) -> dict:
    """One run of a cell; returns the result object. ``chip=False`` skips the
    look for a TPU (the tests drive the rest of a run on the CPU)."""
    import jax

    t_import = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    chips = res["cell"]["chips"]
    peaks = check_devices(devices, chips) if chip else {
        "bf16_flops": math.nan, "hbm_bytes_per_s": math.nan,
    }
    devices = devices[:chips]
    enable_compile_cache()
    mix = res["mix"]

    t_build = time.perf_counter()
    prog = build(res)
    state, traffic = start(res, prog, seed)
    jax.block_until_ready(state)
    t_steps = time.perf_counter()
    state, readings, check_s = program_readings(res, prog, state, traffic, seed)
    first = mix["checked_steps"]
    state, *_ = drive(prog, state, traffic, first, in_flight=mix["in_flight"],
                      steps=mix["warmup_steps"])
    first += mix["warmup_steps"]
    jax.block_until_ready(state)
    t_end = time.perf_counter()
    setup_s = t_end - T_START - check_s
    log(f"set-up {setup_s:.2f} s: imports {t_import - T_START:.2f} s, device "
        f"{t_devices - t_import:.2f} s, compile cache {t_build - t_devices:.2f} s, "
        f"program and weights {t_steps - t_build:.2f} s, first "
        f"{first} steps {t_end - t_steps - check_s:.2f} s (readings {check_s:.2f} s apart)")

    rec = {"mix": mix, "peaks": peaks, "chips": chips, "setup_s": setup_s,
           **work(res, prog.shapes.params, traffic)}
    breakdown = None
    if not trace:
        state, t0, done, losses, _ = drive(
            prog, state, traffic, first, in_flight=mix["in_flight"], seconds=seconds
        )
        rec.update(window_s=done[-1] - t0, steps=len(done))
        gaps = sorted((b - a, i) for i, (a, b) in enumerate(zip([t0] + done, done)))
        log(f"window {done[-1] - t0:.3f} s, {len(done)} steps, median interval "
            f"{gaps[len(gaps) // 2][0] * 1e3:.3f} ms between completions, longest "
            + ", ".join(f"#{i} {d * 1e3:.1f} ms" for d, i in gaps[-3:]))
    else:
        state, done, losses, dispatch, traced = traced_window(prog, state, traffic, first, mix)
        rec.update(traced, traced_steps=len(done), dispatch_s=dispatch)
        breakdown = {k: rec["trace"][k] for k in ("device_ops", "idle_gaps")}
    attempted, failed = len(losses), sum(not math.isfinite(x) for x in losses)

    peak = memory_peak_bytes(devices)
    rec["memory_peak_bytes"] = peak
    del state, prog
    gc.collect()

    metrics = read_metrics(res["per_layer"] if trace else res["end_to_end"], rec)
    ref = reference_readings(res, traffic, seed)
    correct, checks = judge(compare(readings, ref), res["limits"])
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    if trace:
        device.update(busy_s=rec["trace"]["busy_s"], window_s=rec["trace"]["window_s"])
    result = {
        "correct": correct and failed == 0,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = resolve(args.workload)
        result = run_cell(res, args.seed, args.seconds, bool(args.trace))
    except DeviceError as e:
        log(f"refused: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
