#!/usr/bin/env python3
"""Device time of the training step by phase: a profiler trace joined to the
compiled step's ``op_name`` metadata.

    python3 chipbench/scopes.py --workload <name> --seed <n> [--keep DIR]

The program names the parts of its step with ``jax.named_scope``: the phases
``fwd_bwd``, ``reduce`` and ``optimizer`` at the top of the step, the stages
of Algorithm 1 inside the reduce, and the model's blocks inside forward and
backward. XLA writes the scope path into every instruction's metadata
(``op_name="jit(train_step)/reduce/select/..."``); a fusion carries its root's.
Autodiff and loops wrap the path instead of dropping it
(``fwd_bwd/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/
rematted_computation/attn/...``). A device trace's ``XLA Ops`` events are
named by the same module's instructions, so ``scope_map`` of the module's
text and ``scope_times`` of a trace (``chipbench.trace.load``) give each
phase's and stage's self time, with nothing but JAX.

Run as a script, it makes one traced window of a cell as ``chipbench/run.py
--trace 1`` does (same program, warm-up and queued steps, ``trace_steps``
steps), reads the compiled step's text before the capture opens, and prints
one JSON line: per-phase device ms a step, the largest stages, the op kinds
no phase names, and the seconds ``TrainLoop.compiled`` took. ``--keep``
copies the trace and the module's text into a directory.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Dict, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = ("fwd_bwd", "reduce", "optimizer")
# the reduce's stages (core/scalecom.py) and the model's blocks
# (models/transformer.py)
STAGES = (
    "fold", "decode", "ef_sum", "select", "ef_update", "scatter", "worker_mean",
    "fused", "encode", "dense", "stats",
    "embed", "attn", "mlp", "loss",
)
# the model's blocks run only inside fwd_bwd; JAX hoists a block's
# loop-invariant work (the rotary tables of attn) out of the scan that
# forward and backward run, and the hoisted ops keep the block's name alone
BLOCKS = ("embed", "attn", "mlp", "loss")
UNSCOPED = "unscoped"
OTHER = "other"

_JIT = ("jit(", "pjit(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_LOOP = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def component(part: str) -> str:
    """A scope path component without its transform wrappers:
    ``transpose(jvp(attn))`` -> ``attn``; ``vmap(jvp())`` -> ``""``."""
    while part.endswith(")") and "(" in part:
        part = part[part.index("(") + 1:-1]
    return part


def classify(op_name: str) -> Tuple[str, str]:
    """(phase, stage) of one op_name: the phase is its first component after
    the leading ``jit(...)`` when that is in PHASES (``fwd_bwd`` when it is
    one of the model's BLOCKS), and the stage the first later component in
    STAGES, else ``other``. Anything else is unscoped."""
    parts = op_name.split("/")
    if not parts[0].startswith(_JIT):
        # no path at all, or one relative to a reducer's region
        return UNSCOPED, OTHER
    names = [component(p) for p in parts[1:]]
    if names and names[0] in BLOCKS:
        return "fwd_bwd", names[0]
    if not names or names[0] not in PHASES:
        return UNSCOPED, OTHER
    return names[0], next((n for n in names[1:] if n in STAGES), OTHER)


def scope_map(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (phase, stage)} for every instruction of every
    computation of a compiled module's text (``Compiled.as_text()``).

    XLA's rewrites leave some instructions without an op_name: a fusion
    wrapping one op, the first half of a reduction split in two, a
    zero-initialised buffer, a prefetch, a loop XLA makes for a relayout. An
    op_name that names an argument of the step (``state[0]['tok_embed']``)
    places nothing either. Such an instruction takes the scope of its nearest
    scoped neighbour, preferring at each distance its fused computation's
    root, then its users (the op it was made for; a computation's root only
    gathers outputs), then the loop that runs its computation, then its
    operands (the op it was made from: a relayout of an output). A parameter
    takes none."""
    own: Dict[str, str] = {}
    fused: Dict[str, str] = {}  # fusion -> its fused computation
    operands: Dict[str, list] = {}
    users: Dict[str, list] = {}
    roots: Dict[str, str] = {}  # computation -> its root
    loops: Dict[str, str] = {}  # loop body or condition -> the while op
    home: Dict[str, str] = {}  # instruction -> its computation
    parameters = set()
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op and op.group(1).startswith(_JIT) else None
        home[name] = computation
        if line.lstrip().startswith("ROOT "):
            roots[computation] = name
        if " parameter(" in line:
            parameters.add(name)
        called = _CALLS.search(line)
        if called:
            fused[name] = called.group(1)
        for body in _LOOP.findall(line):
            loops.setdefault(body, name)
        operands[name] = _OPERAND.findall(line[m.end():])
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)

    def neighbours(name):
        return (
            [roots[fused[name]]] if fused.get(name) in roots else []
        ) + [u for u in users.get(name, []) if roots.get(home[u]) != u] + (
            [loops[home[name]]] if home[name] in loops else []
        ) + operands[name]

    scope = {name: classify(op) for name, op in own.items() if op is not None}
    pending = [name for name in own if name not in scope and name not in parameters]
    while pending:
        found = {}
        for name in pending:
            near = (scope.get(other, (UNSCOPED, OTHER)) for other in neighbours(name))
            hit = next((sc for sc in near if sc[0] != UNSCOPED), None)
            if hit:
                found[name] = hit
        if not found:
            break
        scope.update(found)
        pending = [name for name in pending if name not in found]
    return {name: scope.get(name, (UNSCOPED, OTHER)) for name in own}


def instruction(event_name: str) -> str:
    """``%select_trailing.33 = (...) custom-call(...)`` -> ``select_trailing.33``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.removeprefix("ROOT ").lstrip("%")


def scope_times(trace: dict, window: Tuple[float, float], scopes: dict, top: int = 10) -> dict:
    """Self time (as ``chipbench.trace.reduce`` takes it for ``device_ops``)
    of the ops wholly inside ``window`` (ns), averaged over the chips:
    ``scope_s`` by phase, ``stage_s`` by ``phase/stage``, ``unscoped_s`` for
    the ops no phase names, and ``unscoped_ops``, their largest op kinds."""
    from chipbench import trace as tr

    lo, hi = window
    chips = sorted(trace["devices"])
    if not chips:
        raise ValueError("the trace has no TPU device plane")
    scope_ns: Dict[str, float] = {}
    stage_ns: Dict[str, float] = {}
    unscoped_ns: Dict[str, float] = {}
    for chip in chips:
        for name, start, dur, own in tr.self_times(trace["devices"][chip]["ops"]):
            if start < lo or start + dur > hi:
                continue
            phase, stage = scopes.get(instruction(name), (UNSCOPED, OTHER))
            if phase == UNSCOPED:
                kind = tr.op_kind(name)
                unscoped_ns[kind] = unscoped_ns.get(kind, 0.0) + own
            else:
                scope_ns[phase] = scope_ns.get(phase, 0.0) + own
                key = f"{phase}/{stage}"
                stage_ns[key] = stage_ns.get(key, 0.0) + own
    n = len(chips)

    def seconds(d):
        return {k: v / n * 1e-9 for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    return {
        "scope_s": seconds(scope_ns),
        "stage_s": seconds(stage_ns),
        "unscoped_s": sum(unscoped_ns.values()) / n * 1e-9,
        "unscoped_ops": [[k, s] for k, s in list(seconds(unscoped_ns).items())[:top]],
    }


def scoped_run(res: dict, seed: int, keep: str = None) -> dict:
    """One traced window of a cell, split by phase; see the module's doc."""
    import jax

    from chipbench import run
    from chipbench import trace as tr

    devices = jax.devices()
    run.check_devices(devices, res["cell"]["chips"])
    run.enable_compile_cache()
    mix = res["mix"]
    loop, make_state, _ = run.build(res)
    state, traffic = run.start(res, make_state, seed)
    first = mix["checked_steps"] + mix["warmup_steps"]
    state, *_ = run.drive(loop, state, traffic, 0, in_flight=mix["in_flight"], steps=first)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    hlo = loop.compiled(state, jax.device_put(traffic.batch(first)), first).as_text()
    compiled_s = time.perf_counter() - t0
    scopes = scope_map(hlo)
    tmp = tempfile.mkdtemp(prefix="chipbench-scopes-")
    try:
        jax.profiler.start_trace(tmp)
        state, _, done, _, dispatch = run.drive(
            loop, state, traffic, first, in_flight=mix["in_flight"],
            steps=mix["trace_steps"], annotate=jax.profiler.TraceAnnotation,
        )
        jax.profiler.stop_trace()
        path = tr.find_xplane(tmp)
        events = tr.load(path, ("window", "batch", "dispatch", "wait"))
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, "scoped.xplane.pb"))
            with open(os.path.join(keep, "scoped.hlo.txt"), "w") as f:
                f.write(hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    window = [e for e in events["host"] if e[0] == "window"][-1]
    lo, hi = window[1], window[1] + window[2]
    reduced = tr.reduce(events, (lo, hi))
    split = scope_times(events, (lo, hi), scopes)
    steps = len(done)

    def ms(s):
        return s / steps * 1e3

    return {
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "traced_steps": steps,
        "compiled_s": compiled_s,
        "host_dispatch_ms": sum(dispatch) / len(dispatch) * 1e3,
        "busy_ms": ms(reduced["busy_s"]),
        "reduce_kernel_ms": ms(reduced["kernel_s"]),
        "phase_ms": {p: ms(split["scope_s"].get(p, 0.0)) for p in PHASES},
        "unscoped_ms": ms(split["unscoped_s"]),
        "scopes": [[k, s / steps] for k, s in list(split["stage_s"].items())[:10]],
        "unscoped_ops": [[k, s / steps] for k, s in split["unscoped_ops"]],
    }


def main(argv=None) -> int:
    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", default=None, help="directory for the trace and the module text")
    args = ap.parse_args(argv)
    try:
        result = scoped_run(run.resolve(args.workload), args.seed, args.keep)
    except run.DeviceError as e:
        run.log(f"refused: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
