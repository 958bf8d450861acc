#!/usr/bin/env python3
"""Readings that set the limits of the correctness comparison.

    python3 chipbench/calibrate.py --workload <name> --seeds 1-12 \
        [--control 1-3] [--faults 1-3]

For every seed, in one process on the chip, at the cell's own sizes: the
program drives its first ``checked_steps`` steps exactly as a run's set-up
does, the reference trains the same weights on the same batches, and the
numbers the comparison reads are printed (the lower readings). For the
``--control`` seeds, the control: the program again with its own bfloat16
compute path switched on (``compute_dtype="bfloat16"``); for the
``--faults`` seeds, the program with each fault of
``chipbench/faults.py`` planted under ``TrainLoop.step`` (a state left
unchanged reads 1 by construction and needs no run). One JSON line per
reading; the last line gives, per number, the largest program reading and
the smallest reading of the control and of each fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds(text: str):
    if not text:
        return []
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import types

    import jax

    from chipbench import faults, run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--offset", type=int, default=0,
                    help="added to every seed, to reach large seeds")
    args = ap.parse_args(argv)
    res = run.resolve(args.workload)
    run.check_devices(jax.devices(), res["cell"]["chips"])
    run.enable_compile_cache()
    ref = run.reference_module(res["config"]).Reference(res["config"]["model"], res["mix"])
    program = run.build(res)
    control = run.build(res, compute_dtype="bfloat16") if args.control else None
    found = {}

    def reading(kind, prog, seed, refs):
        state, traffic = run.start(res, prog, seed)
        t0 = time.perf_counter()
        state, readings, _ = run.program_readings(res, prog, state, traffic, seed)
        del state
        t1 = time.perf_counter()
        if seed not in refs:
            refs[seed] = ref.readings(seed, traffic.batch, res["mix"]["checked_steps"])
        t2 = time.perf_counter()
        gaps = run.compare(readings, refs[seed])
        found.setdefault(kind, []).append(gaps)
        print(json.dumps({"kind": kind, "seed": seed, **gaps, "program_loss": readings["loss"],
                          "reference_loss": refs[seed]["loss"],
                          "program_s": t1 - t0, "reference_s": t2 - t1}), flush=True)

    for s in seeds(args.seeds):
        seed, refs = s + args.offset, {}
        reading("program", program, seed, refs)
        if s in seeds(args.control):
            reading("control_bf16_program", control, seed, refs)
        if s in seeds(args.faults):
            loop = program.loop
            for fault in faults.planted(res["mix"]["workers"]):
                broken = faults.wrap(type(loop).step, fault)
                proxy = types.SimpleNamespace(step=lambda st, b, i: broken(loop, st, b, i))
                reading(fault, program._replace(loop=proxy), seed, refs)
    summary = {}
    for name in run.CHECKS:
        summary[name] = {"program_max": max(g[name] for g in found["program"])}
        for kind, rows in found.items():
            if kind != "program":
                summary[name][f"{kind}_min"] = min(g[name] for g in rows)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
