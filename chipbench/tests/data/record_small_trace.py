#!/usr/bin/env python3
"""Records ``small.xplane.pb`` beside this file: a traced run of the harness on
one TPU at a tiny size (2 layers, d_model 128, vocab 512, batch 4 x 32), so
that the trace reduction's tests read a real device trace of the program.

    python3 chipbench/tests/data/record_small_trace.py   # on a TPU host
"""

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    from chipbench import run, trace

    res = run.resolve("paper.clt_k.b32s128")
    res["config"] = dict(res["config"], name="tiny", model=dict(
        res["config"]["model"], n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
        d_ff=256, vocab=512,
    ))
    res["mix"] = dict(res["mix"], local_batch=4, seq=32, warmup_steps=1, trace_steps=3)
    find = trace.find_xplane

    def keep(trace_dir):
        path = find(trace_dir)
        shutil.copy(path, os.path.join(HERE, "small.xplane.pb"))
        return path

    trace.find_xplane = keep
    result = run.run_cell(res, 5, 1.0, True)
    print(result["device"], result["metrics"])
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
