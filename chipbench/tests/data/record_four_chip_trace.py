#!/usr/bin/env python3
"""Records ``four_chip.xplane.pb`` and ``four_chip.hlo.txt`` beside this
file: a traced window of the harness's worker-sharded step on four TPU
chips, one learner each, at a tiny size (2 layers, d_model 128, vocab 512,
batch 4 x 32 a learner, the jnp kernels of the cell
``paper.clt_k.b32s128.dp4``), and the text of the compiled step it ran,
whose collectives the tests of the trace reduction read. Source paths in
the text are made relative to the checkout.

    python3 chipbench/tests/data/record_four_chip_trace.py   # on a four-chip TPU host
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    from chipbench import run, scopes

    res = run.resolve("paper.clt_k.b32s128.dp4")
    res["config"] = dict(res["config"], name="tiny", model=dict(
        res["config"]["model"], n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
        d_ff=256, vocab=512,
    ))
    res["mix"] = dict(res["mix"], local_batch=4, seq=32, warmup_steps=1, trace_steps=3)
    keep = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        result = scopes.scoped_run(res, 5, keep)
        shutil.copy(os.path.join(keep, "trace.xplane.pb"), os.path.join(HERE, "four_chip.xplane.pb"))
        with open(os.path.join(keep, "step.hlo.txt")) as f:
            text = f.read().replace(f'"{ROOT}/', '"')
        with open(os.path.join(HERE, "four_chip.hlo.txt"), "w") as f:
            f.write(text)
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
