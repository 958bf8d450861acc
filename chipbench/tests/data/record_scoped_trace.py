#!/usr/bin/env python3
"""Records ``small_scoped.xplane.pb`` and ``small_scoped.hlo.txt`` beside this
file: a traced window of the harness's step on one TPU at the tiny size of
``record_small_trace.py`` (2 layers, d_model 128, vocab 512, batch 4 x 32),
and the text of the compiled step it ran, whose op_name metadata carries the
step's named scopes; the tests of ``chipbench/scopes.py`` read both.

    python3 chipbench/tests/data/record_scoped_trace.py   # on a TPU host
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

    res = run.resolve("paper.clt_k.b32s128")
    res["config"] = dict(res["config"], name="tiny", model=dict(
        res["config"]["model"], n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
        d_ff=256, vocab=512,
    ))
    res["mix"] = dict(res["mix"], local_batch=4, seq=32, warmup_steps=1, trace_steps=3)
    keep = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        result = scopes.scoped_run(res, 5, keep)
        for name in ("scoped.xplane.pb", "scoped.hlo.txt"):
            shutil.copy(os.path.join(keep, name), os.path.join(HERE, f"small_{name}"))
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
