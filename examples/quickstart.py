"""Quickstart: train a small LM with ScaleCom gradient compression, then
compare against the uncompressed baseline — the paper's Table-2 experiment in
~2 minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py

Hacking on the repo? The static invariant checker (compat boundary, tracer
hygiene, wire-byte coverage, collective schedule, obs hot path) is
``PYTHONPATH=src python -m repro.analysis.scalecheck`` — see ROADMAP.md
"Static checks". The scale & failure scenario harness (worker sweeps with
straggler/drop/stale-residue faults and per-step invariants) is
``PYTHONPATH=src python -m repro.harness --scenarios all --workers 8`` —
see ROADMAP.md "Scenario harness". Want to see INSIDE a run? The telemetry
subsystem (ROADMAP.md "Observability") records jit-safe taps (measured wire
bytes, build-up, contraction gamma) + wall-clock spans:

    PYTHONPATH=src python -m repro.launch.train --steps 40 \\
        --trace-dir /tmp/trace --metrics-every 10
    PYTHONPATH=src python -m repro.obs.report /tmp/trace/events.jsonl

then load /tmp/trace/trace.json in chrome://tracing or Perfetto.

On TPU-class backends the whole per-tensor inner loop (select -> EF update
-> ghat scatter) can run as ONE VMEM-resident Pallas launch instead of
three: set ``ScaleComConfig(fused=True)`` (it is off by default) —
bitwise-identical results, ~7 -> ~3 modeled HBM passes over the residue; see
ROADMAP.md for what the chip has measured of it.
"""

import sys

sys.path.insert(0, "src")

import jax

from repro import obs
from repro.configs import registry
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig
from repro.data import make_batches
from repro.models import build_model
from repro.optim import make_optimizer, schedule
from repro.training import TrainLoop, init_train_state, run_training

WORKERS, STEPS = 8, 60


def train(compressor: str, chunk: int = 64, beta: float = 1.0):
    cfg = registry.smoke("paper-transformer-base")
    model = build_model(cfg, compute_dtype="float32", loss_chunk=16)
    sc = ScaleComConfig(
        compressor=CompressorConfig(compressor, chunk=chunk),
        beta=beta,
        min_size=512,
        warmup_steps=5,  # the paper trains a few epochs dense first
    )
    opt = make_optimizer("sgdm")
    loop = TrainLoop(model=model, optimizer=opt, schedule=schedule.constant(0.05),
                     sc_cfg=sc, n_workers=WORKERS, log_every=20)
    state, _ = init_train_state(model, opt, sc, jax.random.PRNGKey(0),
                                n_workers=WORKERS)
    batches = make_batches(cfg.vocab, WORKERS, 2, 64, seed=0)
    print(f"--- {compressor} (chunk={chunk}, beta={beta}) ---")
    _, hist = run_training(loop, state, batches, STEPS)
    return hist[-1]["loss"]


def overlap_preview(bucket_mb: float = 25.0):
    """The overlap-aware bucketed launch: what `--bucket-mb` buys.

    The full trainer enables it with

        PYTHONPATH=src python -m repro.launch.train --bucket-mb 25

    (`--no-overlap` keeps the buckets but drops the ordering hints; without
    `--bucket-mb` the reduce is unbucketed). Here we just print the modeled
    timeline for the paper's transformer: how much of the compressed
    all-reduce hides behind backward compute at this bucket size.
    """
    from repro.analysis.perfmodel import overlap_report, reference_transformer_perf

    rep = overlap_report(reference_transformer_perf(), "scalecom",
                         int(bucket_mb * (1 << 20)))
    print(f"\n--- overlap model: transformer-base, --bucket-mb {bucket_mb:g} ---")
    print(f"buckets={rep['n_buckets']}  "
          f"hidden_fraction={rep['hidden_fraction']:.2f}  "
          f"exposed_comm={rep['exposed_comm'] * 1e3:.2f}ms  "
          f"speedup_vs_one_shot={rep['speedup_vs_unbucketed']:.2f}x")


if __name__ == "__main__":
    # run_training logs through the (silent-by-default) repro logger;
    # a console consumer opts in:
    obs.enable_console_logging()
    dense = train("none")
    scalecom = train("clt_k", chunk=64, beta=1.0)
    print(f"\nfinal loss  dense={dense:.4f}  scalecom(64x)={scalecom:.4f}  "
          f"gap={scalecom - dense:+.4f}")
    print("ScaleCom trains to ~baseline loss while all-reducing 64x fewer bytes.")
    overlap_preview()
