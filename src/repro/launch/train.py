"""Training driver: trains a registered architecture on synthetic data with
ScaleCom, simulating n workers stacked on one device (the worker axis runs
unsharded; the dense warm-up and the compressed step are separate programs).

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --workers 8 --steps 200 --compressor clt_k --chunk 64 --beta 0.1

Widths default to the architecture's SMOKE variant, which runs anywhere
(CPU included, with Pallas kernels in interpret mode). ``--full-width`` loads
the published widths (the paper transformer is 56.8M params) and is sized for
a TPU, where the kernel backend resolves to native Pallas; ``chip_smoke.py``
at the repo root drives this path on one TPU v5e.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import obs
from repro.compat import jax_compat
from repro.configs import registry
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig
from repro.data import make_batches
from repro.models import build_model
from repro.optim import make_optimizer, schedule
from repro.training import TrainLoop, init_train_state, run_training


_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and no
    other directory is set here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored) — fixed because the path is part
    of the cache key. Call it before the first compile of the process: JAX
    settles the cache location at that compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-transformer-base")
    ap.add_argument("--full-width", action="store_true",
                    help="train the architecture at its published widths "
                         "(registry.arch) instead of its SMOKE variant")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--local-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default="sgdm")
    ap.add_argument("--compressor", default="clt_k",
                    choices=["clt_k", "true_topk", "local_topk", "random_k", "none"])
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--beta", type=float, default=0.1)
    ap.add_argument("--warmup-steps", type=int, default=10)
    ap.add_argument("--residue-dtype", default="fp32",
                    choices=["fp32", "bf16", "fp8", "fp8_ec"])
    ap.add_argument("--groups", type=int, default=None)
    ap.add_argument("--backend", default="auto", choices=["auto", "jnp", "pallas"],
                    help="kernel backend for the chunked reduce ops "
                         "(repro.backends; auto = env var > TPU probe > jnp)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep Pallas tile geometry for this model's tensor "
                         "sizes and persist winners to the autotune cache "
                         "before training (see repro.backends.autotune)")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="overlap-aware bucketed reduce: pack tensors into "
                         "~this many MB per launch bucket (core.overlap) so "
                         "per-bucket compress+all-reduce can hide behind "
                         "backward compute. Default: $SCALECOM_BUCKET_MB if "
                         "set, else unbucketed; 0 forces unbucketed")
    ap.add_argument("--no-overlap", action="store_true",
                    help="keep the bucketed launch but drop the "
                         "optimization_barrier ordering hints (the "
                         "synchronous per-bucket fallback; numerics are "
                         "identical either way)")
    ap.add_argument("--preflight-scenarios", default=None, metavar="NAMES",
                    help="before training, run the failure-scenario harness "
                         "(repro.harness) at this worker count / compressor / "
                         "groups / residue dtype: comma-separated scenario "
                         "names or 'all'. Any invariant violation — or a "
                         "topology the planner rejects — aborts the launch")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="enable the telemetry subsystem (repro.obs): jit-safe "
                         "metric taps on the reduce (measured wire bytes, "
                         "build-up, contraction gamma, codec error), wall-"
                         "clock step spans, and write DIR/trace.json (Chrome "
                         "trace, Perfetto-loadable) + DIR/events.jsonl "
                         "(summarize with `python -m repro.obs.report`)")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="with --trace-dir: sample the paper's residue-"
                         "similarity diagnostics (core.metrics."
                         "residue_similarity_report) every N steps via a "
                         "lax.cond tap — no retrace. 0 disables")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--history-out", default=None)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    if args.metrics_every and not args.trace_dir:
        ap.error("--metrics-every requires --trace-dir (the similarity taps "
                 "need the telemetry run to land anywhere)")
    if args.arch not in registry._MODULES:
        ap.error(f"unknown arch {args.arch}; choices: {list(registry._MODULES)}")
    return args


def build(args: argparse.Namespace, **sc_overrides):
    """Model, loop, initial state and batch iterator for parsed ``args``.

    ``sc_overrides`` replace ScaleComConfig fields after the CLI has set them
    (chip_smoke.py pins ``fused`` this way). Returns (cfg, loop, state,
    batches).
    """
    cfg = (registry.arch if args.full_width else registry.smoke)(args.arch)
    model = build_model(cfg, compute_dtype="float32", loss_chunk=64)
    # --bucket-mb: None -> "auto" ($SCALECOM_BUCKET_MB probe), 0 -> force the
    # unbucketed single-shot reduce, > 0 -> bucketed at that size
    if args.bucket_mb is None:
        buckets = None
        bucket_bytes = ScaleComConfig.bucket_bytes
    elif args.bucket_mb <= 0:
        buckets = False
        bucket_bytes = ScaleComConfig.bucket_bytes
    else:
        buckets = True
        bucket_bytes = int(args.bucket_mb * (1 << 20))
    sc_cfg = ScaleComConfig(
        compressor=CompressorConfig(args.compressor, chunk=args.chunk),
        beta=args.beta,
        min_size=1024,
        residue_dtype=args.residue_dtype,
        groups=args.groups,
        backend=args.backend,
        warmup_steps=args.warmup_steps,
        bucket_bytes=bucket_bytes,
        overlap=not args.no_overlap,
        telemetry=args.trace_dir is not None,
        metrics_every=args.metrics_every,
    )
    sc_cfg = dataclasses.replace(sc_cfg, **sc_overrides)
    opt = make_optimizer(args.optimizer)
    sched = schedule.linear_warmup(schedule.constant(args.lr), args.warmup_steps)

    state, _ = init_train_state(
        model, opt, sc_cfg, jax.random.PRNGKey(args.seed), n_workers=args.workers
    )
    loop = TrainLoop(
        model=model, optimizer=opt, schedule=sched, sc_cfg=sc_cfg,
        n_workers=args.workers, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=max(args.steps // 2, 1) if args.checkpoint_dir else 0,
        log_every=args.log_every, buckets=buckets,
    )
    batches = make_batches(
        cfg.vocab, args.workers, args.local_batch, args.seq, seed=args.seed,
        vision_tokens=cfg.vision_tokens if cfg.arch_type == "vlm" else 0,
        d_model=cfg.d_model,
        encoder_seq=cfg.encoder_seq if cfg.is_encdec else 0,
    )
    return cfg, loop, state, batches


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)

    if args.preflight_scenarios:
        from repro.harness.scenarios import SCENARIOS, run_scenario

        names = (
            list(SCENARIOS)
            if args.preflight_scenarios == "all"
            else [s.strip() for s in args.preflight_scenarios.split(",") if s.strip()]
        )
        for name in names:
            res = run_scenario(
                name, args.workers, compressor=args.compressor,
                chunk=args.chunk, groups=args.groups,
                residue_dtype=args.residue_dtype,
            )
            print(f"[launch.train] preflight {name}: "
                  f"dist={res.final_distance:.4f}/{res.tolerance:.4f} "
                  f"{'ok' if res.passed else 'VIOLATION'}")
            if not res.passed:
                for v in res.violations:
                    print(f"[launch.train]   {v}")
                raise SystemExit(f"preflight scenario {name!r} failed")

    print(f"[launch.train] {jax_compat.describe()}")

    _, loop, state, batches = build(args)
    if args.autotune and args.backend != "jnp":
        from repro.backends import autotune as _at

        wins = _at.autotune_params(
            state.params, args.chunk, min_size=loop.sc_cfg.min_size
        )
        for key, best in wins.items():
            print(f"[launch.train] autotune {key}: block_chunks={best} "
                  f"-> {_at.cache_path()}")
    elif args.autotune:
        print("[launch.train] --autotune skipped: backend=jnp never consults "
              "the Pallas tile cache")
    telemetry = None
    if args.trace_dir:
        telemetry = obs.TelemetryRun(
            args.trace_dir,
            backend_name=args.backend,
            extra_provenance={"arch": args.arch, "compressor": args.compressor,
                              "workers": args.workers},
        )
    # run_training's default log is the (silent-by-default) telemetry logger;
    # the CLI is the consumer that wants visible step lines
    obs.enable_console_logging()
    try:
        state, history = run_training(
            loop, state, batches, args.steps, telemetry=telemetry
        )
    finally:
        if telemetry is not None:
            paths = telemetry.close()
            print(f"[launch.train] trace -> {paths['trace']}")
            print(f"[launch.train] events -> {paths['events']} "
                  f"(summarize: python -m repro.obs.report {paths['events']})")
    final = history[-1]
    print(f"final: loss={final['loss']:.4f} at step {final['step']}")
    if args.history_out:
        os.makedirs(os.path.dirname(args.history_out) or ".", exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)
    return history


if __name__ == "__main__":
    main()
