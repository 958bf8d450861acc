import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape × mesh)
combination with ShapeDtypeStruct stand-ins (no allocation), then record
memory_analysis / cost_analysis / collective traffic for the roofline tables.

The XLA_FLAGS line above MUST precede every other import (jax locks the device
count on first init); this module is the only place 512 host devices exist.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-3b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod1|pod2|both]
    PYTHONPATH=src python -m repro.launch.dryrun --all --mode dense   # baseline

Results land in experiments/dryrun/<arch>__<shape>__<mesh>__<mode>.json.
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat import jax_compat
from repro.compat.jax_compat import Mesh, NamedSharding, P

from repro.analysis.roofline import analyze_compiled
from repro.configs import SHAPES, registry
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.scalecom import ScaleComConfig
from repro.core.compressors import CompressorConfig
from repro.core.state import init_state
from repro.distributed.sharding import specs_for_axes
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import make_optimizer
from repro.training.serve import decode_state_specs
from repro.training.train_step import TrainState, build_train_step

SDS = jax.ShapeDtypeStruct

# Archs whose residue/params need special handling at production scale (§5).
BIG_ARCHS = {"command-r-plus-104b", "kimi-k2-1t-a32b"}


def default_settings(arch: str, mesh_name: str) -> Dict[str, Any]:
    """Per-arch sharding/compression policy (DESIGN.md §5/§7)."""
    s: Dict[str, Any] = {
        "policy": "tp",
        "residue_dtype": "fp32",
        "worker_axes": ("data",) if mesh_name == "pod1" else ("pod", "data"),
        "groups": None,
        "chunk": 64,
        "microbatches": 1,
    }
    if arch in BIG_ARCHS:
        s["residue_dtype"] = "fp8"
        if mesh_name == "pod2":
            # hierarchical: pods are the ScaleCom workers; params fsdp-sharded
            s["policy"] = "fsdp"
            s["worker_axes"] = ("pod",)
    return s


# ---------------------------------------------------------------------------
# abstract input/state construction
# ---------------------------------------------------------------------------


def train_batch_sds(cfg: ArchConfig, shape: ShapeConfig, n_workers: int):
    local = shape.global_batch // n_workers
    S = shape.seq_len
    b = {
        "tokens": SDS((n_workers, local, S), jnp.int32),
        "labels": SDS((n_workers, local, S), jnp.int32),
        "mask": SDS((n_workers, local, S), jnp.float32),
    }
    if cfg.arch_type == "vlm":
        b["vision"] = SDS((n_workers, local, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec:
        b["frames"] = SDS((n_workers, local, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return b


def serve_batch_sds(cfg: ArchConfig, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    b = {"tokens": SDS((B, S), jnp.int32)}
    if cfg.arch_type == "vlm":
        b["vision"] = SDS((B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec:
        b["frames"] = SDS((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return b


def _tree_sds(tree):
    return jax.tree.map(lambda x: SDS(x.shape, x.dtype), tree)


def _opt_state_specs(opt_state_sds, param_specs):
    """m/v subtrees mirror params; scalars replicate."""
    out = {}
    for k, v in opt_state_sds.items():
        if isinstance(v, dict):
            out[k] = param_specs
        else:
            out[k] = P()
    return out


def _residue_specs(
    sc_state_sds,
    worker_axes: Tuple[str, ...],
    mesh: Mesh,
    layout: str = "flat",
    param_specs=None,
):
    """Residue shardings.

    flat    — (n, size): worker axes on dim0; the flat size dim takes the
              largest divisible combination of remaining mesh axes.
    rowwise — (n, *param_shape): the residue inherits the PARAMETER's spec
              (matched by key path), prefixed with the worker axes — every
              compression op is then sharding-preserving.
    """
    rest = tuple(a for a in mesh.axis_names if a not in worker_axes)
    wa = worker_axes[0] if len(worker_axes) == 1 else worker_axes

    if layout == "rowwise":
        pspec_by_path = {}
        for path, spec in jax.tree_util.tree_flatten_with_path(
            param_specs, is_leaf=lambda x: isinstance(x, P)
        )[0]:
            pspec_by_path[jax.tree_util.keystr(path)] = spec

        out = {}
        for rpath, enc in sc_state_sds.residues.items():
            pspec = tuple(pspec_by_path.get(rpath, P()))
            entries = tuple(e if e not in worker_axes else None for e in pspec)
            leaf_specs = {}
            for k, leaf in enc.items():
                nd = len(leaf.shape) - 1  # minus worker axis
                ent = list(entries[:nd]) + [None] * max(0, nd - len(entries))
                # guard: codec auxiliary leaves (fp8 scales / flat-path pads)
                # may not share the param's dims — drop any axis that no
                # longer divides evenly
                for i in range(nd):
                    a = ent[i]
                    if a is None:
                        continue
                    axes_ = a if isinstance(a, tuple) else (a,)
                    prod = 1
                    for ax in axes_:
                        prod *= mesh.shape[ax]
                    if leaf.shape[1 + i] % prod != 0:
                        ent[i] = None
                leaf_specs[k] = P(wa, *ent[:nd])
            out[rpath] = leaf_specs
        return out

    def candidates():
        if len(rest) > 1:
            yield rest
        for a in sorted(rest, key=lambda a: -mesh.shape[a]):
            yield (a,)
        yield None

    def leaf_spec(x):
        if len(x.shape) != 2:
            return P(wa)
        size = x.shape[1]
        for cand in candidates():
            if cand is None:
                return P(wa, None)
            prod = 1
            for a in cand:
                prod *= mesh.shape[a]
            if size % prod == 0:
                return P(wa, cand if len(cand) > 1 else cand[0])
        return P(wa, None)

    return jax.tree.map(leaf_spec, sc_state_sds.residues)


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


def lower_train(
    cfg: ArchConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    mesh_name: str,
    *,
    mode: str,
    settings: Dict[str, Any],
):
    model = build_model(cfg, compute_dtype="bfloat16", param_dtype="float32")
    worker_axes: Tuple[str, ...] = settings["worker_axes"]
    n_workers = 1
    for a in worker_axes:
        n_workers *= mesh.shape[a]
    if mode == "dense":
        n_workers = max(
            n_workers, 1
        )  # dense path folds workers; keep batch layout identical

    sc_cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=settings["chunk"]),
        beta=0.1,
        residue_dtype=settings["residue_dtype"],
        layout=settings.get("layout") or "flat",
        groups=settings["groups"],
    )
    opt = make_optimizer("sgdm")

    params_sds, axes = model.init(None, abstract=True)
    param_specs = specs_for_axes(params_sds, axes, settings["policy"], mesh)
    opt_sds = jax.eval_shape(opt.init, params_sds)
    sc_sds = jax.eval_shape(
        lambda: init_state(params_sds, sc_cfg.n_workers(n_workers), sc_cfg.residue_dtype, sc_cfg.min_size, sc_cfg.layout)
    )

    state_sds = TrainState(params_sds, opt_sds, sc_sds, SDS((), jnp.int32))
    wa = worker_axes[0] if len(worker_axes) == 1 else worker_axes
    from repro.core.state import ScaleComState

    sc_specs = ScaleComState(
        residues=_residue_specs(
            sc_sds, worker_axes, mesh, sc_cfg.layout, param_specs
        ),
        t=P(),
    )
    state_specs = TrainState(
        param_specs, _opt_state_specs(opt_sds, param_specs), sc_specs, P()
    )
    batch_sds = train_batch_sds(cfg, shape, n_workers)
    inner_axis = "data" if ("data" not in worker_axes and "data" in mesh.axis_names) else None
    batch_specs = jax.tree.map(
        lambda x: P(wa, inner_axis, *([None] * (len(x.shape) - 2))), batch_sds
    )

    def to_sharding(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    worker_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, P(wa, *s)),
        param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    step_fn = build_train_step(
        model,
        opt,
        lambda step: jnp.asarray(0.1, jnp.float32),
        sc_cfg,
        n_workers=n_workers,
        mode=mode,
        worker_axis=wa,
        worker_shardings=worker_shardings if mode == "scalecom" else None,
        microbatches=settings.get("microbatches", 1),
    )

    with jax_compat.set_mesh(mesh):
        jitted = jax.jit(
            step_fn,
            in_shardings=(to_sharding(state_specs), to_sharding(batch_specs)),
            donate_argnums=(0,),
        )
        t0 = time.time()
        lowered = jitted.lower(state_sds, batch_sds)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    return compiled, {"lower_s": t1 - t0, "compile_s": t2 - t1}


def lower_serve(
    cfg: ArchConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    mesh_name: str,
    *,
    settings: Dict[str, Any],
):
    # Sub-quadratic variant for long-context decode on full-attention archs
    decode_window = None
    if shape.name == "long_500k" and not cfg.subquadratic:
        decode_window = 4096  # sliding-window variant (DESIGN.md §7)
    model = build_model(
        cfg, compute_dtype="bfloat16", param_dtype="bfloat16", decode_window=decode_window
    )
    params_sds, axes = model.init(None, abstract=True)
    param_specs = specs_for_axes(params_sds, axes, "tp", mesh)
    B, S = shape.global_batch, shape.seq_len

    def to_sharding(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    with jax_compat.set_mesh(mesh):
        if shape.kind == "prefill":
            from repro.training.serve import batch_axes

            ba = batch_axes(mesh)
            batch_sds = serve_batch_sds(cfg, shape)
            bsz = shape.global_batch
            nba = 1
            for a in (ba if isinstance(ba, tuple) else (ba,)):
                nba *= mesh.shape[a]
            eff = ba if bsz % nba == 0 and bsz >= nba else (
                "data" if bsz % mesh.shape["data"] == 0 and bsz >= mesh.shape["data"] else None
            )
            batch_specs = jax.tree.map(
                lambda x: P(eff, *([None] * (len(x.shape) - 1))), batch_sds
            )

            def prefill_fn(params, batch):
                return model.prefill(params, batch, S)

            jitted = jax.jit(
                prefill_fn,
                in_shardings=(to_sharding(param_specs), to_sharding(batch_specs)),
            )
            t0 = time.time()
            lowered = jitted.lower(params_sds, batch_sds)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        else:  # decode
            state_sds = jax.eval_shape(lambda: model.init_decode_state(B, S))
            state_specs = decode_state_specs(state_sds, mesh)
            from repro.training.serve import batch_axes, _fits as _serve_fits

            tok_sds = SDS((B,), jnp.int32)
            ba = batch_axes(mesh)
            if _serve_fits(B, mesh, ba):
                tok_spec = P(ba)
            elif _serve_fits(B, mesh, "data"):
                tok_spec = P("data")
            else:
                tok_spec = P()
            pos_sds = SDS((), jnp.int32)

            jitted = jax.jit(
                model.decode_step,
                in_shardings=(
                    to_sharding(param_specs),
                    to_sharding(state_specs),
                    NamedSharding(mesh, tok_spec),
                    NamedSharding(mesh, P()),
                ),
                donate_argnums=(1,),
            )
            t0 = time.time()
            lowered = jitted.lower(params_sds, state_sds, tok_sds, pos_sds)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
    return compiled, {"lower_s": t1 - t0, "compile_s": t2 - t1}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_one(
    arch_name: str,
    shape_name: str,
    mesh_name: str,
    mode: str,
    out_dir: str = "experiments/dryrun",
    overrides: Dict[str, Any] | None = None,
    tag: str = "",
) -> Dict[str, Any]:
    cfg = registry.arch(arch_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    chips = mesh.size
    pod_size = 256 if mesh_name == "pod2" else None
    settings = default_settings(arch_name, mesh_name)
    if overrides:
        settings.update({k: v for k, v in overrides.items() if v is not None})

    t_start = time.time()
    if shape.kind == "train":
        compiled, timings = lower_train(
            cfg, shape, mesh, mesh_name, mode=mode, settings=settings
        )
        eff_mode = mode
    else:
        compiled, timings = lower_serve(cfg, shape, mesh, mesh_name, settings=settings)
        eff_mode = "serve"

    report = analyze_compiled(
        compiled,
        arch_cfg=cfg,
        shape_cfg=shape,
        mesh_name=mesh_name,
        mode=eff_mode,
        chips=chips,
        pod_size=pod_size,
    )
    result = report.as_dict()
    result.update(timings)
    result["settings"] = settings
    result["wall_s"] = time.time() - t_start
    try:
        ma = compiled.memory_analysis()
        result["memory_analysis"] = {
            k: float(getattr(ma, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(ma, k)
        }
    except Exception:
        result["memory_analysis"] = None

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fname = f"{arch_name}__{shape_name}__{mesh_name}__{mode}{suffix}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--mode", default="scalecom", choices=["scalecom", "dense"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    # hillclimb overrides
    ap.add_argument("--layout", default=None, choices=["flat", "rowwise", None])
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--policy", default=None, choices=["tp", "fsdp", "dp", None])
    ap.add_argument("--residue-dtype", default=None, choices=["fp32", "bf16", "fp8", None])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--worker-axes", default=None,
                    help="comma list, e.g. data,model for pure-DP isolation")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    overrides = {
        "layout": args.layout,
        "chunk": args.chunk,
        "policy": args.policy,
        "residue_dtype": args.residue_dtype,
        "microbatches": args.microbatches,
        "worker_axes": tuple(args.worker_axes.split(",")) if args.worker_axes else None,
    }

    archs = [args.arch] if args.arch else list(registry.ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shp in shapes:
            for mesh_name in meshes:
                tag = f"{arch} x {shp} x {mesh_name} x {args.mode}"
                try:
                    r = run_one(arch, shp, mesh_name, args.mode, args.out,
                                overrides=overrides, tag=args.tag)
                    print(
                        f"OK   {tag}: flops={r['hlo_flops']:.3e} "
                        f"ici={r['ici_bytes']:.3e} dcn={r['dcn_bytes']:.3e} "
                        f"dominant={r['dominant']} "
                        f"compile={r['compile_s']:.1f}s"
                    )
                except Exception as e:
                    failures.append(tag)
                    print(f"FAIL {tag}: {type(e).__name__}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nAll dry-runs compiled successfully.")


if __name__ == "__main__":
    main()
