"""ScaleCom Algorithm 1 — the worker-axis gradient reduce.

``scalecom_reduce`` replaces the dense data-parallel gradient all-reduce inside
a train step. Inputs are *per-worker, unreduced* gradients stacked on a leading
worker axis (produced by the expanded-params vmap trick — see
repro.training.train_step), plus the persistent ScaleComState. Output is the
dense reduced-and-sparsified gradient ĝ^t every worker applies, and the
updated state.

The function is pure GSPMD-friendly jnp: when the worker axis is sharded over
the mesh ``data`` axis (and ``worker_sharding`` says so), XLA lowers

    leader-index slice    ->  O(k) broadcast from the leader's shard
    mean over worker axis ->  k-element all-reduce        (the compressed reduce)
    everything else       ->  fully local math

which is exactly the paper's communication structure (constant in n; Table 1
row "ScaleCom"). There is no dense gradient collective anywhere on the path —
asserted by tests/test_distributed.py on the CPU and by
tests/test_tpu_compile.py on a module compiled for a described TPU.

Plan / execute split
--------------------
The reduce is ONE layout-aware pipeline:

  plan     (core.plan, cached per tree structure) — resolves, per tensor:
           the compressor after rate_rules, the min_size/dense fallback,
           hierarchical grouping, the chunk layout, residue storage and
           execute work shapes, and the wire-byte accounting (one rule for
           both layouts — see core/plan.py).
  execute  (this module, ``_execute``) — one traced implementation of
           Algorithm 1 over the plan's trailing-axis work view. The flat
           layout is the degenerate single-row case of the rowwise form
           ((G, size) ≡ (G, 1, size) trailing-axis chunks), so there is a
           single code path for every compressor × layout × backend
           combination: clt_k / true_topk / local_topk / random_k, any
           ``topm``, rate rules, and ``groups`` behave identically in both
           layouts.
  launch   (core.plan.plan_buckets + core.overlap) — optional overlap-aware
           bucketed launch: tensors pack into size-targeted buckets in
           reverse-autodiff grad-ready order and each bucket's compress +
           all-reduce is staged behind an optimization_barrier token chain,
           so XLA can hide per-bucket collectives behind remaining backward
           compute. Launch granularity only — bitwise identical to the
           single-shot path (``scalecom_reduce(..., buckets=...)``; off by
           default).

Two chunk layouts (ScaleComConfig.layout):

  flat     — paper-faithful: the tensor is one flat buffer of chunks. Under
             GSPMD the 1-D flatten of a model-sharded tensor is inexpressible
             and forces a reshard (multi-GB all-gathers observed on the
             production mesh).
  rowwise  — beyond-paper TPU optimization: chunks run along the tensor's
             native last dim, so indices/values/residues keep the parameter's
             sharding and the *only* collective is the k-value mean. Bitwise
             identical to flat whenever the last dim is a chunk multiple
             (row-major order), and statistically identical otherwise.

Kernel dispatch (ScaleComConfig.backend): every chunked op — selection,
gather, scatter, and the fused Eq. 5 residue update — routes through the ONE
trailing-axis op set of a ``repro.backends`` KernelBackend resolved per call
("auto" is pallas on a TPU, else jnp). On the pallas backend the per-tensor
inner loop is three kernel launches (worker-stacked select, fused EF update,
ĝ scatter) instead of the 7-pass jnp chain, in both layouts; on the jnp
backend it is the bitwise reference chain. Trajectories agree across
backends to fp32 tolerance (tests/test_backends.py).

Fused inner loop (ScaleComConfig.fused): with ``fused=True`` the whole inner
loop collapses into the backend's ONE ``fused_reduce`` op — on the pallas
backend a single launch keeping each chunk tile VMEM-resident across select
→ EF update → ĝ scatter (kernels.fused_reduce, ~3 HBM passes instead of ~7
— see analysis.perfmodel.reduce_hbm_passes), on the jnp backend the
identical 3-op composition. Only the shared-index compressors are fusable (clt_k,
true_topk); local_topk / random_k / exact / dense tensors silently take the
unfused path, so a mixed rate_rules plan works under fused=True. Bitwise
identical indices and allclose values either way (tests/test_backends.py);
the 1-launch property is pinned by tests/test_kernels.py.

Hierarchical / grouped mode (DESIGN.md §5): with ``groups=G < n`` the inner
n/G workers are dense-averaged first (fast intra-group ICI reduce) and CLT-k
runs across the G groups (the slow inter-group link, e.g. the multi-pod DCN
axis). The residue then lives per *group*: build the state with n_workers=G.
See examples/multipod_groups.py for the 2-pod driver and the DCN-byte
accounting against core.plan / analysis.perfmodel.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.backends.base import resolve_backend
from repro.compat.jax_compat import NamedSharding, P
from repro.core.compressors import (
    FUSABLE_MODES,
    CompressorConfig,
    compress,
    select_indices,
)
from repro.core import overlap
from repro.core.filter import lowpass_update
from repro.core.metrics import residue_similarity_report
from repro.core.plan import TensorPlan, plan_tensors
from repro.core.state import (
    CODECS,
    LAYOUTS,
    ScaleComState,
    codec_key,
    residue_signature,
)
from repro.obs import taps

Array = jnp.ndarray
Pytree = Any

__all__ = ["ScaleComConfig", "scalecom_reduce", "dense_reduce"]


@dataclasses.dataclass(frozen=True)
class ScaleComConfig:
    """Full ScaleCom configuration.

    compressor:     CompressorConfig (clt_k / true_topk / local_topk / random_k / none)
    beta:           low-pass filter discounting factor (1.0 = classic error
                    feedback; paper uses 0.1 for large-batch runs)
    min_size:       tensors smaller than this are reduced densely
    residue_dtype:  fp32 | bf16 | fp8 | fp8_ec (beyond-paper; lossy codecs
                    use stochastic rounding keyed from the step counter)
    layout:         "flat" (default; paper-faithful) | "rowwise"
                    (layout-preserving). init_state must be given the same.
    backend:        kernel backend spec for the chunked hot-path ops:
                    "auto" (default: pallas iff running on TPU, else jnp),
                    "jnp", "pallas", or a KernelBackend instance. Resolved
                    at trace time (repro.backends.resolve_backend).
    fused:          run the per-tensor inner loop through the backend's
                    single ``fused_reduce`` op where the compressor is
                    fusable (clt_k / true_topk — one kernel launch on the
                    pallas backend instead of three). A bool, False by
                    default. Non-fusable tensors (local_topk, random_k,
                    exact, dense) keep the unfused path, so mixed
                    rate_rules plans work under fused=True. Identical
                    numerics either way.
    groups:         ScaleCom worker granularity; None => every data rank is a
                    worker. G < n enables hierarchical mode.
    warmup_steps:   steps of dense reduction before compression kicks in
                    (applied statically by the train loop).
    bucket_bytes:   dense-byte target per launch bucket of the overlap-aware
                    bucketed reduce (core.plan.plan_buckets; 25 MB default —
                    DDP's bucket_cap_mb heritage). Whether bucketing is ON is
                    the ``buckets`` argument of ``scalecom_reduce`` (off by
                    default).
    overlap:        thread the optimization_barrier token chain through the
                    bucketed launch so XLA can interleave per-bucket
                    collectives with remaining backward compute (core.overlap);
                    False forces the synchronous per-bucket fallback. No
                    effect on numerics either way.
    telemetry:      emit the repro.obs metric taps as extra ``"obs/..."``
                    leaves of the returned stats dict (measured wire bytes vs
                    the plan, build-up nnz/k, per-tensor contraction gamma,
                    codec roundtrip error, similarity samples). Jit-safe aux
                    outputs only — never host callbacks — so the primary
                    outputs stay BITWISE identical to telemetry=False and the
                    trace is retrace-deterministic (tests/test_obs.py).
                    False (default) stages nothing: the taps are trace-time
                    no-ops.
    metrics_every:  sample core.metrics.residue_similarity_report every this
                    many steps (a lax.cond on the step counter, so one trace
                    serves sampled and unsampled steps). 0 disables; only
                    meaningful with telemetry=True.
    """

    compressor: CompressorConfig = CompressorConfig()
    beta: float = 1.0
    min_size: int = 2048
    residue_dtype: str = "fp32"
    layout: str = "flat"
    backend: Any = "auto"
    fused: bool = False
    groups: Optional[int] = None
    warmup_steps: int = 0
    bucket_bytes: int = 25 << 20
    overlap: bool = True
    telemetry: bool = False
    metrics_every: int = 0
    # per-tensor compression-rate rules (paper §4 guidance); first match wins,
    # chunk=None => dense. Tuple of core.rates.RateRule.
    rate_rules: Tuple = ()

    def __post_init__(self):
        # fail fast at config construction, not deep inside a traced reduce
        if self.bucket_bytes <= 0:
            raise ValueError(
                f"bucket_bytes must be positive, got {self.bucket_bytes} "
                "(bucketing is toggled by scalecom_reduce(buckets=...), not "
                "by zeroing the size)"
            )
        if self.groups is not None and self.groups < 1:
            raise ValueError(
                f"groups must be a positive worker-group count or None, got "
                f"{self.groups} (divisibility against the actual worker count "
                f"is checked per tensor at plan time)"
            )
        if self.metrics_every < 0:
            raise ValueError(
                f"metrics_every must be >= 0 (0 disables similarity "
                f"sampling), got {self.metrics_every}"
            )
        if not isinstance(self.fused, bool):
            raise ValueError(f"fused must be True or False; got {self.fused!r}")
        if self.layout not in LAYOUTS:
            raise ValueError(
                f"unknown chunk layout {self.layout!r}; expected one of {LAYOUTS}"
            )

    def n_workers(self, data_ranks: int) -> int:
        return self.groups if self.groups is not None else data_ranks


def _group_fold(g: Array, groups: int) -> Array:
    """(n, ...) -> (G, ...): dense mean inside each group of n/G workers.

    Divisibility is validated at plan time (core.plan.plan_tensors raises a
    ValueError naming n, groups and the tensor path — a bare ``assert`` here
    would disappear under ``python -O``); the raise below is defense in depth
    for callers that bypass the plan stage.
    """
    n = g.shape[0]
    if groups == n:
        return g
    if n % groups != 0:
        raise ValueError(f"{n} workers not divisible into {groups} groups")
    return jnp.mean(g.reshape((groups, n // groups) + g.shape[1:]), axis=1)


def dense_reduce(grads_pw: Pytree) -> Pytree:
    """Baseline dense reduce: plain mean over the worker axis (uncompressed)."""
    return jax.tree.map(lambda g: jnp.mean(g, axis=0), grads_pw)


# ---------------------------------------------------------------------------
# execute stage — one tensor through Algorithm 1, layout-agnostic
# ---------------------------------------------------------------------------


def _execute_exact(ef: Array, t: Array, comp: CompressorConfig, backend):
    """Dense top-k analysis path (comp.exact): non-chunked compress().

    Also returns the (vals, idx) wire payload so the telemetry taps can
    measure transmitted bytes uniformly across the exact and chunked paths.
    """
    size = ef.shape[-1]
    vals, idx, ghat = compress(ef, t, comp, backend=backend)
    if comp.name == "local_topk":
        own = jax.vmap(
            lambda v, i: jnp.zeros((size,), ef.dtype).at[i].set(v, mode="drop")
        )(vals, idx)
    else:
        own = jax.vmap(
            lambda v: jnp.zeros((size,), ef.dtype).at[idx].set(v, mode="drop")
        )(vals)
    return ghat, own, vals, idx


# Fixed key order of the residue_similarity_report bundle: both lax.cond
# branches of the metrics_every sampler must build the SAME output structure,
# and the tap keys must be retrace-deterministic.
_SIMILARITY_KEYS = (
    "pairwise_cosine_distance",
    "hamming_d_over_k",
    "topk_energy_overlap",
    "spearman_rho",
)


def _tap_execute(
    plan: TensorPlan,
    codec,
    ef: Array,
    vals: Array,
    idx: Array,
    ghat: Array,
    new_m: Array,
    new_enc,
    t: Array,
    metrics_every: int,
) -> None:
    """Per-tensor telemetry taps (only runs while a taps collector is open).

    Everything here is ordinary traced jnp feeding aux outputs — no host
    callbacks, no timers (the obs-hot-path scalecheck rule rejects those on
    any function reachable from scalecom_reduce). Labels are static plan
    metadata, so tap keys are identical on every retrace.
    """
    comp = plan.comp
    G = ef.shape[0]
    # Measured per-worker wire bytes from the ACTUAL traced payload shapes,
    # against the plan's one byte rule (core.plan._INDEX_BYTES): values are
    # always 4 * k; the shared-index broadcast amortizes over G workers,
    # local_topk ships each worker's own set, random_k re-derives from the
    # shared step counter.
    value_bytes = 4.0 * (vals.size // G)
    if comp.name == "local_topk":
        index_bytes = 4.0 * (idx.size // G)
    elif comp.name == "random_k":
        index_bytes = 0.0
    else:
        index_bytes = 4.0 * idx.size / G
    labels = dict(path=plan.path, compressor=comp.name)
    taps.tap(
        "bytes_measured",
        jnp.asarray(value_bytes + index_bytes, jnp.float32),
        **labels,
    )
    taps.tap(
        "bytes_planned", jnp.asarray(plan.bytes_payload, jnp.float32), **labels
    )
    # Gradient build-up: nnz(ĝ) vs the k values each worker contributed —
    # ~1 for the shared-index compressors, the O(n) union for local_topk
    # (paper Fig. 5; analysis.perfmodel.buildup_ratio_model).
    taps.tap(
        "buildup_nnz",
        jnp.count_nonzero(ghat).astype(jnp.float32),
        path=plan.path,
    )
    taps.tap("buildup_k", jnp.asarray(plan.k, jnp.float32), path=plan.path)
    # Codec roundtrip: how much of the residue the storage codec loses this
    # step (0 for fp32; the contraction the EF loop must absorb for
    # bf16/fp8). Telemetry-only extra decode — never staged when off.
    m_stored = new_m.reshape((G,) + plan.storage)
    decoded = codec.decode(new_enc, plan.storage)
    taps.tap(
        "codec_roundtrip_err",
        jnp.linalg.norm(decoded - m_stored)
        / jnp.maximum(jnp.linalg.norm(m_stored), 1e-30),
        path=plan.path,
        codec=codec.name,
    )
    # metrics_every sampling of the paper's similarity diagnostics, as a
    # lax.cond on the traced step counter: one trace serves both the sampled
    # and unsampled steps (no retrace drift), and the "sampled" flag tap
    # tells the report which steps carry real values. Needs >= 2 workers
    # (pairwise distance) — G is static, so this is a trace-time gate.
    if metrics_every > 0 and G >= 2:
        ef2 = ef.reshape(G, -1)
        kk = max(1, min(plan.k, ef2.shape[1]))

        def _sampled(e):
            rep = residue_similarity_report(e, kk)
            return tuple(
                jnp.asarray(rep[name], jnp.float32) for name in _SIMILARITY_KEYS
            )

        def _skipped(e):
            del e
            return tuple(jnp.zeros((), jnp.float32) for _ in _SIMILARITY_KEYS)

        sampled_now = (t % metrics_every) == 0
        report = jax.lax.cond(sampled_now, _sampled, _skipped, ef2)
        taps.tap(
            "similarity_sampled",
            sampled_now.astype(jnp.float32),
            path=plan.path,
        )
        for name, value in zip(_SIMILARITY_KEYS, report):
            taps.tap(name, value, path=plan.path)


def _learner_local(vals: Array, worker_sharding) -> Array:
    """Pin the (G, ..., n_chunks[, topm]) per-chunk values to one learner a
    chip, the layout ``worker_sharding`` gives the worker axis; the other
    axes are left to propagation.

    The values are the lane sums of a masked (G, ..., n_chunks, chunk) tile
    (``core.chunked.chunk_gather``). Without this fence the TPU compiler
    folds that lane sum into the worker mean and all-reduces the whole tile,
    ``chunk`` times the k values. Nothing is pinned where the worker axis
    spans one device (no exchange) or G does not split evenly over it
    (hierarchical groups on a wider axis).
    """
    mesh, axes = worker_sharding.mesh, worker_sharding.spec[0]
    chips = math.prod(
        mesh.shape[a] for a in (axes if isinstance(axes, tuple) else (axes,))
    )
    if chips == 1 or vals.shape[0] % chips:
        return vals
    spec = P(axes, *(P.UNCONSTRAINED,) * (vals.ndim - 1))
    return jax.lax.with_sharding_constraint(vals, NamedSharding(mesh, spec))


def _execute(
    plan: TensorPlan,
    gw: Array,
    enc: Pytree,
    codec,
    beta: float,
    t: Array,
    enc_key,
    backend,
    compute_stats: bool,
    metrics_every: int = 0,
    fused: bool = False,
    worker_sharding=None,
):
    """Algorithm 1 over the plan's trailing-axis work view.

    gw: (G, *plan.shape) folded fp32 gradients. The work view is
    (G,) + plan.work — (G, size) for the flat layout (the degenerate
    single-row trailing-axis case) and (G, *param_shape) for rowwise, so no
    reshape ever crosses a sharded axis in the rowwise layout. All chunked
    math goes through the backend's one trailing-axis op set; on the pallas
    backend that is three kernel launches (select, fused Eq. 5 EF update,
    ĝ scatter) — or, with ``fused`` and a fusable compressor, ONE
    ``fused_reduce`` launch with the chunk tile VMEM-resident across all
    three phases; on that path ``ef = m + g`` is never materialized unless
    telemetry/stats ask for it.

    ``worker_sharding`` (``scalecom_reduce``'s) puts one learner on each
    chip, or is None when the learners share a device; the worker mean then
    takes the per-chunk values each chip holds (``_learner_local``).

    Returns (ghat (*plan.shape), new_enc, ef_mean) — ef_mean feeds the
    contraction_gamma diagnostic (identical in both layouts; None unless
    compute_stats, so eager callers never pay the extra EF pass).
    """
    comp = plan.comp
    G = gw.shape[0]
    # Stage scopes (fold, decode, ef_sum, select, ef_update, worker_mean,
    # scatter, fused, encode) name each op's part of Algorithm 1 in the
    # compiled module's op_name metadata; trace-time only.
    with jax.named_scope("fold"):
        work = gw.reshape((G,) + plan.work)
    with jax.named_scope("decode"):
        m = codec.decode(enc, plan.storage)
        if plan.work != plan.storage:
            m = m.reshape((G,) + plan.work)  # exact path over a rowwise residue
    C = work.shape[-1]
    use_fused = fused and not comp.exact and comp.name in FUSABLE_MODES
    ef = None
    if not use_fused:
        with jax.named_scope("ef_sum"):
            ef = m + work

    if comp.exact:
        with jax.named_scope("select"):
            ghat, own, vals, idx = _execute_exact(ef, t, comp, backend)
            ghat = ghat.reshape(plan.shape)
        with jax.named_scope("ef_update"):
            new_m = lowpass_update(m, work, own, beta)
    elif use_fused:
        # Single fused op: select over worker-stacked EF, Eq. 5 residue
        # update, ĝ scatter — one kernel launch on the pallas backend, the
        # identical 3-op composition on jnp (backends.base.fused_reduce).
        with jax.named_scope("fused"):
            leader = (
                jnp.mod(t, G).astype(jnp.int32) if comp.name == "clt_k" else None
            )
            idx, vals, new_m, ghat = backend.fused_reduce(
                m, work, beta, comp.chunk, comp.topm, comp.name, leader
            )
            ghat = ghat.reshape(plan.shape)
    else:
        with jax.named_scope("select"):
            idx = select_indices(ef, t, comp, backend)  # shared, or per-worker
        # Fused Eq. 5: one pass emits both the residue update and the values
        # each worker contributes to the k-value all-reduce.
        with jax.named_scope("ef_update"):
            new_m, vals = backend.ef_update(
                m, work, idx, beta, comp.chunk, comp.topm
            )
        if comp.name == "local_topk":
            # union-average (gradient build-up): every worker scatters its own
            with jax.named_scope("scatter"):
                ghat = jnp.mean(
                    backend.scatter(vals, idx, comp.chunk, C, comp.topm), axis=0
                ).reshape(plan.shape)
        else:
            with jax.named_scope("worker_mean"):
                if worker_sharding is not None:
                    vals = _learner_local(vals, worker_sharding)
                vmean = jnp.mean(vals, axis=0)  # all-reduce of k values
            with jax.named_scope("scatter"):
                ghat = backend.scatter(
                    vmean, idx, comp.chunk, C, comp.topm
                ).reshape(plan.shape)

    with jax.named_scope("encode"):
        new_enc = codec.encode(
            new_m.reshape((G,) + plan.storage), plan.storage, key=enc_key
        )
    if taps.active():
        if ef is None:
            ef = m + work  # telemetry-only; the fused hot path skips it
        # Which path this tensor took + the inner-loop launch count a kernel
        # backend pays for it (static plan facts, so the values are the same
        # on every retrace; obs.report surfaces them as the fused-path table).
        taps.tap(
            "fused",
            jnp.asarray(1.0 if use_fused else 0.0, jnp.float32),
            path=plan.path,
            compressor=comp.name,
        )
        # Whether the 3-launch kernels streamed this tensor as lane-dense
        # tiles (chunk_topk.lane_dense): a static fact of its shapes.
        dense_tiles = (
            not comp.exact
            and not use_fused
            and backend.lane_dense(work.shape, comp.chunk, work.dtype)
        )
        taps.tap(
            "lane_dense",
            jnp.asarray(1.0 if dense_tiles else 0.0, jnp.float32),
            path=plan.path,
            size=plan.size,
        )
        taps.tap(
            "fused_launches",
            jnp.asarray(
                0.0 if comp.exact else (1.0 if use_fused else 3.0),
                jnp.float32,
            ),
            path=plan.path,
        )
        _tap_execute(
            plan, codec, ef, vals, idx, ghat, new_m, new_enc, t, metrics_every
        )
    ef_mean = None
    if compute_stats:
        with jax.named_scope("stats"):
            if ef is None:
                ef = m + work
            ef_mean = jnp.mean(ef, axis=0).reshape(plan.shape)
    return ghat, new_enc, ef_mean


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------


def scalecom_reduce(
    grads_pw: Pytree,
    state: ScaleComState,
    cfg: ScaleComConfig,
    *,
    compute_stats: bool = False,
    buckets: Any = None,
    worker_sharding: Any = None,
) -> Tuple[Pytree, ScaleComState, Dict[str, Array]]:
    """Run Algorithm 1 on worker-stacked gradients.

    grads_pw: pytree of (n_workers, *shape) arrays (unreduced).
    buckets:  launch granularity of the overlap-aware bucketed reduce
              (core.overlap.resolve_buckets): None/False is the
              single-shot path, True buckets at cfg.bucket_bytes, an int is
              an explicit byte target, and a tuple of core.plan.Bucket is a
              pre-built schedule.
              Bucketing changes launch order/granularity ONLY — same
              per-tensor plans, same EF residues, bitwise-identical output
              (tests/test_overlap.py).
    worker_sharding: a NamedSharding whose spec splits the leading worker
              axis over a mesh (``P(axis)``), given when the learners live on
              different chips; the shared-index worker mean then all-reduces
              the k values each chip holds. None (the default): the learners
              share a device and nothing is staged for it.
    Returns (ghat, new_state, stats) where ghat matches the *un-stacked* param
    shapes and is identical on every worker (it came out of an all-reduce).

    With cfg.telemetry the repro.obs taps fired during the reduce come back
    as extra ``"obs/<name>{labels}"`` float32 leaves of ``stats`` — ordinary
    jit outputs, so ghat/new_state stay bitwise identical to telemetry=False
    and the trace is retrace-deterministic (keys are sorted; labels are
    static plan metadata). The train step forwards stats into its metrics
    dict, which is where TelemetryRun.record_step picks them up.
    """
    if not cfg.telemetry:
        return _reduce(
            grads_pw, state, cfg, compute_stats, buckets, worker_sharding
        )
    with taps.collect() as collected:
        ghat_tree, new_state, stats = _reduce(
            grads_pw, state, cfg, compute_stats, buckets, worker_sharding
        )
    for key in sorted(collected):
        stats[f"obs/{key}"] = collected[key]
    return ghat_tree, new_state, stats


def _reduce(
    grads_pw: Pytree,
    state: ScaleComState,
    cfg: ScaleComConfig,
    compute_stats: bool,
    buckets: Any,
    worker_sharding: Any,
) -> Tuple[Pytree, ScaleComState, Dict[str, Array]]:
    """The reduce body (scalecom_reduce minus the telemetry collector)."""
    codec = CODECS[cfg.residue_dtype]
    backend = resolve_backend(cfg.backend)
    flat, treedef = jax.tree_util.tree_flatten_with_path(grads_pw)
    plans = plan_tensors(
        tuple(
            (jax.tree_util.keystr(p), tuple(g.shape[1:]), g.shape[0])
            for p, g in flat
        ),
        cfg,
        # encoding signatures, not just paths: the plan validates the stored
        # residues against what _execute will decode (layout/codec/membership
        # drift raises a named error at plan time), and a remapped state
        # re-keys the plan cache
        residue_signature(state.residues),
    )
    t = state.t

    def _run_leaf(i: int, g: Array):
        """One tensor through Algorithm 1 -> (ghat_leaf, new_enc, stat_sums).

        stat_sums are the (sq_err, sq_all) contraction-gamma contributions,
        computed on the fp32 ghat before the output cast.
        """
        plan = plans[i]
        with jax.named_scope("fold"):
            gw = _group_fold(g.astype(jnp.float32), plan.groups)
        if plan.dense:
            with jax.named_scope("dense"):
                ghat = jnp.mean(gw, axis=0).reshape(plan.shape)
                return ghat.astype(g.dtype), None, None
        # the telemetry taps also want the ef-mean pass (per-tensor gamma);
        # with both off it is never staged
        want_ef = compute_stats or taps.active()
        ghat, new_enc, ef_mean = _execute(
            plan, gw, state.residues[plan.path], codec, cfg.beta, t,
            codec_key(plan.path, t), backend, want_ef, cfg.metrics_every,
            cfg.fused, worker_sharding,
        )
        sums = None
        if want_ef:
            with jax.named_scope("stats"):
                sq = (jnp.sum((ef_mean - ghat) ** 2), jnp.sum(ef_mean**2))
            taps.tap(
                "contraction_gamma",
                sq[0] / jnp.maximum(sq[1], 1e-30),
                path=plan.path,
            )
            if compute_stats:
                sums = sq
        return ghat.astype(g.dtype), new_enc, sums

    schedule = overlap.resolve_buckets(buckets, cfg, plans)
    results: list = [None] * len(flat)
    if schedule is None:
        for i, (_, g) in enumerate(flat):
            results[i] = _run_leaf(i, g)
    else:
        # Bucketed launch in grad-ready order: stage each bucket's leaves
        # behind the previous bucket's fence so per-bucket collectives issue
        # in schedule order and XLA can overlap them with remaining backward
        # compute (core.overlap). Identity on values.
        token = overlap.init_token()
        for b in schedule:
            leaves, token = overlap.stage_bucket(
                [flat[i][1] for i in b.leaf_ids], token,
                overlap=cfg.overlap, bucket=b.index,
            )
            taps.tap(
                "bucket_bytes_dense",
                jnp.asarray(b.bytes_dense, jnp.float32),
                bucket=b.index,
            )
            taps.tap(
                "bucket_bytes_payload",
                jnp.asarray(b.bytes_payload, jnp.float32),
                bucket=b.index,
            )
            outs = [_run_leaf(i, g) for i, g in zip(b.leaf_ids, leaves)]
            for i, out in zip(b.leaf_ids, outs):
                results[i] = out
            token = overlap.fence_bucket(
                [out[0] for out in outs], token, overlap=cfg.overlap
            )

    with jax.named_scope("stats"):
        # Accumulation runs in LEAF order regardless of launch schedule, so the
        # bucketed and unbucketed paths build identical output graphs.
        new_residues = dict(state.residues)
        ghat_leaves = []
        bytes_sent = 0.0  # per-worker payload under the plan's one byte rule
        bytes_dense = 0.0
        sq_err = 0.0
        sq_all = 0.0
        for plan, (ghat, new_enc, sums) in zip(plans, results):
            bytes_dense += plan.bytes_dense
            bytes_sent += plan.bytes_payload
            ghat_leaves.append(ghat)
            if new_enc is not None:
                new_residues[plan.path] = new_enc
            if sums is not None:
                sq_err = sq_err + sums[0]
                sq_all = sq_all + sums[1]

        ghat_tree = jax.tree_util.tree_unflatten(treedef, ghat_leaves)
        new_state = ScaleComState(residues=new_residues, t=t + 1)
        stats: Dict[str, Array] = {
            "comm_bytes_per_worker": jnp.asarray(bytes_sent, jnp.float32),
            "comm_bytes_dense": jnp.asarray(bytes_dense, jnp.float32),
        }
        if compute_stats:
            stats["contraction_gamma"] = sq_err / jnp.maximum(sq_all, 1e-30)
        return ghat_tree, new_state, stats
