"""Per-tensor reduce planning — the *plan* stage of the unified pipeline.

``scalecom_reduce`` is split into two stages:

  plan     (this module)  — pure-Python, resolved once per tree structure and
           cached: per-tensor compression rules (rate_rules, min_size/dense
           fallback), grouping, chunk layout, residue storage shape, the
           execute-stage work view, and the wire-byte accounting.
  execute  (core.scalecom) — traced jnp, one layout-agnostic implementation
           of Algorithm 1 driven entirely by the plan: flat is the
           degenerate single-row case of the trailing-axis (rowwise) form,
           so every compressor/feature lands once, in both layouts, on both
           backends.

Plans are static with respect to tracing: every field is shape/config
metadata (no arrays), so building them inside a jit'd reduce costs nothing
after the first trace, and the lru_cache below removes even the Python cost
on retrace-free steps.

Byte accounting — ONE rule for both layouts
-------------------------------------------
Per-worker TRANSMIT bytes for one tensor and one step (fp32 values, int32
indices; k = n_chunks * topm kept entries). Send-side only: every worker
additionally *receives* the k reduced values (and, for shared-index
compressors, the leader's k-index broadcast) on the down leg — the
link-level round trip is modeled by ``analysis.perfmodel``, which uses this
same rule for its up leg:

  dense                      4 * size            (the gradient itself)
  values (every compressor)  4 * k               (each worker ships its k)
  indices:
    local_topk               + 4 * k             every worker ships its OWN set
    clt_k / true_topk        + 4 * k / G         only the LEADER ships the
                                                 shared set — the paper's O(k)
                                                 index broadcast (§5),
                                                 amortized over the G workers
    random_k                 + 0                 indices re-derived from the
                                                 shared step counter; nothing
                                                 crosses the wire

This replaces the historical split accounting (rowwise charged a flat
``8k``; flat charged ``4k + 4|idx|``, which billed the shared index set to
every worker — and, for local_topk, billed ALL workers' sets to each
worker). ``analysis.perfmodel`` uses the same amortized-index rule, and
examples/multipod_groups.py asserts measured == planned.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

from repro.core.chunked import num_chunks
from repro.core.compressors import CompressorConfig, exact_k
from repro.core.rates import resolve_compressor
from repro.core.state import CODECS, codec_signature, storage_shape

Shape = Tuple[int, ...]

__all__ = ["TensorPlan", "Bucket", "plan_tensors", "plan_buckets", "payload_bytes"]


@dataclasses.dataclass(frozen=True)
class TensorPlan:
    """Everything the execute stage needs to know about one tensor.

    path:          pytree key path (also the residue-dict key)
    shape:         parameter shape (no worker axis)
    size:          element count
    groups:        G — ScaleCom worker count after hierarchical folding
    layout:        chunk layout ("flat" | "rowwise")
    comp:          resolved CompressorConfig, or None => dense reduce
    storage:       residue storage shape (no worker axis)
    work:          execute-stage view (no worker axis): ``(size,)`` for the
                   flat layout and the exact analysis path, the full
                   parameter shape for rowwise — chunks always run along
                   work[-1], so flat is the single-row degenerate case
    n_chunks:      total chunks across the tensor in this layout
    k:             values each worker contributes per step
    bytes_dense:   4 * size (the uncompressed payload, for ratio reporting)
    bytes_payload: per-worker wire bytes under the one rule above
    """

    path: str
    shape: Shape
    size: int
    groups: int
    layout: str
    comp: Optional[CompressorConfig]
    storage: Shape
    work: Shape
    n_chunks: int
    k: int
    bytes_dense: float
    bytes_payload: float

    @property
    def dense(self) -> bool:
        return self.comp is None


# Per-worker INDEX bytes for k kept values, by compressor. Value bytes are
# always 4k (fp32 on the wire); index cost is what distinguishes the schemes:
#   clt_k / true_topk  one index set chosen by the leader, broadcast once and
#                      amortized over the G workers sharing it -> 4k/G
#   local_topk         every worker ships its own index set -> 4k
#   random_k           indices are derived from the shared PRNG key -> 0
# This dict IS the wire-format registry: scalecheck's payload-coverage rule
# statically cross-checks its keys against core.compressors.COMPRESSORS
# ("none" excluded — dense tensors never enter payload_bytes).
_INDEX_BYTES = {
    "clt_k": lambda k, G: 4.0 * k / G,
    "true_topk": lambda k, G: 4.0 * k / G,
    "local_topk": lambda k, G: 4.0 * k,
    "random_k": lambda k, G: 0.0,
}


def payload_bytes(comp: Optional[CompressorConfig], k: int, groups: int) -> float:
    """Per-worker wire bytes for k kept values (see module docstring)."""
    if comp is None or comp.name == "none":
        raise ValueError("payload_bytes is for compressed tensors; dense is 4*size")
    return 4.0 * k + _INDEX_BYTES[comp.name](k, groups)


def _raise_state_drift(
    path: str,
    shape: Shape,
    G: int,
    layout: str,
    residue_dtype: str,
    actual: Tuple,
    expected: Tuple,
) -> None:
    """Diagnose an init_state/ScaleComConfig drift and raise a named error.

    The execute stage would otherwise hit this as a cryptic reshape/broadcast
    failure deep inside ``_execute``; here we know which tensor, which layout
    each side resolved, and (by re-deriving candidate signatures) WHAT
    drifted: the chunk layout, the residue codec, or the worker count.
    """
    other = "rowwise" if layout == "flat" else "flat"
    causes = []
    if actual == codec_signature(residue_dtype, G, storage_shape(shape, other)):
        causes.append(
            f"the residue was initialized under layout={other!r} but this "
            f"reduce runs layout={layout!r} (init_state and ScaleComConfig "
            f"were given different layouts)"
        )
    # worker-axis drift: every codec stores (n, *storage) in its "q" leaf
    actual_by_name = dict((name, sh) for name, sh, _ in actual)
    q_shape = actual_by_name.get("q")
    if q_shape and q_shape[0] != G and actual == codec_signature(
        residue_dtype, q_shape[0], storage_shape(shape, layout)
    ):
        causes.append(
            f"the residue carries {q_shape[0]} worker rows but this reduce "
            f"folds to G={G} workers — membership or `groups` changed; "
            f"core.state.remap_state(state, {q_shape[0]}, {G}) migrates the "
            f"EF mass to the new worker count"
        )
    for name in CODECS:
        if name != residue_dtype and actual == codec_signature(
            name, G, storage_shape(shape, layout)
        ):
            causes.append(
                f"the residue was encoded by the {name!r} codec but "
                f"ScaleComConfig.residue_dtype={residue_dtype!r}"
            )
    detail = "; ".join(causes) if causes else (
        f"expected {expected}, found {actual}"
    )
    raise ValueError(
        f"ScaleCom state drift on tensor {path!r}: the stored residue "
        f"encoding does not match what this reduce's plan (layout={layout!r}, "
        f"residue_dtype={residue_dtype!r}, G={G}) will decode — {detail}. "
        f"Remediation: re-init the state (core.state.init_state) with the "
        f"current config, or give ScaleComConfig(layout=...) and "
        f"init_state(layout=...) the same layout; on membership change use "
        f"core.state.remap_state."
    )


def _plan_one(
    path: str,
    shape: Shape,
    n_stack: int,
    layout: str,
    base: CompressorConfig,
    rate_rules: Tuple,
    min_size: int,
    groups: Optional[int],
    has_residue: bool,
    residue_dtype: str = "fp32",
    enc_sig: Optional[Tuple] = None,
) -> TensorPlan:
    size = int(np.prod(shape)) if len(shape) else 1
    if groups is not None and (groups < 1 or n_stack % groups != 0):
        # plan-time guard for the execute stage's _group_fold reshape: a bare
        # assert there disappears under `python -O`, and membership changes
        # (e.g. a 64 -> 63 dropped-worker transition) hit this first
        raise ValueError(
            f"n={n_stack} workers are not divisible into groups={groups} "
            f"(tensor {path!r}): hierarchical grouping needs n % groups == 0 "
            f"with groups >= 1. After a membership change, re-plan groups to "
            f"a divisor of {n_stack} and remap the residues "
            f"(core.state.remap_state; see repro.harness elastic re-plan)."
        )
    G = groups if groups is not None else n_stack
    comp: Optional[CompressorConfig] = base
    if rate_rules:
        comp = resolve_compressor(path, base, rate_rules)
    if comp is not None and (comp.name == "none" or size < min_size or not has_residue):
        comp = None

    storage = storage_shape(shape, layout)
    if comp is not None and enc_sig is not None:
        expected = codec_signature(residue_dtype, G, storage)
        if enc_sig != expected:
            _raise_state_drift(
                path, shape, G, layout, residue_dtype, enc_sig, expected
            )
    if comp is None:
        return TensorPlan(
            path=path, shape=shape, size=size, groups=G, layout=layout,
            comp=None, storage=storage, work=(size,), n_chunks=0, k=0,
            bytes_dense=4.0 * size, bytes_payload=4.0 * size,
        )

    # The exact (dense top-k) analysis path always runs on the flat view;
    # chunked selection runs wherever the layout puts the chunks.
    work = (size,) if (layout == "flat" or comp.exact) else storage
    rows = int(np.prod(work[:-1])) if len(work) > 1 else 1
    nch = rows * num_chunks(work[-1], comp.chunk)
    k = exact_k(size, comp) if comp.exact else nch * comp.topm
    return TensorPlan(
        path=path, shape=shape, size=size, groups=G, layout=layout,
        comp=comp, storage=storage, work=work, n_chunks=nch, k=k,
        bytes_dense=4.0 * size, bytes_payload=payload_bytes(comp, k, G),
    )


@functools.lru_cache(maxsize=128)
def _plan_cached(
    leaves: Tuple[Tuple[str, Shape, int], ...],
    residue_paths: frozenset,
    layout: str,
    base: CompressorConfig,
    rate_rules: Tuple,
    min_size: int,
    groups: Optional[int],
    residue_dtype: str,
) -> Tuple[TensorPlan, ...]:
    # residue_paths elements are either bare paths (no drift validation) or
    # (path, enc_signature) pairs from core.state.residue_signature — the
    # signature both keys the cache (a remapped state re-plans) and is
    # validated against what this plan will decode.
    sigs = {e[0]: e[1] for e in residue_paths if isinstance(e, tuple)}
    paths = {e if isinstance(e, str) else e[0] for e in residue_paths}
    return tuple(
        _plan_one(
            path, shape, n_stack, layout, base, rate_rules, min_size, groups,
            path in paths, residue_dtype, sigs.get(path),
        )
        for path, shape, n_stack in leaves
    )


# ---------------------------------------------------------------------------
# bucketing — the launch-granularity stage of the overlap-aware reduce
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One launch unit of the bucketed reduce (core.overlap schedules these).

    leaf_ids:      indices into the plan/leaf tuple, in REVERSE leaf order —
                   reverse-autodiff produces gradients for the LAST parameters
                   first, so packing reversed leaves keeps each bucket's
                   tensors becoming ready together and lets the first bucket's
                   compress+all-reduce launch while earlier layers are still
                   in backward.
    bytes_dense:   summed dense gradient bytes (the packing target —
                   bucket_bytes bounds THIS, mirroring DDP's bucket_cap_mb;
                   payload bytes vary per compressor and would make bucket
                   geometry depend on rate rules).
    bytes_payload: summed per-worker wire bytes (feeds the overlap timeline
                   in analysis.perfmodel).
    """

    index: int
    leaf_ids: Tuple[int, ...]
    bytes_dense: float
    bytes_payload: float


@functools.lru_cache(maxsize=128)
def _buckets_cached(
    plans: Tuple[TensorPlan, ...], bucket_bytes: int
) -> Tuple[Bucket, ...]:
    order = range(len(plans) - 1, -1, -1)  # grad-ready (reverse leaf) order
    buckets = []
    ids: list = []
    acc_dense = acc_payload = 0.0
    for i in order:
        p = plans[i]
        if ids and acc_dense + p.bytes_dense > bucket_bytes:
            buckets.append(Bucket(len(buckets), tuple(ids), acc_dense, acc_payload))
            ids, acc_dense, acc_payload = [], 0.0, 0.0
        ids.append(i)
        acc_dense += p.bytes_dense
        acc_payload += p.bytes_payload
    if ids:
        buckets.append(Bucket(len(buckets), tuple(ids), acc_dense, acc_payload))
    return tuple(buckets)


def plan_buckets(
    plans: Tuple[TensorPlan, ...], bucket_bytes: int
) -> Tuple[Bucket, ...]:
    """Pack TensorPlans into size-targeted launch buckets (cached).

    Greedy first-fit in reverse-autodiff grad-ready order: a bucket closes
    when adding the next tensor would push its summed *dense* bytes past
    ``bucket_bytes``. Every tensor lands in exactly one bucket — dense
    fallbacks and rate-rule tensors ride along in grad order (a dense reduce
    is still a collective worth overlapping); a tensor larger than
    ``bucket_bytes`` gets a bucket of its own. Bucketing changes launch
    granularity ONLY: per-tensor plans (and therefore the reduce numerics)
    are untouched, which is what keeps bucketed ≡ unbucketed bitwise.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    return _buckets_cached(tuple(plans), int(bucket_bytes))


def plan_tensors(
    leaves: Tuple[Tuple[str, Shape, int], ...],
    cfg,
    residue_paths,
) -> Tuple[TensorPlan, ...]:
    """Plans for a flattened gradient tree, cached per tree structure.

    leaves:        tuple of (path, param_shape, worker_axis_size) — the tree
                   signature (shapes only, no arrays), hashable.
    cfg:           ScaleComConfig (only the plan-relevant fields key the
                   cache, so backend instances etc. don't defeat it).
    residue_paths: paths that carry EF state (init_state's min_size cut);
                   tensors without a residue are reduced densely. Either bare
                   path strings, or the (path, encoding-signature) pairs of
                   ``core.state.residue_signature`` — with signatures, the
                   plan validates that the stored residues match what the
                   execute stage will decode (layout / codec / worker-count
                   drift raises a named ValueError here instead of a cryptic
                   reshape deep in ``_execute``), and a membership remap
                   (``remap_state``) automatically invalidates stale cached
                   plans because the signature is part of the cache key.

    Also validated here, per tensor: hierarchical divisibility
    (worker_axis_size % cfg.groups == 0) — plan-time, so it survives
    ``python -O`` and names the offending tensor.
    """
    return _plan_cached(
        tuple(leaves),
        frozenset(residue_paths),
        cfg.layout,
        cfg.compressor,
        tuple(cfg.rate_rules),
        cfg.min_size,
        cfg.groups,
        cfg.residue_dtype,
    )
