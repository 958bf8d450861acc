"""Engine 2: jaxpr-level verification of the bucketed schedule contract.

The overlap-aware bucketed launch (core.overlap) makes three promises that a
source-level linter cannot see — they live in the *traced graph*:

  1. **Deterministic bucket order.** The per-bucket optimization_barrier
     pairs (stage, fence) appear in exactly ``plan_buckets`` schedule order,
     threaded on one token chain: stage_b consumes fence_{b-1}'s token, so
     XLA cannot legally reorder per-bucket collectives across ranks — the
     classic bucketed-collective deadlock-avoidance requirement (every rank
     must issue the same collectives in the same order).
  2. **Bucket independence.** Bucket N's compute (the slice of the graph its
     fence depends on) has NO data dependence on bucket N+1's gradient
     leaves. This is what lets the latency-hiding scheduler issue bucket 0's
     compressed all-reduce while later buckets' gradients are still being
     produced by backward.
  3. **Trace determinism.** Tracing the same (config, tree-structure) twice
     yields a character-identical jaxpr. Cache-key drift here means silent
     recompilation every step — the systems failure Agarwal et al. 2021
     single out as erasing compression's modeled gains.

``check_schedule`` traces ``scalecom_reduce`` on a synthetic 6-tensor tree
packed into >= 3 buckets and verifies all three properties structurally; the
registered ``collective-schedule`` rule runs it for BOTH layouts (flat and
rowwise resolve to different work views but must produce the same schedule
shape). Findings anchor to virtual ``<jaxpr:LAYOUT>`` paths, line 0.

The checker is deliberately trace-only: no device execution, no collectives
actually run, so it is safe (and fast) in a lint leg on a CPU runner.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Set, Tuple

from repro.analysis.scalecheck.engine import register_rule
from repro.analysis.scalecheck.findings import Finding

__all__ = ["check_schedule", "trace_schedule"]

_BARRIER_PRIMITIVE = "optimization_barrier"
# Single-device trace proxy for the worker-axis collective: the k-value
# all-reduce traces as a reduction over the worker axis (reduce_sum under
# jnp.mean, reduce_* under the selectors). Presence of a reduction inside a
# bucket's stage->fence span is the "this bucket issues its collective here"
# witness.
_REDUCE_MARKER = "reduce"


def _default_setup(layout: str):
    """A 6-tensor tree that packs into 3 buckets of 2 tensors each."""
    import jax.numpy as jnp

    from repro.core.scalecom import ScaleComConfig
    from repro.core.compressors import CompressorConfig
    from repro.core.state import init_state

    n_workers = 4
    shape = (8, 256)  # 2048 fp32 elements = 8 KiB dense
    params = {f"p{i}": jnp.zeros(shape, jnp.float32) for i in range(6)}
    grads = {
        k: jnp.ones((n_workers,) + shape, jnp.float32) for k in params
    }
    cfg = ScaleComConfig(
        compressor=CompressorConfig(name="clt_k", chunk=64, topm=1),
        layout=layout,
        backend="jnp",  # the reference chain; kernel dispatch is out of scope
        min_size=1,
        bucket_bytes=2 * 8192,  # two 8 KiB tensors per bucket -> 3 buckets
        overlap=True,
    )
    state = init_state(params, n_workers, min_size=1, layout=layout)
    return grads, state, cfg


def trace_schedule(layout: str, *, overlap: bool = True):
    """Trace scalecom_reduce bucketed in ``layout``; return
    (closed_jaxpr, schedule, n_grad_leaves).

    ``overlap=False`` traces the synchronous fallback — used by tests as the
    negative control (the checker must fail it)."""
    import dataclasses

    import jax

    from repro.core import overlap as overlap_mod
    from repro.core.plan import plan_tensors
    from repro.core.scalecom import scalecom_reduce

    grads, state, cfg = _default_setup(layout)
    if not overlap:
        cfg = dataclasses.replace(cfg, overlap=False)

    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    plans = plan_tensors(
        tuple(
            (jax.tree_util.keystr(p), tuple(g.shape[1:]), g.shape[0])
            for p, g in flat
        ),
        cfg,
        frozenset(state.residues),
    )
    schedule = overlap_mod.resolve_buckets(True, cfg, plans)

    def fn(g, s):
        return scalecom_reduce(g, s, cfg, buckets=True)

    closed = jax.make_jaxpr(fn)(grads, state)
    return closed, schedule, len(flat)


def _barrier_eqns(jaxpr) -> List[Tuple[int, Any]]:
    return [
        (i, eqn)
        for i, eqn in enumerate(jaxpr.eqns)
        if eqn.primitive.name == _BARRIER_PRIMITIVE
    ]


def _has_reduction(eqn) -> bool:
    """Reduction primitive in this eqn, descending into call/closed jaxprs."""
    if _REDUCE_MARKER in eqn.primitive.name:
        return True
    for v in eqn.params.values():
        inner = getattr(v, "jaxpr", None)
        if inner is not None and any(_has_reduction(e) for e in inner.eqns):
            return True
    return False


def _dependency_closure(jaxpr, seed_vars) -> Set[int]:
    """ids of every var the seeds transitively depend on (backward slice)."""
    producer: Dict[int, Any] = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producer[id(ov)] = eqn
    seen: Set[int] = set()
    stack = [v for v in seed_vars]
    while stack:
        v = stack.pop()
        if id(v) in seen or not hasattr(v, "aval"):
            continue  # literals carry no dependence
        seen.add(id(v))
        eqn = producer.get(id(v))
        if eqn is not None:
            stack.extend(eqn.invars)
    return seen


def check_schedule(layout: str, *, overlap: bool = True) -> List[Finding]:
    """Verify the three schedule properties on one layout's bucketed trace."""
    path = f"<jaxpr:{layout}>"

    def finding(msg: str) -> Finding:
        return Finding(rule="collective-schedule", path=path, line=0, message=msg)

    closed, schedule, n_leaves = trace_schedule(layout, overlap=overlap)
    jaxpr = closed.jaxpr
    out: List[Finding] = []

    if schedule is None or len(schedule) < 3:
        return [
            finding(
                "internal: synthetic setup no longer packs >= 3 buckets "
                f"(got {0 if schedule is None else len(schedule)}); the "
                "schedule checks below would be vacuous"
            )
        ]

    K = len(schedule)
    barriers = _barrier_eqns(jaxpr)
    if len(barriers) != 2 * K:
        out.append(
            finding(
                f"expected {2 * K} optimization_barrier eqns "
                f"(stage+fence per bucket x {K} buckets), found "
                f"{len(barriers)}: the token chain is not threading every "
                "bucket"
            )
        )
        return out  # every later check keys off the barrier pairing

    grad_invars = jaxpr.invars[:n_leaves]  # grads flatten before state
    leaf_var = {i: v for i, v in enumerate(grad_invars)}

    # 1. token chain + bucket order ------------------------------------
    for j in range(1, 2 * K):
        prev_tok = barriers[j - 1][1].outvars[-1]
        cur_tok = barriers[j][1].invars[-1]
        if cur_tok is not prev_tok:
            out.append(
                finding(
                    f"token chain broken between barrier {j - 1} and "
                    f"{j}: barrier {j}'s token input is not barrier "
                    f"{j - 1}'s token output, so XLA may reorder these "
                    "collectives across ranks"
                )
            )
    for b, bucket in enumerate(schedule):
        stage = barriers[2 * b][1]
        staged = stage.invars[:-1]
        expect = [leaf_var[i] for i in bucket.leaf_ids]
        if len(staged) != len(expect) or any(
            s is not e for s, e in zip(staged, expect)
        ):
            out.append(
                finding(
                    f"bucket {b} stage barrier does not stage exactly the "
                    f"schedule's leaves {list(bucket.leaf_ids)} in order: "
                    "collective issue order diverges from plan_buckets"
                )
            )

    # 2. per-bucket collective + independence --------------------------
    for b, bucket in enumerate(schedule):
        stage_pos, fence_pos = barriers[2 * b][0], barriers[2 * b + 1][0]
        if not any(
            _has_reduction(jaxpr.eqns[i]) for i in range(stage_pos + 1, fence_pos)
        ):
            out.append(
                finding(
                    f"bucket {b}: no reduction between its stage and fence "
                    "barriers — the bucket's collective is not fenced by "
                    "its own token pair"
                )
            )
        fence = barriers[2 * b + 1][1]
        closure = _dependency_closure(jaxpr, fence.invars)
        later = [
            i
            for later_bucket in schedule[b + 1 :]
            for i in later_bucket.leaf_ids
            if id(leaf_var[i]) in closure
        ]
        if later:
            out.append(
                finding(
                    f"bucket {b}'s fence depends on later buckets' gradient "
                    f"leaves {later}: bucket independence is broken, so the "
                    "all-reduce cannot overlap remaining backward compute"
                )
            )

    # 3. retrace determinism -------------------------------------------
    closed2, _, _ = trace_schedule(layout, overlap=overlap)
    if str(jaxpr) != str(closed2.jaxpr):
        out.append(
            finding(
                "re-tracing with identical plan inputs produced a different "
                "jaxpr: cache-key drift — this recompiles every step"
            )
        )
    return out


@register_rule(
    "collective-schedule",
    "jaxpr",
    "bucketed reduce: token-chained bucket order, independence, retrace "
    "determinism (traced, both layouts)",
)
def check_collective_schedule(_sources) -> List[Finding]:
    out: List[Finding] = []
    for layout in ("flat", "rowwise"):
        out.extend(check_schedule(layout))
    return out
