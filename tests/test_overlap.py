"""Overlap-aware bucketed reduce: bucket packing, env/arg resolution, the
bitwise bucketed ≡ unbucketed contract, and the modeled hidden fraction.

The load-bearing invariant: bucketing changes launch granularity ONLY — same
per-tensor plans, same EF residues — so a 20-step bucketed trajectory must be
BITWISE identical to the single-shot one, in both layouts, on both backends,
with or without the optimization_barrier token chain.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.perfmodel import (
    overlap_report,
    overlap_timeline,
    reference_transformer_perf,
)
from repro.core import overlap
from repro.core.compressors import CompressorConfig
from repro.core.plan import Bucket, plan_buckets, plan_tensors
from repro.core.scalecom import ScaleComConfig, scalecom_reduce
from repro.core.state import init_state

CHUNK = 8


def _cfg(**kw):
    base = dict(
        compressor=CompressorConfig("clt_k", chunk=CHUNK),
        beta=0.25,
        min_size=64,
    )
    base.update(kw)
    return ScaleComConfig(**base)


def _plans(cfg, leaves, residues=None):
    if residues is None:
        residues = [p for p, _, _ in leaves]
    return plan_tensors(tuple(leaves), cfg, frozenset(residues))


# ---------------------------------------------------------------------------
# plan_buckets
# ---------------------------------------------------------------------------


def test_plan_buckets_packs_reverse_grad_ready_order():
    cfg = _cfg(min_size=1)
    leaves = tuple((f"['w{i}']", (256,), 4) for i in range(6))  # 1 KB each
    plans = _plans(cfg, leaves)
    buckets = plan_buckets(plans, 2 * 1024)  # 2 tensors per bucket
    assert [b.leaf_ids for b in buckets] == [(5, 4), (3, 2), (1, 0)]
    assert all(b.bytes_dense == 2 * 1024 for b in buckets)
    # every leaf lands in exactly one bucket
    seen = sorted(i for b in buckets for i in b.leaf_ids)
    assert seen == list(range(6))


def test_plan_buckets_oversize_tensor_gets_own_bucket():
    cfg = _cfg(min_size=1)
    leaves = (("['small']", (64,), 4), ("['huge']", (8192,), 4))
    buckets = plan_buckets(_plans(cfg, leaves), 1024)
    assert [b.leaf_ids for b in buckets] == [(1,), (0,)]
    assert buckets[0].bytes_dense == 4.0 * 8192  # over target, still one bucket


def test_plan_buckets_includes_dense_fallback_tensors():
    """Dense-reduced tensors (below min_size / rate-ruled off) still ride in
    buckets — a dense mean is a collective worth overlapping too."""
    cfg = _cfg(min_size=128)
    leaves = (("['tiny']", (16,), 4), ("['big']", (1024,), 4))
    plans = _plans(cfg, leaves)
    assert plans[0].dense and not plans[1].dense
    buckets = plan_buckets(plans, 1 << 20)
    assert buckets[0].leaf_ids == (1, 0)
    assert buckets[0].bytes_payload == plans[0].bytes_payload + plans[1].bytes_payload


def test_plan_buckets_empty_tree():
    """An empty param tree plans to an empty schedule — and the bucketed
    reduce over it is a no-op, not a crash."""
    cfg = _cfg(min_size=1)
    assert plan_buckets((), 1024) == ()
    state = init_state({}, 4, min_size=1)
    ghat, new_state, stats = scalecom_reduce({}, state, cfg, buckets=1024)
    assert ghat == {}
    assert int(new_state.t) == int(state.t) + 1
    assert float(stats["comm_bytes_per_worker"]) == 0.0


def test_plan_buckets_all_oversize_one_bucket_each():
    """A tree of ONLY oversize tensors degenerates to one bucket per tensor,
    in reverse grad-ready order."""
    cfg = _cfg(min_size=1)
    leaves = tuple((f"['w{i}']", (2048,), 4) for i in range(3))  # 8 KB each
    buckets = plan_buckets(_plans(cfg, leaves), 1024)
    assert [b.leaf_ids for b in buckets] == [(2,), (1,), (0,)]
    assert all(b.bytes_dense == 4.0 * 2048 for b in buckets)


def test_plan_buckets_exact_boundary_stays_in_bucket():
    """A tensor landing EXACTLY on bucket_bytes does not open a new bucket:
    the close condition is strictly greater-than (DDP bucket_cap semantics)."""
    cfg = _cfg(min_size=1)
    leaves = tuple((f"['w{i}']", (256,), 4) for i in range(3))  # 1 KB each
    buckets = plan_buckets(_plans(cfg, leaves), 2048)
    assert [b.leaf_ids for b in buckets] == [(2, 1), (0,)]
    assert buckets[0].bytes_dense == 2048.0  # filled to the boundary exactly


@pytest.mark.parametrize("layout", ["flat", "rowwise"])
def test_edge_trees_bitwise_identical(layout):
    """The bitwise bucketed≡unbucketed contract holds on the edge geometries
    too: only-oversize tensors and an exact-boundary pack."""
    n = 4
    for sizes, bucket_bytes in (
        ({"a": (2048,), "b": (2048,)}, 1024),  # every tensor oversize
        ({"a": (256,), "b": (256,)}, 2048),  # sum lands exactly on the target
    ):
        cfg = _cfg(layout=layout, min_size=1)
        params = {k: jnp.zeros(s) for k, s in sizes.items()}
        g = {
            k: jax.random.normal(jax.random.PRNGKey(i), (n,) + s)
            for i, (k, s) in enumerate(sizes.items())
        }
        outs = []
        for buckets in (False, bucket_bytes):
            state = init_state(params, n, min_size=1, layout=layout)
            ghat, new_state, _ = scalecom_reduce(g, state, cfg, buckets=buckets)
            outs.append((ghat, new_state))
        for k in sizes:
            np.testing.assert_array_equal(
                np.asarray(outs[0][0][k]), np.asarray(outs[1][0][k])
            )
        for path in outs[0][1].residues:
            np.testing.assert_array_equal(
                np.asarray(outs[0][1].residues[path]["q"]),
                np.asarray(outs[1][1].residues[path]["q"]),
            )


def test_plan_buckets_cached_and_rejects_nonpositive():
    cfg = _cfg(min_size=1)
    plans = _plans(cfg, (("['w']", (256,), 4),))
    assert plan_buckets(plans, 1024) is plan_buckets(plans, 1024)
    with pytest.raises(ValueError, match="bucket_bytes"):
        plan_buckets(plans, 0)


def test_config_rejects_nonpositive_bucket_bytes():
    with pytest.raises(ValueError, match="bucket_bytes"):
        _cfg(bucket_bytes=0)
    with pytest.raises(ValueError, match="bucket_bytes"):
        _cfg(bucket_bytes=-(1 << 20))


# ---------------------------------------------------------------------------
# resolution: the buckets= arg alone (off unless asked for)
# ---------------------------------------------------------------------------


def test_resolve_bucket_bytes_invalid_values():
    assert overlap.resolve_bucket_bytes(None) is None
    assert overlap.resolve_bucket_bytes(False) is None
    assert overlap.resolve_bucket_bytes(True, default_bytes=123) == 123
    assert overlap.resolve_bucket_bytes(4096) == 4096
    with pytest.raises(ValueError, match="positive"):
        overlap.resolve_bucket_bytes(-1)
    with pytest.raises(TypeError, match="buckets spec"):
        overlap.resolve_bucket_bytes("yes please")
    with pytest.raises(TypeError, match="buckets spec"):
        overlap.resolve_bucket_bytes("auto")


def test_resolve_buckets_passthrough_and_env():
    """A pre-built schedule passes through; None and False are unbucketed;
    an explicit size or True (cfg.bucket_bytes) plans buckets."""
    cfg = _cfg(min_size=1)
    plans = _plans(cfg, (("['w']", (256,), 4),))
    prebuilt = plan_buckets(plans, 512)
    assert overlap.resolve_buckets(prebuilt, cfg, plans) == prebuilt
    assert overlap.resolve_buckets(None, cfg, plans) is None
    assert overlap.resolve_buckets(False, cfg, plans) is None
    sched = overlap.resolve_buckets(1 << 20, cfg, plans)
    assert sched is not None and isinstance(sched[0], Bucket)
    assert overlap.resolve_buckets(True, cfg, plans) == plan_buckets(
        plans, cfg.bucket_bytes
    )


# ---------------------------------------------------------------------------
# the bitwise contract: bucketed ≡ unbucketed over a 20-step trajectory
# ---------------------------------------------------------------------------

_TREE_SIZES = {"a": (96,), "b": (24, 16), "c": (520,), "tiny": (16,)}


def _trajectory(cfg, buckets, steps=20, n=4, seed=0):
    params = {k: jnp.zeros(s) for k, s in _TREE_SIZES.items()}
    state = init_state(params, n, min_size=cfg.min_size, layout=cfg.layout)
    reduce_fn = jax.jit(
        lambda g, s: scalecom_reduce(g, s, cfg, buckets=buckets)
    )
    key = jax.random.PRNGKey(seed)
    ghats = []
    for _ in range(steps):
        key, sub = jax.random.split(key)
        g = {
            k: jax.random.normal(jax.random.fold_in(sub, i), (n,) + s)
            for i, (k, s) in enumerate(_TREE_SIZES.items())
        }
        ghat, state, _ = reduce_fn(g, state)
        ghats.append(ghat)
    return ghats, state


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("layout", ["flat", "rowwise"])
def test_bucketed_trajectory_bitwise_identical(layout, backend):
    cfg = _cfg(layout=layout, backend=backend)
    ghats_u, state_u = _trajectory(cfg, buckets=False)
    # 1 KB buckets -> several buckets over this tree, incl. the dense tiny leaf
    ghats_b, state_b = _trajectory(cfg, buckets=1024)
    for gu, gb in zip(ghats_u, ghats_b):
        for k in _TREE_SIZES:
            np.testing.assert_array_equal(np.asarray(gu[k]), np.asarray(gb[k]))
    for path in state_u.residues:
        np.testing.assert_array_equal(
            np.asarray(state_u.residues[path]["q"]),
            np.asarray(state_b.residues[path]["q"]),
        )


def test_sync_fallback_and_env_leg_bitwise_identical():
    """overlap=False (the synchronous fallback) and buckets=True at a ~1 KB
    cfg.bucket_bytes both stay bitwise identical to the single-shot launch."""
    ghats_u, state_u = _trajectory(_cfg(), buckets=False, steps=6)
    ghats_s, state_s = _trajectory(_cfg(overlap=False), buckets=1024, steps=6)
    ghats_e, state_e = _trajectory(_cfg(bucket_bytes=1048), buckets=True, steps=6)
    for gu, gs, ge in zip(ghats_u, ghats_s, ghats_e):
        for k in _TREE_SIZES:
            np.testing.assert_array_equal(np.asarray(gu[k]), np.asarray(gs[k]))
            np.testing.assert_array_equal(np.asarray(gu[k]), np.asarray(ge[k]))
    for path in state_u.residues:
        np.testing.assert_array_equal(
            np.asarray(state_u.residues[path]["q"]),
            np.asarray(state_s.residues[path]["q"]),
        )
        np.testing.assert_array_equal(
            np.asarray(state_u.residues[path]["q"]),
            np.asarray(state_e.residues[path]["q"]),
        )


def test_bucketed_stats_match_unbucketed():
    cfg = _cfg()
    params = {k: jnp.zeros(s) for k, s in _TREE_SIZES.items()}
    state = init_state(params, 4, min_size=cfg.min_size)
    g = {
        k: jax.random.normal(jax.random.PRNGKey(i), (4,) + s)
        for i, (k, s) in enumerate(_TREE_SIZES.items())
    }
    _, _, su = scalecom_reduce(g, state, cfg, buckets=False, compute_stats=True)
    _, _, sb = scalecom_reduce(g, state, cfg, buckets=1024, compute_stats=True)
    for key in su:
        np.testing.assert_array_equal(np.asarray(su[key]), np.asarray(sb[key]))


# ---------------------------------------------------------------------------
# the modeled overlap timeline (analysis.perfmodel)
# ---------------------------------------------------------------------------


def test_reference_transformer_hidden_fraction_at_25mb():
    """The ISSUE-6 acceptance number: >= 0.5 of comm time hidden for the
    reference transformer at the default 25 MB buckets."""
    rep = overlap_report(reference_transformer_perf(), "scalecom", 25 << 20)
    assert rep["hidden_fraction"] >= 0.5
    assert rep["speedup_vs_unbucketed"] > 1.0
    assert rep["exposed_comm"] < rep["t_step"]


def test_unbucketed_timeline_hides_nothing():
    cfg = reference_transformer_perf()
    tl = overlap_timeline(cfg, "scalecom", bucket_bytes=cfg.params * 4)
    assert tl["n_buckets"] == 1
    assert tl["hidden_fraction"] == pytest.approx(0.0, abs=1e-9)
    # single bucket only becomes ready when backward completes
    assert tl["buckets"][0]["ready"] == pytest.approx(tl["t_compute"])


def test_timeline_comm_serialized_in_schedule_order():
    cfg = reference_transformer_perf()
    tl = overlap_timeline(cfg, "scalecom", 25 << 20)
    rows = tl["buckets"]
    assert len(rows) == tl["n_buckets"] > 1
    for prev, cur in zip(rows, rows[1:]):
        assert cur["comm_start"] >= prev["comm_end"]  # one link, in order
        assert cur["ready"] >= prev["ready"]  # grad-ready order
    # per-bucket comm shares sum back to the unbucketed link time
    total = sum(r["comm_end"] - r["comm_start"] for r in rows)
    assert total == pytest.approx(tl["t_comm_total"])


def test_timeline_degrades_for_uncompressed_scheme():
    """Dense all-reduce can't hide behind this config's backward (comm >>
    compute) — the model must say so rather than flatter it."""
    cfg = reference_transformer_perf()
    dense = overlap_timeline(cfg, "none", 25 << 20)
    sc = overlap_timeline(cfg, "scalecom", 25 << 20)
    assert dense["hidden_fraction"] < sc["hidden_fraction"]
    assert dense["exposed_comm"] > sc["exposed_comm"]


def test_timeline_rejects_nonpositive_bucket_bytes():
    with pytest.raises(ValueError, match="bucket_bytes"):
        overlap_timeline(reference_transformer_perf(), "scalecom", 0)


# ---------------------------------------------------------------------------
# the fused-vs-unfused HBM pass model (analysis.perfmodel.reduce_hbm_passes)
# ---------------------------------------------------------------------------


def test_fused_hbm_passes_strictly_fewer():
    """The fused single-launch reduce must model strictly fewer HBM passes
    than the 3-launch chain for every worker count — the PR's acceptance
    criterion — and both break down into per-phase passes that sum to the
    total."""
    from repro.analysis.perfmodel import fused_hbm_report, reduce_hbm_passes

    for workers in (1, 2, 8, 64):
        fused = reduce_hbm_passes(True, workers=workers)
        unfused = reduce_hbm_passes(False, workers=workers)
        assert fused["passes_total"] < unfused["passes_total"]
        for model in (fused, unfused):
            assert model["passes_total"] == sum(model["phases"].values())
        # the saved passes are exactly the inter-launch re-streaming: the ef
        # materialization (3) and the select's re-read (1)
        assert unfused["passes_total"] - fused["passes_total"] == 4.0

    rep = fused_hbm_report(1 << 20, workers=8)
    assert rep["fused"]["bytes"] < rep["unfused"]["bytes"]
    assert rep["traffic_ratio"] > 2.0  # ~7.1/3.1 at 8 workers
    assert rep["launches"] == {"unfused": 3, "fused": 1}
    base = 8 * (1 << 20) * 4
    assert rep["fused"]["phases"]["fused_kernel"] == 3.0 * base
