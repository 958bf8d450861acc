"""The plan stage: per-tensor resolution, caching, layout validation and the
one-rule byte accounting (core/plan.py + core.state.storage_shape)."""

import jax.numpy as jnp
import pytest

from repro.core.compressors import CompressorConfig
from repro.core.plan import payload_bytes, plan_tensors
from repro.core.rates import RateRule
from repro.core.scalecom import ScaleComConfig
from repro.core.state import storage_shape


def _plans(cfg, leaves, residues=None):
    if residues is None:
        residues = [p for p, _, _ in leaves]
    return plan_tensors(tuple(leaves), cfg, frozenset(residues))


def test_plan_is_cached_per_tree_structure():
    cfg = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=16), min_size=1)
    leaves = (("['w']", (8, 64), 4), ("['b']", (64,), 4))
    p1 = _plans(cfg, leaves)
    p2 = _plans(cfg, leaves)
    assert p1 is p2  # lru_cache hit: resolved once per tree structure
    # a different structure (or config) misses
    p3 = _plans(cfg, (("['w']", (8, 32), 4),))
    assert p3 is not p1


def test_plan_rate_rules_and_min_size():
    cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=16),
        min_size=128,
        rate_rules=(RateRule(r"embed", None), RateRule(r"mlp", 64, topm=2)),
    )
    leaves = (
        ("['embed']", (4096,), 4),   # rule: never compress
        ("['mlp']", (4096,), 4),     # rule: chunk 64, topm 2
        ("['other']", (4096,), 4),   # base compressor
        ("['tiny']", (16,), 4),      # below min_size
        ("['warm']", (4096,), 4),    # no residue yet (warmup) -> dense
    )
    plans = _plans(cfg, leaves, residues=["['embed']", "['mlp']", "['other']", "['tiny']"])
    by_path = {p.path: p for p in plans}
    assert by_path["['embed']"].dense and by_path["['tiny']"].dense
    assert by_path["['warm']"].dense
    assert by_path["['mlp']"].comp.chunk == 64 and by_path["['mlp']"].comp.topm == 2
    assert by_path["['other']"].comp.chunk == 16
    # dense payload is the gradient itself
    assert by_path["['embed']"].bytes_payload == 4.0 * 4096


@pytest.mark.parametrize("layout", ["flat", "rowwise"])
def test_plan_shapes_and_k(layout):
    cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=16, topm=2), min_size=1,
        layout=layout,
    )
    (p,) = _plans(cfg, (("['w']", (8, 40), 4),))
    assert p.storage == storage_shape((8, 40), layout)
    if layout == "flat":
        assert p.work == (320,)
        assert p.n_chunks == 20  # ceil(320/16)
    else:
        assert p.work == (8, 40)
        assert p.n_chunks == 8 * 3  # ceil(40/16) per row
    assert p.k == p.n_chunks * 2


def test_plan_exact_runs_on_flat_view_in_any_layout():
    cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=16, exact=True), min_size=1,
        layout="rowwise",
    )
    (p,) = _plans(cfg, (("['w']", (8, 64), 4),))
    assert p.work == (512,) and p.storage == (8, 64)
    assert p.k == 512 // 16  # size * topm / chunk


def test_payload_rule_per_compressor():
    k, G = 100, 4
    assert payload_bytes(CompressorConfig("local_topk"), k, G) == 8.0 * k
    assert payload_bytes(CompressorConfig("random_k"), k, G) == 4.0 * k
    for shared in ("clt_k", "true_topk"):
        assert payload_bytes(CompressorConfig(shared), k, G) == 4.0 * k + 4.0 * k / G
    with pytest.raises(ValueError, match="dense"):
        payload_bytes(CompressorConfig("none"), k, G)


def test_topm_beyond_chunk_fails_fast():
    """topm > chunk would silently duplicate indices in the masked-argmax
    kernels (backend-divergent garbage); the config rejects it up front."""
    with pytest.raises(ValueError, match="topm"):
        CompressorConfig("clt_k", chunk=4, topm=6)
    with pytest.raises(ValueError, match="topm"):
        CompressorConfig("clt_k", chunk=16, topm=0)


def test_resolve_layout_invalid_env_value_names_valid_set():
    """A typo'd layout must fail loudly AND name the valid set — at config
    construction and at init_state's storage shapes — not silently fall back
    to flat and quietly change the wire format. "auto" is no longer a
    layout."""
    for bad in ("diagonal", "auto", None):
        with pytest.raises(ValueError, match="unknown chunk layout") as err:
            ScaleComConfig(layout=bad)
        assert "flat" in str(err.value) and "rowwise" in str(err.value)
        with pytest.raises(ValueError, match="unknown chunk layout") as err:
            storage_shape((8, 64), bad)
        assert "flat" in str(err.value) and "rowwise" in str(err.value)
    assert storage_shape((8, 64), "flat") == (512,)
    assert storage_shape((8, 64), "rowwise") == (8, 64)


def test_groups_amortize_the_index_broadcast():
    """Hierarchical mode: the leader broadcast amortizes over G groups, not
    the n underlying ranks."""
    cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=16), min_size=1, groups=2
    )
    (p,) = _plans(cfg, (("['w']", (1024,), 8),))
    assert p.groups == 2
    assert p.bytes_payload == 4.0 * p.k + 4.0 * p.k / 2


def test_scalar_and_0d_params_plan_densely():
    cfg = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=16), min_size=2)
    plans = _plans(cfg, (("['s']", (), 4),), residues=[])
    assert plans[0].dense and plans[0].size == 1
    assert plans[0].bytes_payload == 4.0


def test_plan_leaves_jit_unpolluted():
    """plan_tensors is pure shape/config metadata — no jnp arrays anywhere
    (it must be safe to call at trace time without leaking tracers)."""
    cfg = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=16), min_size=1)
    (p,) = _plans(cfg, (("['w']", (64,), 4),))
    for field in p.__dataclass_fields__:
        assert not isinstance(getattr(p, field), jnp.ndarray), field
