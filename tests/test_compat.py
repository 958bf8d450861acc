"""Compat-layer delegation (monkeypatched) + residue codec round-trip
properties.

The codec section is the acceptance gate for the stochastic-rounding /
error-compensation work: the quantized EF trajectory must track the fp32 one
through the exact scenario of test_scalecom.py::test_residue_codecs_bounded_error
with >=25% margin on that test's tolerances, and encode∘decode must stay a
contraction over a long (50-step) accumulation loop for every codec.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import jax_compat
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig, scalecom_reduce
from repro.core.state import CODECS, codec_key, codec_roundtrip_error, init_state


# ---------------------------------------------------------------------------
# delegation to the installed jax API
# ---------------------------------------------------------------------------


class _FakeAxisType:
    Auto = "auto"


def test_make_mesh_uses_axis_types_when_available(monkeypatch):
    calls = {}

    def fake_make_mesh(shape, axes, *, axis_types=None, devices=None):
        calls["shape"], calls["axes"] = shape, axes
        calls["axis_types"] = axis_types
        return "fake-mesh"

    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh, raising=False)
    monkeypatch.setattr(jax.sharding, "AxisType", _FakeAxisType, raising=False)
    out = jax_compat.make_mesh((2, 2), ("a", "b"))
    assert out == "fake-mesh"
    assert calls["axis_types"] == (_FakeAxisType.Auto, _FakeAxisType.Auto)


def test_set_mesh_prefers_new_api(monkeypatch):
    entered = {}

    @contextlib.contextmanager
    def fake_set_mesh(mesh):
        entered["mesh"] = mesh
        yield mesh

    monkeypatch.setattr(jax, "set_mesh", fake_set_mesh, raising=False)
    with jax_compat.set_mesh("m") as m:
        assert m == "m"
    assert entered["mesh"] == "m"


def test_shard_map_prefers_top_level(monkeypatch):
    seen = {}

    def fake_shard_map(f, *, mesh, in_specs, out_specs):
        seen["mesh"] = mesh
        return f

    monkeypatch.setattr(jax, "shard_map", fake_shard_map, raising=False)
    f = jax_compat.shard_map(lambda x: x, mesh="m", in_specs=(), out_specs=())
    assert f(3) == 3 and seen["mesh"] == "m"


# ---------------------------------------------------------------------------
# single-import-point enforcement
# ---------------------------------------------------------------------------


def test_no_version_gated_jax_symbols_outside_compat():
    """Only repro.compat may touch version-gated JAX symbols directly; every
    other call site must go through the compat layer (the portability
    contract this PR establishes). One implementation of the invariant: the
    scalecheck ``compat-boundary`` rule (AST-level, so string literals naming
    the symbols — e.g. the rule's own gated list — are not false positives the
    way the historical grep had)."""
    import pathlib

    from repro.analysis import scalecheck

    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    findings = scalecheck.run([str(src)], rules=["compat-boundary"])
    assert not findings, scalecheck.format_text(findings)


# ---------------------------------------------------------------------------
# codec round-trip properties
# ---------------------------------------------------------------------------

# 25%-margin thresholds on test_residue_codecs_bounded_error's tolerances
# (bf16: 0.02, fp8-family: 0.08) — the acceptance gate for the codec work.
_MARGIN_5STEP = {"bf16": 0.75 * 0.02, "fp8_ec": 0.75 * 0.08}


def _ef_trajectory_error(dtype: str, steps: int = 5) -> float:
    """Exact scenario of test_scalecom.py::test_residue_codecs_bounded_error."""
    n, size = 4, 2048
    params = {"w": jnp.zeros((size,))}
    cfgq = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=8), beta=0.2, min_size=1,
        residue_dtype=dtype,
    )
    cfg32 = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=8), beta=0.2, min_size=1
    )
    sq = init_state(params, n, dtype, min_size=1)
    s32 = init_state(params, n, min_size=1)
    key = jax.random.PRNGKey(0)
    for _ in range(steps):
        key, sub = jax.random.split(key)
        g = {"w": jax.random.normal(sub, (n, size))}
        _, sq, _ = scalecom_reduce(g, sq, cfgq)
        _, s32, _ = scalecom_reduce(g, s32, cfg32)
    mq = CODECS[dtype].decode(sq.residues["['w']"], (size,))
    m32 = CODECS["fp32"].decode(s32.residues["['w']"], (size,))
    return float(jnp.linalg.norm(mq - m32) / jnp.linalg.norm(m32))


@pytest.mark.parametrize("dtype", ["bf16", "fp8_ec"])
def test_codec_trajectory_error_with_margin(dtype):
    err = _ef_trajectory_error(dtype)
    assert err < _MARGIN_5STEP[dtype], (dtype, err)


def test_bf16_stochastic_rounding_unbiased():
    """Mean over dither keys converges to the fp32 value (RN cast does not)."""
    from repro.core.state import stochastic_round

    x = jnp.asarray([1.0 + 2.0**-9, -0.3, 3.14159e-3], jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 4096)
    samples = jax.vmap(
        lambda k: stochastic_round(x, k, jnp.bfloat16).astype(jnp.float32)
    )(keys)
    sr_bias = np.abs(np.asarray(jnp.mean(samples, 0) - x))
    rn_bias = np.abs(np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32) - x))
    # SR bias shrinks with sampling; RN bias is structural (~ulp/2)
    assert np.all(sr_bias < 0.2 * np.maximum(rn_bias, 1e-7)), (sr_bias, rn_bias)


@pytest.mark.parametrize(
    "name,per_step_bound",
    [("fp32", 1e-12), ("bf16", 6e-3), ("fp8", 6e-2), ("fp8_ec", 5e-4)],
)
def test_codec_roundtrip_contraction_50_steps(name, per_step_bound):
    """encode∘decode stays a contraction through a 50-step accumulation loop:
    worst per-step relative roundtrip error bounded by the format's noise
    floor (<< 1), and the accumulated drift vs an exact fp32 shadow does not
    blow up (no bias accumulation — the stochastic-rounding guarantee)."""
    r = codec_roundtrip_error(name, steps=50)
    assert r["worst_step"] < per_step_bound, r
    # unbiased rounding: drift grows ~sqrt(steps), not linearly; allow 10x
    # the per-step floor (fp32 is exact)
    assert r["drift"] < max(10 * per_step_bound, 1e-12), r


def test_codec_key_is_jittable_and_step_dependent():
    k0 = codec_key("['w']", jnp.int32(0))
    k1 = codec_key("['w']", jnp.int32(1))
    k0b = jax.jit(lambda t: codec_key("['w']", t))(jnp.int32(0))
    assert not np.array_equal(np.asarray(k0), np.asarray(k1))
    np.testing.assert_array_equal(np.asarray(k0), np.asarray(k0b))
