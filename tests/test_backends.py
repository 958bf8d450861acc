"""Backend dispatch layer: jnp vs pallas-interpret parity + resolution rules.

The contract under test (src/repro/backends): the pallas backend in interpret
mode is *bitwise-identical* on indices and allclose on values against the jnp
oracle backend, for every op, both layouts, odd sizes, tail chunks, bf16 and
top-m — and a 20-step scalecom_reduce trajectory is identical between
backend="jnp" and backend="pallas" to fp32 tolerance. Resolution (a spec and
the platform, nothing else) is pure-python and tested directly.
"""

import inspect

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.backends import (
    KernelBackend,
    available_backends,
    resolve_backend,
)
from repro.backends.jnp_backend import JnpBackend
from repro.backends.pallas_backend import PallasBackend
from repro.core import chunked
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig, scalecom_reduce
from repro.core.state import CODECS, init_state

JNP = resolve_backend("jnp")
PAL = resolve_backend("pallas")  # CPU probe -> interpret mode


def _rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


# ---------------------------------------------------------------------------
# resolution / registry
# ---------------------------------------------------------------------------


def test_registry_lists_shipped_backends():
    names = available_backends()
    assert "jnp" in names and "pallas" in names


def test_resolve_by_name_and_instance_passthrough():
    assert isinstance(resolve_backend("jnp"), JnpBackend)
    assert isinstance(resolve_backend("pallas"), PallasBackend)
    inst = JnpBackend()
    assert resolve_backend(inst) is inst


def test_resolve_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_backend("cuda")


# (spec, platform, pallas importable) -> backend class, or the error raised
_RESOLUTION_CASES = [
    ("auto", "cpu", True, JnpBackend),
    (None, "cpu", True, JnpBackend),
    ("auto", "tpu", True, PallasBackend),
    (None, "tpu", True, PallasBackend),
    ("auto", "tpu", False, JnpBackend),
    ("jnp", "tpu", True, JnpBackend),
    ("jnp", "cpu", False, JnpBackend),
    ("pallas", "cpu", True, PallasBackend),
    ("pallas", "tpu", True, PallasBackend),
    ("pallas", "tpu", False, ImportError),
    ("pallas", "cpu", False, ImportError),
    ("cuda", "tpu", True, ValueError),
]


@pytest.mark.parametrize(
    "spec,platform,importable,expected", _RESOLUTION_CASES,
    ids=[f"{c[0]}-{c[1]}-{'pl' if c[2] else 'nopl'}" for c in _RESOLUTION_CASES],
)
def test_backend_resolution(monkeypatch, spec, platform, importable, expected):
    """The backend is a function of the spec and what the code can observe:
    the platform (probed at call time) and whether pallas imports. An
    explicit spec wins over the platform; an unknown name raises and lists
    the registered set; pallas without the package raises ImportError."""
    import repro.backends.base as base
    import repro.backends.pallas_backend as pb

    monkeypatch.setattr(base.jax, "default_backend", lambda: platform)
    monkeypatch.setattr(base, "pallas_available", lambda: importable)
    monkeypatch.setattr(pb, "pallas_available", lambda: importable)
    pb._instance.cache_clear()
    try:
        if isinstance(expected, type) and issubclass(expected, Exception):
            with pytest.raises(expected) as err:
                resolve_backend(spec)
            if expected is ValueError:
                assert "jnp" in str(err.value) and "pallas" in str(err.value)
            else:
                assert "pallas" in str(err.value)
        else:
            assert isinstance(resolve_backend(spec), expected)
    finally:
        pb._instance.cache_clear()


def test_config_rejects_invalid_fused_spec():
    """fused is a bool: no other value (the old "auto" included) defers the
    choice to anything else."""
    for spec in ("auto", "on", None, 1):
        with pytest.raises(ValueError, match="fused must be"):
            ScaleComConfig(fused=spec)


# ---------------------------------------------------------------------------
# flat op parity (1-D buffers, incl. odd sizes / tail chunks / bf16 / top-m)
# ---------------------------------------------------------------------------

SIZES = [1024, 1000, 257]  # aligned, tail chunk, prime
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("chunk", [8, 16, 32, 64, 128])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("topm", [1, 3])
def test_flat_select_parity(size, chunk, dtype, topm):
    x = _rand((size,), size + chunk + topm, dtype)
    i1, v1 = JNP.select(x, chunk, topm)
    i2, v2 = PAL.select(x, chunk, topm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(
        np.asarray(v1, np.float32), np.asarray(v2, np.float32), rtol=1e-6
    )


@pytest.mark.parametrize("size", [1000, 40960])  # rows; lane-dense, ragged
@pytest.mark.parametrize("topm", [1, 2])
def test_flat_gather_scatter_parity(size, topm):
    chunk = 16
    x = _rand((size,), 3)
    idx = JNP.select_indices(x, chunk, topm)
    v1 = JNP.gather(x, idx, chunk, topm)
    v2 = PAL.gather(x, idx, chunk, topm)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
    d1 = JNP.scatter(v1, idx, chunk, size, topm)
    d2 = PAL.scatter(v2, idx, chunk, size, topm)
    assert d1.shape == d2.shape == (size,)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


@pytest.mark.parametrize("size", [1000, 512, 40960])
@pytest.mark.parametrize("beta", [0.1, 1.0])
@pytest.mark.parametrize("topm", [1, 2])
def test_flat_ef_update_parity(size, beta, topm):
    chunk = 16
    m, g = _rand((size,), 11), _rand((size,), 12)
    idx = JNP.select_indices(m + g, chunk, topm)
    m1, v1 = JNP.ef_update(m, g, idx, beta, chunk, topm)
    m2, v2 = PAL.ef_update(m, g, idx, beta, chunk, topm)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# worker-stacked parity (the shapes scalecom_reduce actually dispatches)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topm", [1, 3])
def test_stacked_select_parity(topm):
    ef = _rand((4, 520), 21)  # tail chunk at chunk=16
    i1 = JNP.select_indices(ef, 16, topm)
    i2 = PAL.select_indices(ef, 16, topm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("topm", [1, 2])
def test_stacked_shared_index_gather_ef_parity(topm):
    """Shared leader indices broadcast over the worker axis, both backends."""
    chunk, size, G = 16, 520, 4
    m, g = _rand((G, size), 31), _rand((G, size), 32)
    ef = m + g
    idx = JNP.select_indices(ef[0], chunk, topm)  # shared (ncr[, topm]) set
    v1 = JNP.gather(ef, idx, chunk, topm)
    v2 = PAL.gather(ef, idx, chunk, topm)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
    m1, w1 = JNP.ef_update(m, g, idx, 0.25, chunk, topm)
    m2, w2 = PAL.ef_update(m, g, idx, 0.25, chunk, topm)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-5, atol=1e-7)
    # shared-idx scatter of the value mean (the ĝ densify step)
    d1 = JNP.scatter(jnp.mean(v1, axis=0), idx, chunk, size, topm)
    d2 = PAL.scatter(jnp.mean(v2, axis=0), idx, chunk, size, topm)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


# ---------------------------------------------------------------------------
# trailing-axis parity on batched (layout-preserving) shapes — the SAME ops
# as the flat tests above; rowwise is just a non-degenerate leading shape
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("topm", [1, 2])
# chunk multiple + tail-chunk padding; 1024: lane-dense in fp32; 384: a
# 128-multiple trailing dim whose 5760 elements make no whole (8, 128) tiles
@pytest.mark.parametrize("C", [48, 45, 1024, 384])
def test_batched_trailing_axis_parity(dtype, topm, C):
    chunk = 16
    x = _rand((3, 5, C), 41, dtype)
    i1 = JNP.select_indices(x, chunk, topm)
    i2 = PAL.select_indices(x, chunk, topm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    v1 = JNP.gather(x, i1, chunk, topm)
    v2 = PAL.gather(x, i2, chunk, topm)
    np.testing.assert_allclose(
        np.asarray(v1, np.float32), np.asarray(v2, np.float32), rtol=1e-6
    )
    d1 = JNP.scatter(v1, i1, chunk, C, topm)
    d2 = PAL.scatter(v2, i2, chunk, C, topm)
    assert d1.shape == d2.shape == (3, 5, C)
    np.testing.assert_allclose(
        np.asarray(d1, np.float32), np.asarray(d2, np.float32), rtol=1e-6
    )


@pytest.mark.parametrize("topm", [1, 2])
def test_batched_ef_update_parity_shared_idx(topm):
    """A shared (no worker axis) index set against worker-stacked 3-D data —
    the exact shapes the rowwise layout dispatches."""
    chunk, G = 16, 4
    m, g = _rand((G, 5, 48), 51), _rand((G, 5, 48), 52)
    idx = JNP.select_indices(jnp.mean(m + g, axis=0), chunk, topm)  # (5, 3[, topm])
    m1, v1 = JNP.ef_update(m, g, idx, 0.25, chunk, topm)
    m2, v2 = PAL.ef_update(m, g, idx, 0.25, chunk, topm)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# lane-dense tiles: the whole 3-launch op set, bitwise against the oracles
# ---------------------------------------------------------------------------


def _tricky(shape, seed):
    """Small integers (magnitude ties in nearly every chunk), NaN lanes and
    all-zero chunks."""
    x = jax.random.randint(jax.random.PRNGKey(seed), shape, -3, 4).astype(jnp.float32)
    flat = x.reshape(-1).at[jnp.array([3, 130, 131, 5000])].set(jnp.nan)
    return flat.at[256:512].set(0.0).reshape(shape)


@pytest.mark.parametrize("topm", [1, 2])
@pytest.mark.parametrize("chunk", [8, 16, 32, 64, 128])
@pytest.mark.parametrize(
    "shape",
    [(2, 5, 4096), (1, 3 * 1024 * 64 + 1024 * 8), (2, 6, 384)],
    ids=["stacked", "ragged_blocks", "rows_side"],
)
def test_lane_dense_ops_match_the_oracles_bitwise(shape, chunk, topm):
    """select, gather, ef_update and scatter on shapes either side of the
    geometry rule: the (2, 5, 4096) stack and the flat buffer of 3.125
    blocks at chunk 64 (a ragged last block in every lane-dense chunk) take
    lane-dense tiles for chunks 16-64; (2, 6, 384) has no whole (8, 128)
    tiles and keeps the rows, as chunks 8 and 128 do everywhere. Indices and
    picked values equal the oracle's bit for bit; m' equals the rows
    geometry's kernel bit for bit (the oracle's own arithmetic may round the
    last bit differently)."""
    from repro.kernels import chunk_topk, ef_update as efk

    size = int(np.prod(shape))
    assert chunk_topk.lane_dense(chunk, shape[-1], size, jnp.float32) is (
        16 <= chunk <= 64 and shape[-1] != 384
    )
    x, g = _tricky(shape, 61), _rand(shape, 62)
    i1, v1 = JNP.select(x, chunk, topm)
    i2, v2 = PAL.select(x, chunk, topm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    idx = i1[0] if shape[0] > 1 else i1  # a shared set, as clt_k broadcasts
    w1 = JNP.gather(x, idx, chunk, topm)
    w2 = PAL.gather(x, idx, chunk, topm)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    m1, e1 = JNP.ef_update(x, g, idx, 0.25, chunk, topm)
    m2, e2 = PAL.ef_update(x, g, idx, 0.25, chunk, topm)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-6, atol=1e-6)
    idx_rows = jnp.broadcast_to(idx, e1.shape).reshape((-1,) + e1.shape[len(shape):])
    m_rows, _ = efk.row_ef_update(
        x.reshape(-1, chunk), g.reshape(-1, chunk), idx_rows, 0.25, chunk,
        interpret=True, block_chunks=1024,
    )
    np.testing.assert_array_equal(np.asarray(m2).reshape(-1), np.asarray(m_rows).reshape(-1))
    d1 = JNP.scatter(e1, idx, chunk, shape[-1], topm)
    d2 = PAL.scatter(e1, idx, chunk, shape[-1], topm)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


@pytest.mark.parametrize(
    "shape,chunk,dtype,dense",
    [
        ((2, 1024), 64, jnp.float32, True),
        ((2, 960), 64, jnp.float32, False),
        ((2, 1024), 64, jnp.bfloat16, False),
        ((2, 1024), 128, jnp.float32, False),
    ],
    ids=["lane_dense", "ragged_width", "bf16", "chunk128"],
)
def test_tile_is_a_kernel_constant(monkeypatch, shape, chunk, dtype, dense):
    """Every pallas launch takes one of kernels.chunk_topk's two tile
    constants, picked by the launch's static shape alone: DENSE_BLOCK_CHUNKS
    where the tiles are lane-dense, BLOCK_CHUNKS otherwise and for the fused
    launch."""
    from repro.kernels import chunk_topk, fused_reduce as fr, rowwise

    seen = []

    def spy(fn):
        def wrapped(*args, **kw):
            seen.append((fn.__name__, kw.get("block_chunks", "default")))
            return fn(*args, **kw)
        return wrapped

    for name in ("select_trailing", "gather_trailing", "ef_update_trailing",
                 "scatter_trailing"):
        monkeypatch.setattr(rowwise, name, spy(getattr(rowwise, name)))
    fused_kernel = fr.fused_reduce_trailing
    monkeypatch.setattr(fr, "fused_reduce_trailing", spy(fused_kernel))
    x, g = _rand(shape, 71, dtype), _rand(shape, 72, dtype)
    idx, _ = PAL.select(x, chunk)
    PAL.gather(x, idx, chunk)
    _, vals = PAL.ef_update(x, g, idx, 0.25, chunk)
    PAL.scatter(vals, idx, chunk, shape[-1])
    PAL.fused_reduce(x, g, 0.25, chunk)
    want = chunk_topk.DENSE_BLOCK_CHUNKS if dense else chunk_topk.BLOCK_CHUNKS
    assert seen[:4] == [
        ("select_trailing", want), ("gather_trailing", want),
        ("ef_update_trailing", want), ("scatter_trailing", want),
    ]
    assert seen[4] == ("fused_reduce_trailing", "default")
    assert inspect.signature(fused_kernel.__wrapped__).parameters[
        "block_chunks"].default == chunk_topk.BLOCK_CHUNKS


# ---------------------------------------------------------------------------
# fused_reduce parity: single launch ≡ composed 3-op ≡ jnp oracle
# ---------------------------------------------------------------------------

# worker-stacked geometries: flat (G, size), rowwise with a tail chunk at
# chunk=16 (45 % 16 != 0), and an aligned rowwise with a non-power-of-2
# worker count
_FUSED_SHAPES = [(4, 200), (4, 5, 45), (3, 7, 64)]


@pytest.mark.parametrize("mode", ["clt_k", "true_topk"])
@pytest.mark.parametrize("topm", [1, 2, 4])
@pytest.mark.parametrize("shape", _FUSED_SHAPES)
def test_fused_reduce_parity(mode, topm, shape):
    """pallas fused_reduce (1 launch) vs the base 3-op composition on both
    backends: bitwise indices, allclose values/residue/ĝ."""
    chunk = 16
    m = _rand(shape, 61 + topm)
    g = _rand(shape, 62 + topm)
    leader = jnp.asarray(1, jnp.int32)
    ref = KernelBackend.fused_reduce(JNP, m, g, 0.25, chunk, topm, mode, leader)
    fused = PAL.fused_reduce(m, g, 0.25, chunk, topm, mode, leader)
    composed = KernelBackend.fused_reduce(PAL, m, g, 0.25, chunk, topm, mode, leader)
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(composed[0]), np.asarray(ref[0]))
    for i in (1, 2, 3):  # vals, m_new, ghat
        np.testing.assert_allclose(
            np.asarray(fused[i]), np.asarray(ref[i]), rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(composed[i]), np.asarray(ref[i]), rtol=1e-6, atol=1e-7
        )


def test_fused_reduce_parity_bf16_tail_chunk():
    chunk, shape = 16, (4, 130)  # bf16 + tail chunk
    m = _rand(shape, 71, jnp.bfloat16)
    g = _rand(shape, 72, jnp.bfloat16)
    leader = jnp.asarray(3, jnp.int32)
    ref = KernelBackend.fused_reduce(JNP, m, g, 0.5, chunk, 2, "clt_k", leader)
    fused = PAL.fused_reduce(m, g, 0.5, chunk, 2, "clt_k", leader)
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(ref[0]))
    for i in (1, 2, 3):
        np.testing.assert_allclose(
            np.asarray(fused[i], np.float32),
            np.asarray(ref[i], np.float32),
            rtol=2e-2,
            atol=2e-2,
        )


def test_fused_reduce_leader_matters():
    """clt_k: the traced leader rank actually picks that worker's indices."""
    chunk, shape = 16, (4, 96)
    m, g = _rand(shape, 81), _rand(shape, 82)
    ef = m + g
    for rank in range(shape[0]):
        idx, _, _, _ = PAL.fused_reduce(
            m, g, 0.25, chunk, 1, "clt_k", jnp.asarray(rank, jnp.int32)
        )
        want = JNP.select_indices(ef[rank], chunk, 1)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))


def test_fused_reduce_rejects_unfusable_mode():
    m = _rand((2, 32), 91)
    with pytest.raises(ValueError, match="clt_k"):
        JNP.fused_reduce(m, m, 0.5, 16, 1, "local_topk", None)
    with pytest.raises(ValueError, match="clt_k"):
        PAL.fused_reduce(m, m, 0.5, 16, 1, "local_topk", None)


# ---------------------------------------------------------------------------
# property sweep (odd sizes x chunks x seeds through the hypothesis shim)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(
    size=st.integers(16, 2000),
    chunk=st.sampled_from([16, 64]),
    topm=st.sampled_from([1, 2]),
    seed=st.integers(0, 10_000),
)
def test_backend_parity_property(size, chunk, topm, seed):
    x = _rand((size,), seed)
    i1, v1 = JNP.select(x, chunk, topm)
    i2, v2 = PAL.select(x, chunk, topm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
    d1 = JNP.scatter(v1, i1, chunk, size, topm)
    d2 = PAL.scatter(v2, i2, chunk, size, topm)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


# ---------------------------------------------------------------------------
# end-to-end: scalecom_reduce trajectory identity + pallas-only dispatch
# ---------------------------------------------------------------------------

_TRAJ_CASES = [
    ("flat", "clt_k", 1),
    ("flat", "clt_k", 2),
    ("flat", "local_topk", 1),
    ("rowwise", "clt_k", 1),
    ("rowwise", "clt_k", 2),  # rowwise top-m: the unified pipeline's new path
    ("rowwise", "local_topk", 2),
]


def _trajectory(layout, compressor, topm, backend, steps=20):
    G, shape = 4, (8, 65)  # odd last dim: rowwise pads, flat has a tail chunk
    params = {"w": jnp.zeros(shape)}
    cfg = ScaleComConfig(
        compressor=CompressorConfig(compressor, chunk=16, topm=topm),
        beta=0.25,
        min_size=1,
        layout=layout,
        backend=backend,
    )
    state = init_state(params, G, min_size=1, layout=layout)
    reduce_fn = jax.jit(lambda g, s: scalecom_reduce(g, s, cfg)[:2])
    ghats = []
    for t in range(steps):
        g = _rand((G,) + shape, 1000 + t)
        ghat, state = reduce_fn({"w": g}, state)
        ghats.append(ghat["w"])
    return ghats, state


@pytest.mark.slow
@pytest.mark.parametrize("layout,compressor,topm", _TRAJ_CASES)
def test_reduce_trajectory_identity_across_backends(layout, compressor, topm):
    """20 steps of Algorithm 1 agree between backend="jnp" and "pallas"."""
    gh1, st1 = _trajectory(layout, compressor, topm, "jnp")
    gh2, st2 = _trajectory(layout, compressor, topm, "pallas")
    for a, b in zip(gh1, gh2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    shape = (8, 65) if layout == "rowwise" else (8 * 65,)
    r1 = CODECS["fp32"].decode(st1.residues["['w']"], shape)
    r2 = CODECS["fp32"].decode(st2.residues["['w']"], shape)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-5, atol=1e-6)


# fused=True vs fused=False must be BITWISE identical through the full reduce
# (the fused kernel composes the exact same fp ops tile-locally). The matrix
# covers every compressor kind (fusable shared-index, non-fusable local_topk),
# topm {1, 2, 4}, both layouts, and the bucketed launch path.
_FUSED_TRAJ_CASES = [
    ("flat", "clt_k", 1, False),
    ("flat", "true_topk", 2, False),
    ("flat", "clt_k", 4, True),
    ("flat", "local_topk", 2, True),  # non-fusable: silent 3-launch fallback
    ("rowwise", "clt_k", 2, False),
    ("rowwise", "true_topk", 4, True),
    ("rowwise", "local_topk", 1, False),
]


def _fused_trajectory(layout, compressor, topm, backend, fused, bucketed,
                      steps=20):
    G = 4
    params = {"w": jnp.zeros((8, 65)), "v": jnp.zeros((3, 40))}
    cfg = ScaleComConfig(
        compressor=CompressorConfig(compressor, chunk=16, topm=topm),
        beta=0.25,
        min_size=1,
        layout=layout,
        backend=backend,
        fused=fused,
        bucket_bytes=2048,  # splits w and v into separate buckets
    )
    state = init_state(params, G, min_size=1, layout=layout)
    reduce_fn = jax.jit(
        lambda g, s: scalecom_reduce(g, s, cfg, buckets=bucketed)[:2]
    )
    ghats = []
    for t in range(steps):
        g = {
            k: _rand((G,) + v.shape, 3000 + 10 * t + i)
            for i, (k, v) in enumerate(sorted(params.items()))
        }
        ghat, state = reduce_fn(g, state)
        ghats.append(ghat)
    return ghats, state


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("layout,compressor,topm,bucketed", _FUSED_TRAJ_CASES)
def test_fused_trajectory_bitwise_identity(layout, compressor, topm, bucketed,
                                           backend):
    """20 steps of Algorithm 1 with fused=True ≡ fused=False, bitwise —
    outputs every step AND the final EF residues."""
    gh1, st1 = _fused_trajectory(layout, compressor, topm, backend, False, bucketed)
    gh2, st2 = _fused_trajectory(layout, compressor, topm, backend, True, bucketed)
    for a, b in zip(gh1, gh2):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert st1.residues.keys() == st2.residues.keys()
    for path in st1.residues:
        for leaf in st1.residues[path]:
            np.testing.assert_array_equal(
                np.asarray(st1.residues[path][leaf]),
                np.asarray(st2.residues[path][leaf]),
                err_msg=f"residue[{path}][{leaf}]",
            )


@pytest.mark.slow
def test_fused_trajectory_across_backends():
    """fused=True trajectories agree between jnp and pallas to fp32 tolerance
    (the cross-backend leg of the fused matrix)."""
    gh1, _ = _fused_trajectory("rowwise", "clt_k", 2, "jnp", True, False)
    gh2, _ = _fused_trajectory("rowwise", "clt_k", 2, "pallas", True, False)
    for a, b in zip(gh1, gh2):
        for k in a:
            np.testing.assert_allclose(
                np.asarray(a[k]), np.asarray(b[k]), rtol=1e-5, atol=1e-6
            )


@pytest.mark.parametrize("layout", ["flat", "rowwise"])
def test_pallas_backend_bypasses_jnp_chunked_ops(monkeypatch, layout):
    """With backend="pallas" no jnp chunked op runs on the compressed path.

    Every core.chunked selection/gather/scatter oracle is replaced with a
    tripwire; only the pad helpers (pure layout, no chunked math) stay. The
    reduce must still complete — i.e. the whole compressed path dispatches
    through the Pallas kernels.
    """

    def _trip(name):
        def fn(*a, **k):
            raise AssertionError(f"jnp chunked op {name} ran under backend='pallas'")

        return fn

    for name in (
        "chunk_argmax", "chunk_topm_indices", "chunk_gather", "chunk_scatter",
        "chunk_view",
    ):
        monkeypatch.setattr(chunked, name, _trip(name))

    G, shape = 2, (4, 33)
    params = {"w": jnp.zeros(shape)}
    cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=16),
        beta=0.5, min_size=1, layout=layout, backend="pallas",
    )
    state = init_state(params, G, min_size=1, layout=layout)
    g = _rand((G,) + shape, 7)
    ghat, state, _ = scalecom_reduce({"w": g}, state, cfg)
    assert ghat["w"].shape == shape
    assert int(state.t) == 1


# ---------------------------------------------------------------------------
# unified-surface tripwires
# ---------------------------------------------------------------------------


def test_no_rw_symbols_survive():
    """The dual flat/rowwise op surface is gone for good — no ``rw_*`` symbol
    anywhere in the package. A reappearing rw_ helper means a feature is about
    to land twice (once per layout), the exact trap the unified trailing-axis
    pipeline removed. One implementation of the invariant: the scalecheck
    ``no-rw-surface`` rule (this wrapper keeps the tripwire in tier-1)."""
    import pathlib

    import repro
    from repro.analysis import scalecheck

    root = pathlib.Path(repro.__file__).parent
    findings = scalecheck.run([str(root)], rules=["no-rw-surface"])
    assert not findings, scalecheck.format_text(findings)


def test_backend_surface_has_no_rw_methods():
    """No per-layout op variants on the protocol or any registered backend."""
    for name in available_backends():
        be = resolve_backend(name)
        rw = [a for a in dir(be) if a.startswith("rw_")]
        assert not rw, (name, rw)
