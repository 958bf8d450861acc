"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

Kernels run in interpret mode on CPU (the TPU is the target, not the runtime);
the kernel *math* is identical either way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

# 102400: lane-dense for fp32 chunks 16-64, over several blocks with a
# ragged last one; 5000 and 65553 keep the (n_chunks, chunk) rows
SIZES = [1024, 4096, 5000, 65536 + 17, 102400]
CHUNKS = [8, 16, 32, 64, 128]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize(
    "chunk,width,size,dtype,dense",
    [
        (64, 512, 37000 * 512, jnp.float32, True),
        (16, 128, 1024, jnp.float32, True),
        (32, 2048, 6 * 512 * 2048, jnp.float32, True),
        (128, 512, 4096, jnp.float32, False),  # fills its rows already
        (8, 512, 4096, jnp.float32, False),  # under MIN_DENSE_CHUNK
        (96, 2112, 2112 * 512, jnp.float32, False),  # does not divide 128
        (64, 192, 192 * 64, jnp.float32, False),  # width not a multiple of 128
        (64, 384, 384 * 5, jnp.float32, False),  # no whole (8, 128) tiles
        (64, 512, 4096, jnp.bfloat16, False),
    ],
)
def test_lane_dense_rule(chunk, width, size, dtype, dense):
    """The tile geometry follows from static shapes alone."""
    from repro.kernels.chunk_topk import lane_dense

    assert lane_dense(chunk, width, size, dtype) is dense


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_chunk_select_matches_ref(size, chunk, dtype):
    x = jax.random.normal(jax.random.PRNGKey(size + chunk), (size,)).astype(dtype)
    i1, v1 = ops.chunk_select(x, chunk)
    i2, v2 = ref.chunk_argmax_ref(x, chunk)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(
        np.asarray(v1, np.float32), np.asarray(v2, np.float32), rtol=1e-6
    )


@pytest.mark.parametrize("size", [4096, 5000])
@pytest.mark.parametrize("chunk", [64])
def test_chunk_gather_matches_ref(size, chunk):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (size,))
    n_chunks = -(-size // chunk)
    idx = jax.random.randint(jax.random.PRNGKey(1), (n_chunks,), 0, chunk)
    v1 = ops.chunk_gather(x, idx, chunk)
    v2 = ref.chunk_gather_ref(x, idx, chunk)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("beta", [0.1, 1.0])
def test_ef_update_matches_ref(size, chunk, beta):
    k1, k2 = jax.random.split(jax.random.PRNGKey(size))
    m = jax.random.normal(k1, (size,))
    g = jax.random.normal(k2, (size,))
    idx, _ = ops.chunk_select(m + g, chunk)
    m1, v1 = ops.ef_update(m, g, idx, beta, chunk)
    m2, v2 = ref.ef_update_ref(m, g, idx, beta, chunk)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5, atol=1e-7)


@settings(max_examples=20, deadline=None)
@given(
    size=st.integers(16, 3000),
    chunk=st.sampled_from([16, 64]),
    seed=st.integers(0, 10_000),
)
def test_kernel_property_sweep(size, chunk, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (size,))
    i1, v1 = ops.chunk_select(x, chunk)
    i2, v2 = ref.chunk_argmax_ref(x, chunk)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)


_TIE_CASES = [(1, 64), (2, 64)] + [
    (topm, chunk) for chunk in (8, 16, 32, 128) for topm in (1, 2)
]


@pytest.mark.parametrize(
    "topm,chunk",
    _TIE_CASES,
    ids=[str(t) if c == 64 else f"{t}-c{c}" for t, c in _TIE_CASES],
)
def test_select_breaks_ties_to_the_lowest_lane(topm, chunk):
    """Exact magnitude ties pick the lowest lane, as jnp.argmax and
    lax.top_k do (full-width random gradients hit a few per step). Small
    integers make ties in nearly every chunk, signs included; NaN lanes rank
    first, the first of them winning; all-zero chunks pick lane 0. The
    (3, 40960) stack is lane-dense for chunks 16-64 over several blocks with
    a ragged last one, and keeps the (n_chunks, chunk) rows at 8 and 128."""
    from repro.backends import resolve_backend

    x = jax.random.randint(jax.random.PRNGKey(3), (3, 40960), -3, 4).astype(jnp.float32)
    x = x.at[0, jnp.array([5, 9, 700, 40959])].set(jnp.nan)
    x = x.at[1, 64 * 3 : 64 * 6].set(0.0).at[2, -chunk:].set(-0.0)
    i1, v1 = resolve_backend("pallas").select(x, chunk, topm)
    i2, v2 = resolve_backend("jnp").select(x, chunk, topm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_kernel_grid_covers_multiple_blocks():
    """Sizes spanning several BLOCK_CHUNKS grid steps (the tiling path)."""
    from repro.kernels.chunk_topk import BLOCK_CHUNKS

    chunk = 16
    size = chunk * BLOCK_CHUNKS * 3 + 5
    x = jax.random.normal(jax.random.PRNGKey(7), (size,))
    i1, v1 = ops.chunk_select(x, chunk)
    i2, v2 = ref.chunk_argmax_ref(x, chunk)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)


# ---------------------------------------------------------------------------
# launch-count tripwire: the fused reduce is ONE pallas_call, the composed
# chain is three — counted on the jaxpr (repro.backends.introspect), which a
# cached jit executable cannot fool
# ---------------------------------------------------------------------------


def test_fused_reduce_is_one_launch():
    from repro.backends import resolve_backend
    from repro.backends.base import KernelBackend
    from repro.backends.introspect import count_pallas_launches

    pal = resolve_backend("pallas")
    chunk, G = 16, 4
    m = jax.random.normal(jax.random.PRNGKey(0), (G, 200))
    g = jax.random.normal(jax.random.PRNGKey(1), (G, 200))
    leader = jnp.zeros((), jnp.int32)

    def fused(mm, gg, ll):
        return pal.fused_reduce(mm, gg, 0.25, chunk, 1, "clt_k", ll)

    def composed(mm, gg, ll):
        return KernelBackend.fused_reduce(pal, mm, gg, 0.25, chunk, 1, "clt_k", ll)

    assert count_pallas_launches(fused, m, g, leader) == 1
    assert count_pallas_launches(composed, m, g, leader) == 3


def test_whole_reduce_launch_count_with_fusion():
    """Through scalecom_reduce: fused=True pays 1 inner-loop launch per
    compressed tensor, fused=False pays 3 — the end-to-end tripwire for a
    regression that silently re-splits the fused path."""
    from repro.backends.introspect import count_pallas_launches
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig, scalecom_reduce
    from repro.core.state import init_state

    G = 4
    params = {"w": jnp.zeros((8, 64))}
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (G, 8, 64))}

    def launches(fused):
        cfg = ScaleComConfig(
            compressor=CompressorConfig("clt_k", chunk=16),
            min_size=1, layout="rowwise", backend="pallas", fused=fused,
        )
        state = init_state(params, G, min_size=1, layout="rowwise")
        return count_pallas_launches(
            lambda gg, ss: scalecom_reduce(gg, ss, cfg)[0], g, state
        )

    assert launches(True) == 1
    assert launches(False) == 3
