"""Compile the reduce's Pallas kernels for a described TPU v5e.

Nothing runs. Each case lowers one kernel with ``interpret=False`` at the
widths of a full-width paper-transformer tensor stacked over 8 workers, and
compiles it with the TPU compiler for one chip of a described ``v5e:2x2``.
Mosaic refuses what the chip cannot run — an in-kernel gather, a block shape
the TPU tiling rejects, more VMEM than the scoped limit — so a passing case
means the kernel compiles natively (a ``tpu_custom_call`` in the compiled
HLO). Interpret mode, which every other kernel test uses, checks none of it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_reduce, rowwise

WORKERS = 8
CHUNK = 64
BETA = 0.1
# per-worker tensor shapes of the paper transformer at published widths:
# a feed-forward weight and the embedding
SHAPES = {"ffn": (512, 2048), "embed": (37000, 512)}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache off
    (a compile for a described chip cannot be read back from it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_case(op, shape):
    """(fn, arg shapes) for one kernel over a worker-stacked tensor."""
    rows, cols = shape
    data = (WORKERS, rows, cols)
    per_chunk = (rows, cols // CHUNK)
    f32, i32 = jnp.float32, jnp.int32
    kw = dict(interpret=False)
    if op == "select":
        return lambda x: rowwise.select_trailing(x, CHUNK, 1, **kw), [(data, f32)]
    if op == "select_topm2":
        return lambda x: rowwise.select_trailing(x, CHUNK, 2, **kw), [(data, f32)]
    if op == "gather":
        return (
            lambda x, i: rowwise.gather_trailing(x, i, CHUNK, 1, **kw),
            [(data, f32), (per_chunk, i32)],
        )
    if op == "scatter":
        return (
            lambda v, i: rowwise.scatter_trailing(v, i, CHUNK, cols, **kw),
            [(per_chunk, f32), (per_chunk, i32)],
        )
    if op == "ef_update":
        return (
            lambda m, g, i: rowwise.ef_update_trailing(m, g, i, BETA, CHUNK, 1, **kw),
            [(data, f32), (data, f32), (per_chunk, i32)],
        )
    mode = op.removeprefix("fused_")
    return (
        lambda m, g, lead: fused_reduce.fused_reduce_trailing(
            m, g, lead, BETA, CHUNK, 1, mode, **kw
        ),
        [(data, f32), (data, f32), ((), i32)],
    )


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize(
    "op",
    ["select", "select_topm2", "gather", "scatter", "ef_update",
     "fused_clt_k", "fused_true_topk"],
)
def test_kernel_compiles_for_v5e(one_chip, op, shape):
    fn, args = _kernel_case(op, SHAPES[shape])
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
