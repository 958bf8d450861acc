"""Compile the reduce's Pallas kernels for a described TPU v5e.

Nothing runs. Each case lowers one kernel with ``interpret=False`` at the
widths of a full-width paper-transformer tensor stacked over 8 workers, and
compiles it with the TPU compiler for one chip of a described ``v5e:2x2``.
Mosaic refuses what the chip cannot run — an in-kernel gather it cannot
lower, a block shape the TPU tiling rejects, more VMEM than the scoped
limit — so a passing case means the kernel compiles natively (a
``tpu_custom_call`` in the compiled HLO). Interpret mode, which every other
kernel test uses, checks none of it. Cases cover both tile geometries: the
lane-dense tiles (chunks 64 and 32) and the (n_chunks, chunk) rows a chunk
of 96 keeps. A further test compiles the whole 3-launch reduce and reads the
compiled HLO for copies of the tensor made around the kernels, and one the
worker-sharded step on all four chips for what its worker mean exchanges.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chunk_topk, fused_reduce, rowwise

WORKERS = 8
CHUNK = 64
BETA = 0.1
# per-worker tensor shapes of the paper transformer at published widths:
# a feed-forward weight and the embedding
SHAPES = {"ffn": (512, 2048), "embed": (37000, 512)}


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off (a compile
    for a described chip cannot be read back from it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(op, shape, chunk=CHUNK):
    """(fn, arg shapes) for one kernel over a worker-stacked tensor whose
    trailing dim is padded to a chunk multiple."""
    rows, cols = shape
    cols = -(-cols // chunk) * chunk
    data = (WORKERS, rows, cols)
    per_chunk = (rows, cols // chunk)
    f32, i32 = jnp.float32, jnp.int32
    kw = dict(interpret=False)
    if op == "select":
        return lambda x: rowwise.select_trailing(x, chunk, 1, **kw), [(data, f32)]
    if op == "select_topm2":
        return lambda x: rowwise.select_trailing(x, chunk, 2, **kw), [(data, f32)]
    if op == "gather":
        return (
            lambda x, i: rowwise.gather_trailing(x, i, chunk, 1, **kw),
            [(data, f32), (per_chunk, i32)],
        )
    if op == "scatter":
        return (
            lambda v, i: rowwise.scatter_trailing(v, i, chunk, cols, **kw),
            [(per_chunk, f32), (per_chunk, i32)],
        )
    if op == "ef_update":
        return (
            lambda m, g, i: rowwise.ef_update_trailing(m, g, i, BETA, chunk, 1, **kw),
            [(data, f32), (data, f32), (per_chunk, i32)],
        )
    mode = op.removeprefix("fused_")
    return (
        lambda m, g, lead: fused_reduce.fused_reduce_trailing(
            m, g, lead, BETA, chunk, 1, mode, **kw
        ),
        [(data, f32), (data, f32), ((), i32)],
    )


def _compile(one_chip, fn, args):
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize(
    "op",
    ["select", "select_topm2", "gather", "scatter", "ef_update",
     "fused_clt_k", "fused_true_topk"],
)
def test_kernel_compiles_for_v5e(one_chip, op, shape):
    fn, args = _kernel_case(op, SHAPES[shape])
    assert "tpu_custom_call" in _compile(one_chip, fn, args)


@pytest.mark.parametrize(
    "chunk,dense", [(64, True), (32, True), (96, False)], ids=["c64", "c32", "c96"]
)
@pytest.mark.parametrize(
    "op", ["select", "select_topm2", "gather", "scatter", "ef_update"]
)
def test_kernel_compiles_for_v5e_in_both_geometries(one_chip, op, chunk, dense):
    """The 3-launch kernels at chunks that take the lane-dense tiles (64, 32)
    and one that keeps the (n_chunks, chunk) rows (96 does not divide 128)."""
    rows, cols = SHAPES["ffn"]
    cols = -(-cols // chunk) * chunk
    assert chunk_topk.lane_dense(chunk, cols, WORKERS * rows * cols, jnp.float32) is dense
    fn, args = _kernel_case(op, SHAPES["ffn"], chunk)
    assert "tpu_custom_call" in _compile(one_chip, fn, args)


# ---------------------------------------------------------------------------
# the compiled 3-launch reduce: no copy of the tensor around the kernels
# ---------------------------------------------------------------------------

# (name, shape, dtype, opcode, operand names, op_name) of each HLO instruction
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")


def _instructions(hlo: str):
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name, shape, opcode, rest = m.groups()
            op_name = re.search(r'op_name="([^"]*)"', line)
            operands = re.findall(r"%([\w.\-]+)", rest.split("),")[0])
            out.append((name, shape, opcode, operands, op_name.group(1) if op_name else ""))
    return out


def _dims(shape: str):
    m = re.match(r"\(?\w+\[([\d,]*)\]", shape)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else ()


def test_reduce_compiles_without_tensor_copies_for_v5e(one_chip):
    """scalecom_reduce over the paper embedding and a 6-layer FFN stack: flat
    layout, one worker, CLT-k chunk 64, the pallas backend compiled for the
    chip. Under the select, ef_update and scatter scopes no pad or slice
    touches a buffer as large as a tensor, and every reshape or copy that
    does has the parameter's own shape on one side: it folds the gradient
    into the flat buffer or unfolds ĝ to the parameter, which the flat
    layout does whatever the kernels' tiles. The kernels read and write the
    flat buffers through bitcast views."""
    from repro.backends.pallas_backend import PallasBackend
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig, scalecom_reduce
    from repro.core.state import init_state

    shapes = {"embed": (37000, 512), "ffn": (6, 512, 2048)}
    cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=CHUNK), min_size=1024,
        layout="flat", backend=PallasBackend(interpret=False), fused=False,
    )
    params = {k: jnp.zeros(s) for k, s in shapes.items()}
    state = jax.eval_shape(lambda: init_state(params, 1, min_size=1024, layout="flat"))

    def reduce(g, s):
        with jax.named_scope("reduce"):
            return scalecom_reduce(g, s, cfg)[:2]

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    grads = {k: spec(jax.ShapeDtypeStruct((1,) + s, jnp.float32)) for k, s in shapes.items()}
    hlo = jax.jit(reduce).lower(grads, jax.tree_util.tree_map(spec, state)).compile().as_text()

    instrs = _instructions(hlo)
    shape_of = {name: shape for name, shape, *_ in instrs}
    smallest = min(math.prod(s) for s in shapes.values())
    param_shapes = {s for s in shapes.values()} | {(1,) + s for s in shapes.values()}
    glue = []
    for name, shape, opcode, operands, op_name in instrs:
        if not re.search(r"/reduce/(select|ef_update|scatter)/", op_name):
            continue
        sides = [_dims(shape)] + [_dims(shape_of.get(o, "")) for o in operands]
        if max(math.prod(d) for d in sides) < smallest:
            continue
        if opcode in ("pad", "slice", "dynamic-slice"):
            glue.append((opcode, name, shape))
        elif opcode in ("reshape", "copy") and not param_shapes & set(sides):
            glue.append((opcode, name, shape))
    assert hlo.count("tpu_custom_call") >= 6  # 3 launches a tensor
    assert not glue, glue


# ---------------------------------------------------------------------------
# the blockwise causal attention kernel at the long cells' attention shapes
# ---------------------------------------------------------------------------

# (B, S, H, KV, q/k head, v head): StarCoder2-3B's grouped-query heads at
# 8 x 4096, Moonlight's latent attention (128 + 64 rotary, v 128) at 1 x 8192
ATTENTION = {"starcoder2": (8, 4096, 24, 2, 128, 128), "moonlight": (1, 8192, 16, 16, 192, 128)}


@pytest.mark.parametrize("cell", sorted(ATTENTION))
def test_flash_attention_compiles_for_v5e(one_chip, cell):
    """Forward and backward (flash_fwd, flash_dq, flash_dkv) under the
    training step's vmap over one learner: Mosaic accepts the block shapes,
    the 192-wide heads and the VMEM the tiles take."""
    from repro.kernels import flash_attention

    B, S, H, KV, hd, vd = ATTENTION[cell]

    def fwd_bwd(q, k, v, do):
        attn = jax.vmap(lambda q, k, v: flash_attention.causal_attention(q, k, v, interpret=False))
        o, back = jax.vjp(attn, q, k, v)
        return (o,) + back(do)

    f32 = jnp.float32
    hlo = _compile(one_chip, fwd_bwd, [((1, B, S, H, hd), f32), ((1, B, S, KV, hd), f32),
                                       ((1, B, S, KV, vd), f32), ((1, B, S, H, vd), f32)])
    calls = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)", line).group(1) for line in calls]
    assert len(names) == 3, names
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert any(kernel in name for name in names), names


# ---------------------------------------------------------------------------
# one learner a chip: what the worker mean exchanges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("buckets", [None, True], ids=["single_shot", "bucketed"])
def test_worker_sharded_step_all_reduces_k_values_for_v5e(topo, buckets):
    """The compressed step of four learners, one on each chip of the 2x2
    (paper transformer's shape at smoke widths, jnp kernels, CLT-k chunk 64),
    in one launch and in the bucketed one: the TPU compiler all-reduces one
    value a chunk under the worker mean, as many bytes as the leader's index
    broadcast, and never the (n_chunks, chunk) tile the values are gathered
    from."""
    import dataclasses

    from repro.compat.jax_compat import NamedSharding, P, make_mesh
    from repro.configs import registry
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig
    from repro.core.state import ScaleComState
    from repro.models import build_model
    from repro.optim import make_optimizer, schedule
    from repro.training import init_train_state
    from repro.training.train_step import build_train_step

    n, batch, seq = 4, 2, 32
    cfg = registry.smoke("paper-transformer-base")
    model = build_model(cfg, compute_dtype="float32", loss_chunk=16)
    sc = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=CHUNK), beta=0.1,
                        min_size=1024, backend="jnp")
    opt = make_optimizer("sgdm")
    shapes = jax.eval_shape(
        lambda: init_train_state(model, opt, sc, jax.random.PRNGKey(0), n_workers=n)[0]
    )
    mesh = make_mesh((n,), ("data",), devices=topo.devices[:n])
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    placed = dataclasses.replace(
        jax.tree.map(lambda _: whole, shapes),
        sc_state=ScaleComState(jax.tree.map(lambda _: split, shapes.sc_state.residues), whole),
    )
    step = build_train_step(model, opt, schedule.constant(0.05), sc, n_workers=n,
                            worker_axis="data",
                            worker_shardings=jax.tree.map(lambda _: split, shapes.params),
                            buckets=buckets)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), shapes, placed
    )
    tokens = jax.ShapeDtypeStruct((n, batch, seq), jnp.int32, sharding=split)
    data = {"tokens": tokens, "labels": tokens,
            "mask": jax.ShapeDtypeStruct((n, batch, seq), jnp.float32, sharding=split)}
    hlo = jax.jit(step).lower(state, data).compile().as_text()

    # one shape an exchanged operand: a lone all-reduce's own, or each
    # element a combined all-reduce's tuple hands out
    combined = set()
    exchanged = {"worker_mean": [], "select": []}
    for name, shape, opcode, operands, op_name in _instructions(hlo):
        stage = re.search(r"/reduce/(worker_mean|select)/", op_name)
        if opcode.removesuffix("-start") == "all-reduce":
            if shape.startswith("("):
                combined.add(name)
            elif stage:
                exchanged[stage.group(1)].append(_dims(shape))
        elif stage and opcode == "get-tuple-element" and operands[0] in combined:
            exchanged[stage.group(1)].append(_dims(shape))
    values, indices = exchanged["worker_mean"], exchanged["select"]
    assert values and all(len(d) == 1 for d in values), values
    assert sorted(values) == sorted(indices)
