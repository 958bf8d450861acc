"""Pallas TPU kernels: blockwise causal self-attention for training.

Three launches, each over the lower triangle of (query block, key block)
pairs and nothing else:

  ``flash_fwd``  the output and each query row's log-sum-exp, by the online
                 softmax over the key blocks at or below the row's block;
  ``flash_dq``   dq from the saved log-sum-exp, query block resident;
  ``flash_dkv``  dk and dv, key block resident, over the query blocks at or
                 above it (and, for grouped heads, every query head of the
                 group).

A grid step is one block pair, read from a table the kernel prefetches into
SMEM, so a key block wholly above the diagonal is never visited: not loaded,
not computed and masked. Only the pairs on the diagonal mask. Score and
probability tiles live in VMEM and never reach HBM.

Precision is the same work as XLA's: the softmax statistics and every
accumulation stay in float32, o, dq, dk and dv are written in float32, and
the operands of each matmul are rounded to bfloat16 exactly where XLA's
default matmul precision rounds them, and kept in float32 when the default
precision asks for more (``mxu_dtype``). q, k, v and dO feed nothing but
matmuls, so they are rounded once, outside the kernels, which then read half
the bytes; the probabilities and dS are rounded in VMEM. The result is the
same bits as rounding every operand in VMEM.

Layout: the kernels read head-major (B, H, S, d) operands, made as they
are rounded; grouped-query heads read their key and value head through the
index maps (head h reads h // G), so K and V are never repeated to H heads
in HBM. Results whose width is whole lanes (o, dv, and dq and dk at 128-wide
heads) are written straight into the token-major (B, S, H, d) layout the
model uses; others are transposed after. The log-sum-exp and the backward's
row sums of dO·o travel as (B, H, 1, S) rows.

``models.attention.attention_train`` dispatches to ``projected_attention``,
whose backward makes q, k and v again from the projections' inputs; the CPU
tests run the kernels in interpret mode, and ``tests/test_tpu_compile.py``
compiles them for a described v5e at the cells' attention shapes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["BLOCK", "causal_attention", "fits", "mxu_dtype", "projected_attention"]

# query and key block: rows of a score tile and its columns
BLOCK = 512
NUM_LANES = 128
NEG_INF = -1e30
# room above the 16 MiB default scoped limit for the double-buffered tiles
# and the (block, block) float32 temporaries, at 192-wide heads
VMEM_LIMIT_BYTES = 64 * 2**20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def fits(seq: int, block: int = BLOCK) -> bool:
    """Whether the kernels take a sequence of ``seq``: whole blocks, more
    than one (a single block is the XLA path's one chunk)."""
    return seq % block == 0 and seq > block


def mxu_dtype():
    """The operand dtype of the kernels' matmuls: bfloat16 where XLA's
    default matmul precision rounds float32 operands to it (TPU default),
    float32 where the current default precision asks for more."""
    prec = jax.config.jax_default_matmul_precision
    return jnp.bfloat16 if prec in (None, "default", "bfloat16", "fastest") else jnp.float32


def _dot(a, b, dims, dtype):
    precision = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           precision=precision, preferred_element_type=jnp.float32)


def _lanes(x, width):
    """(rows, 128) lane-broadcast column -> (rows, width)."""
    return jnp.tile(x, (1, pl.cdiv(width, NUM_LANES)))[:, :width]


def _row_pairs(n: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(query block, key block) of each lower-triangle pair, row by row."""
    qi, kj = zip(*[(i, j) for i in range(n) for j in range(i + 1)])
    return jnp.asarray(qi, jnp.int32), jnp.asarray(kj, jnp.int32)


def _column_triples(n: int, groups: int):
    """(key block, group head, query block) of each lower-triangle pair and
    head of the group, key block by key block."""
    rows = [(j, g, i) for j in range(n) for g in range(groups) for i in range(j, n)]
    return tuple(jnp.asarray(a, jnp.int32) for a in zip(*rows))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale, block, dtype):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def update(masked):
        s = _dot(q_ref[...], k_ref[...], _NT, dtype) * scale
        if masked:  # the diagonal block: i == j, so positions share an offset
            row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row, s, NEG_INF)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
        p = jnp.exp(s - _lanes(m_next, block))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1)[:, None]
        m_sc[...] = m_next
        vd = acc_sc.shape[-1]
        acc_sc[...] = _lanes(alpha, vd) * acc_sc[...] + _dot(p, v_ref[...], _NN, dtype)

    pl.when(j < i)(lambda: update(False))

    @pl.when(j == i)
    def _last():
        update(True)
        l = l_sc[...]
        o_ref[...] = acc_sc[...] / _lanes(l, acc_sc.shape[-1])
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1, :]


def _dq_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, dq_sc, *, scale, dtype):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def update(masked):
        s = _dot(q_ref[...], k_ref[...], _NT, dtype) * scale
        if masked:
            row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row, s, NEG_INF)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = _dot(do_ref[...], v_ref[...], _NT, dtype)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        dq_sc[...] += _dot(ds, k_ref[...], _NN, dtype)

    pl.when(j < i)(lambda: update(False))

    @pl.when(j == i)
    def _last():
        update(True)
        dq_ref[...] = dq_sc[...] * scale


def _dkv_kernel(kj_ref, gh_ref, qi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, n_blocks, groups,
                dtype):
    t = pl.program_id(2)
    j, g, i = kj_ref[t], gh_ref[t], qi_ref[t]

    @pl.when((g == 0) & (i == j))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def update(masked):
        # transposed scores (key rows, query columns): the query rows'
        # statistics broadcast along sublanes as they are stored
        st = _dot(k_ref[...], q_ref[...], _NT, dtype) * scale
        if masked:
            krow = lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qcol = lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(krow <= qcol, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[...])
        dv_sc[...] += _dot(pt, do_ref[...], _NN, dtype)
        dpt = _dot(v_ref[...], do_ref[...], _NT, dtype)
        dst = pt * (dpt - di_ref[...])
        dk_sc[...] += _dot(dst, q_ref[...], _NN, dtype)

    pl.when(i > j)(lambda: update(False))
    pl.when(i == j)(lambda: update(True))

    @pl.when((g == groups - 1) & (i == n_blocks - 1))
    def _write():
        dk_ref[...] = dk_sc[...] * scale
        dv_ref[...] = dv_sc[...]


# ---------------------------------------------------------------------------
# launchers (head-major arrays)
# ---------------------------------------------------------------------------


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )


def _heads(x):
    """(B, S, H, d) <-> (B, H, S, d)."""
    return jnp.swapaxes(x, 1, 2)


def _output(B, H, S, d, block, pick):
    """A float32 (B, S, H, d) result written in (block, d) tiles, ``pick``
    mapping a grid step to its (batch, head, row block): through a
    token-major (B, S, H * d) view where d is whole lanes, so no transpose
    follows, else head-major and transposed after. Returns (shape, block
    spec, the map to (B, S, H, d))."""
    if d % NUM_LANES == 0:
        def index(*a):
            b, h, i = pick(*a)
            return b, i, h

        return (jax.ShapeDtypeStruct((B, S, H * d), jnp.float32),
                pl.BlockSpec((None, block, d), index), lambda x: x.reshape(B, S, H, d))
    return (jax.ShapeDtypeStruct((B, H, S, d), jnp.float32),
            pl.BlockSpec((None, None, block, d), lambda *a: (*pick(*a), 0)), _heads)


def _forward(q, k, v, block, interpret, dtype):
    B, H, S, hd = q.shape
    KV, vd = k.shape[1], v.shape[-1]
    G = H // KV
    qi, kj = _row_pairs(S // block)
    blk = lambda d: (None, None, block, d)  # noqa: E731
    kernel = functools.partial(_fwd_kernel, scale=hd**-0.5, block=block, dtype=dtype)
    o_shape, o_spec, o_tokens = _output(B, H, S, vd, block,
                                        lambda b, h, t, qi, kj: (b, h, qi[t]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, len(qi)),
        in_specs=[
            pl.BlockSpec(blk(hd), lambda b, h, t, qi, kj: (b, h, qi[t], 0)),
            pl.BlockSpec(blk(hd), lambda b, h, t, qi, kj: (b, h // G, kj[t], 0)),
            pl.BlockSpec(blk(vd), lambda b, h, t, qi, kj: (b, h // G, kj[t], 0)),
        ],
        out_specs=[
            o_spec,
            pl.BlockSpec((None, None, 1, block), lambda b, h, t, qi, kj: (b, h, 0, qi[t])),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, NUM_LANES), jnp.float32),
            pltpu.VMEM((block, NUM_LANES), jnp.float32),
            pltpu.VMEM((block, vd), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[o_shape, jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_fwd",
    )(qi, kj, q, k, v)
    return o_tokens(o), lse


def _backward_dq(q, k, v, do, lse, di, block, interpret, dtype):
    B, H, S, hd = q.shape
    KV, vd = k.shape[1], v.shape[-1]
    G = H // KV
    qi, kj = _row_pairs(S // block)
    blk = lambda d: (None, None, block, d)  # noqa: E731
    row = pl.BlockSpec((None, None, 1, block), lambda b, h, t, qi, kj: (b, h, 0, qi[t]))
    dq_shape, dq_spec, dq_tokens = _output(B, H, S, hd, block,
                                           lambda b, h, t, qi, kj: (b, h, qi[t]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, len(qi)),
        in_specs=[
            pl.BlockSpec(blk(hd), lambda b, h, t, qi, kj: (b, h, qi[t], 0)),
            pl.BlockSpec(blk(hd), lambda b, h, t, qi, kj: (b, h // G, kj[t], 0)),
            pl.BlockSpec(blk(vd), lambda b, h, t, qi, kj: (b, h // G, kj[t], 0)),
            pl.BlockSpec(blk(vd), lambda b, h, t, qi, kj: (b, h, qi[t], 0)),
            row,
            row,
        ],
        out_specs=dq_spec,
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32)],
    )
    return dq_tokens(pl.pallas_call(
        functools.partial(_dq_kernel, scale=hd**-0.5, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=dq_shape,
        compiler_params=_params(),
        interpret=interpret,
        name="flash_dq",
    )(qi, kj, q, k, v, do, lse, di))


def _backward_dkv(q, k, v, do, lse, di, block, interpret, dtype):
    B, H, S, hd = q.shape
    KV, vd = k.shape[1], v.shape[-1]
    G = H // KV
    n = S // block
    kj, gh, qi = _column_triples(n, G)
    blk = lambda d: (None, None, block, d)  # noqa: E731
    q_map = lambda b, h, t, kj, gh, qi: (b, h * G + gh[t], qi[t], 0)  # noqa: E731
    kv_map = lambda b, h, t, kj, gh, qi: (b, h, kj[t], 0)  # noqa: E731
    row = pl.BlockSpec((None, None, 1, block),
                       lambda b, h, t, kj, gh, qi: (b, h * G + gh[t], 0, qi[t]))
    pick = lambda b, h, t, kj, gh, qi: (b, h, kj[t])  # noqa: E731
    dk_shape, dk_spec, dk_tokens = _output(B, KV, S, hd, block, pick)
    dv_shape, dv_spec, dv_tokens = _output(B, KV, S, vd, block, pick)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, len(kj)),
        in_specs=[
            pl.BlockSpec(blk(hd), q_map),
            pl.BlockSpec(blk(hd), kv_map),
            pl.BlockSpec(blk(vd), kv_map),
            pl.BlockSpec(blk(vd), q_map),
            row,
            row,
        ],
        out_specs=[dk_spec, dv_spec],
        scratch_shapes=[
            pltpu.VMEM((block, hd), jnp.float32),
            pltpu.VMEM((block, vd), jnp.float32),
        ],
    )
    kernel = functools.partial(_dkv_kernel, scale=hd**-0.5, n_blocks=n, groups=G,
                               dtype=dtype)
    dk, dv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[dk_shape, dv_shape],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_dkv",
    )(kj, gh, qi, q, k, v, do, lse, di)
    return dk_tokens(dk), dv_tokens(dv)


# The VJP's inputs are what q, k and v are made from (``project``'s
# arguments, which a training step holds anyway), and its residuals those,
# o and the log-sum-exp: the backward makes q, k and v again when it reaches
# the attention, instead of holding them through the rest of a layer's
# backward, behind a barrier with dO, so that XLA cannot merge them with the
# forward's. q, k, v and dO enter the kernels only as matmul operands, so
# each is rounded to the operand dtype once, as it is made head-major; o,
# the log-sum-exp and di = rowsum(dO * o) stay float32.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2, 3, 4))
def _attention(project, args, block, interpret, dtype):
    return _attention_fwd(project, args, block, interpret, dtype)[0]


def _operands(q, k, v, dtype):
    return tuple(_heads(x.astype(jnp.float32)).astype(dtype) for x in (q, k, v))


def _attention_fwd(project, args, block, interpret, dtype):
    o, lse = _forward(*_operands(*project(*args), dtype), block, interpret, dtype)
    return o, (args, o, lse)


def _attention_bwd(project, block, interpret, dtype, res, do):
    args, o, lse = res
    args, do = lax.optimization_barrier((args, do))
    (q, k, v), project_vjp = jax.vjp(project, *args)
    di = _heads(jnp.sum(o * do, axis=-1, keepdims=True)).swapaxes(2, 3)
    ops = (*_operands(q, k, v, dtype), _heads(do).astype(dtype), lse, di, block,
           interpret, dtype)
    dk, dv = _backward_dkv(*ops)
    dq = _backward_dq(*ops)
    return (project_vjp((dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))),)


_attention.defvjp(_attention_fwd, _attention_bwd)


def projected_attention(project, args, *, block: int = BLOCK, interpret: bool = None):
    """``causal_attention(*project(*args))``, float32, whose backward makes
    q, k and v again from ``args`` rather than holding them: ``project``
    maps ``args`` to q (B, S, H, hd), k (B, S, KV, hd) and v (B, S, KV, vd)
    over positions 0..S-1."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _attention(project, tuple(args), block, interpret, mxu_dtype())


def causal_attention(q, k, v, *, block: int = BLOCK, interpret: bool = None):
    """Causal self-attention over positions 0..S-1.

    q: (B, S, H, hd), k: (B, S, KV, hd), v: (B, S, KV, vd), with H a
    multiple of KV; ``fits(S, block)``. Returns (B, S, H, vd) in q's dtype, the
    same as ``attention.attention_core(q, k, v, arange(S), arange(S),
    causal=True, window=None)`` at the current default matmul precision.
    ``interpret`` defaults to interpret mode off a TPU.
    """
    o = projected_attention(lambda *qkv: qkv, (q, k, v), block=block, interpret=interpret)
    return o.astype(q.dtype)
