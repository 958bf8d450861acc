"""ScaleCom optimizer-adjacent state: per-worker error-feedback residues.

The residue ("local memory") is the only persistent state the algorithm adds.
For a model with P parameters and n data-parallel workers it is n·P elements —
the binding memory cost at scale (DESIGN.md §5). This module provides:

  * ``init_state``      — zero residues per tensor
  * residue codecs      — fp32 / bf16 / fp8(e4m3, scaled) / fp8_ec storage
                          (low-precision residues are a beyond-paper memory
                          optimization; the residue tolerates quantization
                          because it is itself an error accumulator —
                          quantization error is re-fed next step)

Low-precision encodes use STOCHASTIC rounding, keyed from ``ScaleComState.t``
(via ``codec_key``) so the reduce stays pure and jittable. Round-to-nearest is
biased: the EF memory is a long-lived accumulator, and once |m| outgrows the
per-step increment by the mantissa width, nearest rounding silently swallows
updates every step (the classic EF-precision failure; cf. DGC's sensitivity to
memory precision). Stochastic rounding is the minimum-variance unbiased
quantizer onto the grid, so codec error stays a zero-mean perturbation the
error feedback itself absorbs. ``fp8_ec`` additionally carries a bf16
compensation term per element (3B total) for near-fp32 trajectories at 25%
memory savings. ``codec_roundtrip_error`` is the standing diagnostic
(surfaced by analysis/report.py) verifying encode∘decode stays a contraction.

Residue storage layout follows ScaleComConfig.layout:

  flat     — (n_workers, size) per tensor (paper-faithful flat buffer). fp8
             uses one fp32 scale per 512 elements.
  rowwise  — (n_workers, R, C) preserving the tensor's last dim (C), so the
             residue shares the parameter's sharding and the compression step
             never reshards (see core.chunked row-wise ops). fp8 uses one
             fp32 scale per row.
"""

from __future__ import annotations

import dataclasses
import math
import os
import zlib
from typing import Any, Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import jax_compat

Array = jnp.ndarray
Pytree = Any
Shape = Tuple[int, ...]

__all__ = [
    "ResidueCodec",
    "CODECS",
    "ScaleComState",
    "codec_key",
    "codec_roundtrip_error",
    "codec_signature",
    "init_state",
    "remap_state",
    "residue_bytes",
    "residue_signature",
    "resolve_layout",
    "storage_shape",
    "stochastic_round",
]

_LAYOUT_ENV = "SCALECOM_LAYOUT"
_LAYOUTS = ("flat", "rowwise")


def resolve_layout(spec: Union[str, None] = "auto") -> str:
    """Resolve a chunk-layout spec ("auto" | "flat" | "rowwise").

    "auto" (and None) read the SCALECOM_LAYOUT env var at call time —
    compat-layer style, mirroring resolve_backend's SCALECOM_BACKEND probe
    (that is the CI leg that runs the whole tier-1 suite through the
    layout-preserving rowwise pipeline) — and fall back to "flat", the
    paper-faithful default. An explicit layout always wins. Must resolve
    identically at init_state and scalecom_reduce time, which is why both
    route through here.
    """
    if spec in (None, "auto"):
        env = os.environ.get(_LAYOUT_ENV, "").strip()
        spec = env or "flat"
    if spec not in _LAYOUTS:
        raise ValueError(
            f"unknown chunk layout {spec!r}; expected one of {_LAYOUTS} "
            f'(or "auto" to probe ${_LAYOUT_ENV})'
        )
    return spec

_FP8_MAX = 448.0  # e4m3 finite max
_FP8_CHUNK = 512  # flat-layout scale granularity

# Fixed PRNG salt for stochastic-rounding dither (same role as the random_k
# salt in core.scalecom); codec_key folds in the tensor path then the step.
_SR_SALT = 4


def codec_key(path: str, t: Array):
    """Per-(tensor, step) PRNG key for stochastic-rounding encodes.

    ``t`` may be a traced int32 scalar (ScaleComState.t), so this composes
    with jit; ``path`` is static and hashed at trace time.
    """
    h = zlib.crc32(path.encode()) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(_SR_SALT), h), t)


def stochastic_round(x: Array, key, dtype) -> Array:
    """Unbiased stochastic rounding of fp32 ``x`` onto the bf16 grid.

    Adds a uniform 16-bit dither below the bf16 mantissa boundary and
    truncates: rounds to a neighbouring representable with probability equal
    to the fractional position between them (exact SR — bf16 is fp32's top
    16 bits). Non-finite inputs and dither overflow fall back to nearest.
    """
    f = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(f, jnp.uint32)
    dither = jax.random.bits(key, x.shape, jnp.uint32) >> 16
    out = jax.lax.bitcast_convert_type(
        (bits + dither) & jnp.uint32(0xFFFF0000), jnp.float32
    )
    out = jnp.where(jnp.isfinite(f) & jnp.isfinite(out), out, f)
    return out.astype(dtype)


def storage_shape(param_shape: Shape, layout: str) -> Shape:
    """Residue storage shape (without the worker axis) for one tensor.

    rowwise keeps the FULL parameter shape: the residue then inherits the
    parameter's exact sharding (expert/heads/mlp dims included) and every
    compression op (last-dim chunking) is sharding-preserving. Collapsing to
    (R, C) was measurably worse for expert-sharded tensors — the merged
    leading dim can't carry the expert-axis sharding (see EXPERIMENTS §Perf).
    """
    layout = resolve_layout(layout)
    size = int(np.prod(param_shape)) if len(param_shape) else 1
    if layout == "flat":
        return (size,)
    if len(param_shape) == 0:
        return (1,)
    return tuple(param_shape)


class ResidueCodec:
    """Encode/decode an (n, *storage) fp32 residue.

    ``encode`` takes an optional PRNG ``key`` (from ``codec_key``); lossy
    codecs use it for stochastic rounding and fall back to nearest rounding
    when it is None (e.g. offline tools re-encoding a checkpoint).
    """

    name: str = "fp32"

    def init(self, n: int, shape: Shape) -> Pytree:
        return {"q": jnp.zeros((n,) + shape, jnp.float32)}

    def decode(self, enc: Pytree, shape: Shape) -> Array:
        del shape
        return enc["q"]

    def encode(self, m: Array, shape: Shape, *, key=None) -> Pytree:
        del shape, key
        return {"q": m}

    def nbytes(self, n: int, shape: Shape) -> int:
        return n * int(np.prod(shape)) * 4


class _Bf16Codec(ResidueCodec):
    name = "bf16"

    def init(self, n, shape):
        return {"q": jnp.zeros((n,) + shape, jnp.bfloat16)}

    def decode(self, enc, shape):
        del shape
        return enc["q"].astype(jnp.float32)

    def encode(self, m, shape, *, key=None):
        del shape
        if key is None:
            return {"q": m.astype(jnp.bfloat16)}
        return {"q": stochastic_round(m, key, jnp.bfloat16)}

    def nbytes(self, n, shape):
        return n * int(np.prod(shape)) * 2


class _Fp8Codec(ResidueCodec):
    """e4m3 residue.

    flat (n, size): one fp32 scale per _FP8_CHUNK elements (size padded).
    rowwise (n, R, C): one fp32 scale per row — stays in the param layout.
    """

    name = "fp8"

    @staticmethod
    def _padded(size: int) -> int:
        return -(-size // _FP8_CHUNK) * _FP8_CHUNK

    def init(self, n, shape):
        qdt = jax_compat.float8_e4m3_dtype()
        if len(shape) == 1:
            p = self._padded(shape[0])
            return {
                "q": jnp.zeros((n, p), qdt),
                "scale": jnp.zeros((n, p // _FP8_CHUNK), jnp.float32),
            }
        return {
            "q": jnp.zeros((n,) + shape, qdt),
            "scale": jnp.zeros((n,) + shape[:-1], jnp.float32),
        }

    def decode(self, enc, shape):
        q, scale = enc["q"], enc["scale"]
        if len(shape) == 1:
            n, p = q.shape
            x = q.astype(jnp.float32).reshape(n, -1, _FP8_CHUNK)
            x = x * scale[..., None]
            return x.reshape(n, p)[:, : shape[0]]
        return q.astype(jnp.float32) * scale[..., None]

    def encode(self, m, shape, *, key=None):
        del key  # e4m3 stays nearest-rounded; fp8_ec carries the correction
        if len(shape) == 1:
            n = m.shape[0]
            p = self._padded(shape[0])
            mp = jnp.pad(m, ((0, 0), (0, p - shape[0]))).reshape(n, -1, _FP8_CHUNK)
            amax = jnp.max(jnp.abs(mp), axis=-1)
            scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
            q = jax_compat.cast_to_e4m3(mp / scale[..., None])
            return {"q": q.reshape(n, p), "scale": scale}
        amax = jnp.max(jnp.abs(m), axis=-1)
        scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
        q = jax_compat.cast_to_e4m3(m / scale[..., None])
        return {"q": q, "scale": scale}

    def nbytes(self, n, shape):
        size = int(np.prod(shape))
        q_item = jnp.dtype(jax_compat.float8_e4m3_dtype()).itemsize
        if len(shape) == 1:
            p = self._padded(size)
            return n * (q_item * p + 4 * p // _FP8_CHUNK)
        return n * (q_item * size + 4 * size // shape[-1])


class _Fp8EcCodec(_Fp8Codec):
    """Error-compensated e4m3: the fp8 encoding plus a bf16 correction term.

    decode = q·scale + c where c = SR_bf16(m − q·scale). The correction
    captures the (≈6% relative) e4m3 quantization error down to bf16 noise,
    so the EF trajectory tracks the fp32 one to ~1e-4 at 3B/element — the
    residue option for archs whose convergence can't absorb raw-fp8 noise
    but whose memory budget can't hold fp32 (DESIGN.md §5 scale limits).
    """

    name = "fp8_ec"

    def init(self, n, shape):
        enc = super().init(n, shape)
        enc["c"] = jnp.zeros(enc["q"].shape, jnp.bfloat16)
        return enc

    def decode(self, enc, shape):
        base = super().decode({"q": enc["q"], "scale": enc["scale"]}, shape)
        c = enc["c"].astype(jnp.float32)
        if len(shape) == 1:
            c = c[:, : shape[0]]
        return base + c

    def encode(self, m, shape, *, key=None):
        enc = super().encode(m, shape)
        base = super().decode(enc, shape)
        resid = m - base
        if len(shape) == 1:
            resid = jnp.pad(resid, ((0, 0), (0, enc["q"].shape[1] - shape[0])))
        if key is None:
            enc["c"] = resid.astype(jnp.bfloat16)
        else:
            enc["c"] = stochastic_round(resid, key, jnp.bfloat16)
        return enc

    def nbytes(self, n, shape):
        size = int(np.prod(shape))
        extra = 2 * (self._padded(size) if len(shape) == 1 else size)
        return super().nbytes(n, shape) + n * extra


CODECS: Dict[str, ResidueCodec] = {
    "fp32": ResidueCodec(),
    "bf16": _Bf16Codec(),
    "fp8": _Fp8Codec(),
    "fp8_ec": _Fp8EcCodec(),
}


@dataclasses.dataclass
class ScaleComState:
    """Pytree-registered container: per-tensor encoded residues + step counter."""

    residues: Dict[str, Pytree]  # path -> codec-encoded residue
    t: Array  # int32 step counter (drives the cyclic leader)

    def tree_flatten(self):
        return (self.residues, self.t), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    ScaleComState,
    ScaleComState.tree_flatten,
    lambda aux, ch: ScaleComState(*ch),
)


def _flat_paths(params: Pytree) -> Dict[str, Array]:
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


def init_state(
    params: Pytree,
    n_workers: int,
    residue_dtype: str = "fp32",
    min_size: int = 2048,
    layout: str = "auto",
) -> ScaleComState:
    """Zero-initialized ScaleCom state for a parameter pytree.

    Tensors below ``min_size`` carry no residue: they are always reduced
    densely (norm scales, biases). Must match ScaleComConfig at train time;
    ``layout`` resolves through ``resolve_layout`` exactly like
    ``ScaleComConfig.layout`` does, so the "auto" defaults stay in sync.
    """
    codec = CODECS[residue_dtype]
    residues = {}
    for path, leaf in _flat_paths(params).items():
        size = int(np.prod(leaf.shape)) if len(leaf.shape) else 1
        if size < min_size:
            continue
        residues[path] = codec.init(n_workers, storage_shape(leaf.shape, layout))
    return ScaleComState(residues=residues, t=jnp.zeros((), jnp.int32))


def _enc_signature(enc: Pytree) -> Tuple:
    """Hashable (leaf-name, shape, dtype) signature of one encoded residue."""
    return tuple(
        sorted((k, tuple(v.shape), str(v.dtype)) for k, v in enc.items())
    )


def codec_signature(residue_dtype: str, n: int, storage: Shape) -> Tuple:
    """The encoding signature ``CODECS[residue_dtype].init(n, storage)`` would
    produce, computed shape-only (``jax.eval_shape`` — no allocation).

    This is the expected side of the plan-time state-drift check
    (core.plan.plan_tensors): comparing it against ``residue_signature`` of
    the live state catches layout drift (flat vs rowwise storage), codec
    drift, and worker-count drift *before* the execute stage turns them into
    a cryptic reshape error.
    """
    codec = CODECS[residue_dtype]
    return _enc_signature(jax.eval_shape(lambda: codec.init(n, storage)))


def residue_signature(residues: Dict[str, Pytree]) -> frozenset:
    """Hashable per-tensor encoding signatures of a residue dict.

    Frozenset of (path, enc_signature) pairs — the form ``scalecom_reduce``
    hands to ``plan_tensors`` so the plan cache is keyed by (and validates
    against) the state that will actually be decoded, not just the residue
    path set. Membership changes (``remap_state``) alter the worker axis and
    therefore the signature, which is what invalidates stale cached plans.
    """
    return frozenset(
        (path, _enc_signature(enc)) for path, enc in residues.items()
    )


def remap_state(
    state: ScaleComState,
    old_n: int,
    new_n: int,
    residue_dtype: str = "fp32",
) -> ScaleComState:
    """Elastic re-plan: fold/expand residue worker axes on membership change.

    When the worker set changes (dropped worker, rejoin, regrouping after a
    hierarchical re-plan), the EF residues must move to the new worker count
    without losing the gradient mass they hold. The remap is MEAN-preserving:
    ``mean_i m_i`` — the quantity the reduce's worker-axis mean feeds back
    into ĝ — is invariant, so the trajectory picks up where it left off
    instead of double-counting or dropping accumulated error.

      expand (new_n = r·old_n)  each worker's residue is replicated to its r
                                successors (repeat);
      fold   (old_n = r·new_n)  each survivor absorbs the mean of the r
                                workers folded into it;
      general (e.g. 64 -> 63)   expand to lcm(old_n, new_n) then fold — both
                                steps are mean-preserving, so arbitrary
                                membership changes compose from the two
                                primitives (transient memory scales with
                                lcm/new_n; membership deltas are small in
                                practice).

    expand-then-fold round-trips BITWISE for fp32 residues with power-of-two
    factors (repeat then mean of identical rows is exact). Lossy codecs
    decode -> remap in fp32 -> re-encode (nearest rounding: no step counter
    is advanced here, and the EF loop absorbs the re-quantization error).

    ``state.t`` is preserved — the cyclic leader schedule continues modulo
    the new worker count.
    """
    if old_n <= 0 or new_n <= 0:
        raise ValueError(
            f"remap_state worker counts must be positive, got {old_n} -> {new_n}"
        )
    codec = CODECS[residue_dtype]
    lcm = old_n * new_n // math.gcd(old_n, new_n)
    up, down = lcm // old_n, lcm // new_n
    new_residues: Dict[str, Pytree] = {}
    for path, enc in state.residues.items():
        q = enc["q"]
        if q.shape[0] != old_n:
            raise ValueError(
                f"remap_state: residue {path!r} has worker axis {q.shape[0]}, "
                f"expected old_n={old_n} (was the state already remapped, or "
                f"initialized for a different n_workers/groups?)"
            )
        # Decode against the *encoded* trailing shape: for the flat fp8
        # layouts that is the padded buffer, and padded-size decode/encode
        # round-trips exactly (the pad slice is the identity there).
        shape = tuple(q.shape[1:])
        m = codec.decode(enc, shape)
        if up > 1:
            m = jnp.repeat(m, up, axis=0)
        if down > 1:
            m = jnp.mean(m.reshape((new_n, down) + m.shape[1:]), axis=1)
        new_residues[path] = codec.encode(m, shape, key=None)
    return ScaleComState(residues=new_residues, t=state.t)


def codec_roundtrip_error(
    name: str,
    *,
    n: int = 4,
    size: int = 2048,
    steps: int = 5,
    step_scale: float = 0.2,
    seed: int = 0,
) -> Dict[str, float]:
    """Standing diagnostic: encode∘decode error of one residue codec over an
    EF-like accumulation loop (decoded value feeds the next step, exactly as
    in ``scalecom_reduce``).

    Returns per-step worst/last relative roundtrip error and the drift of the
    quantized accumulator against an exact fp32 shadow. ``worst_step`` < 1
    is the contraction property ScaleCom's Theorem 1 needs from the memory;
    ``drift`` is the end-to-end bias the convergence analysis actually feels.
    Rendered as a table by ``analysis/report.py`` and pinned by
    tests/test_compat.py.
    """
    codec = CODECS[name]
    key = jax.random.PRNGKey(seed)
    m = jnp.zeros((n, size), jnp.float32)  # quantized-path accumulator (decoded)
    shadow = jnp.zeros((n, size), jnp.float32)  # exact fp32 accumulator
    worst = 0.0
    last = 0.0
    for t in range(steps):
        key, sub = jax.random.split(key)
        g = step_scale * jax.random.normal(sub, (n, size))
        target = m + g
        shadow = shadow + g
        enc = codec.encode(target, (size,), key=codec_key("<roundtrip>", jnp.int32(t)))
        m = codec.decode(enc, (size,))
        denom = float(jnp.linalg.norm(target)) or 1.0
        last = float(jnp.linalg.norm(m - target)) / denom
        worst = max(worst, last)
    drift = float(jnp.linalg.norm(m - shadow)) / (float(jnp.linalg.norm(shadow)) or 1.0)
    return {"worst_step": worst, "last_step": last, "drift": drift}


def residue_bytes(
    params: Pytree,
    n_workers: int,
    residue_dtype: str = "fp32",
    min_size: int = 2048,
    layout: str = "auto",
) -> int:
    codec = CODECS[residue_dtype]
    total = 0
    for leaf in jax.tree.leaves(params):
        size = int(np.prod(leaf.shape)) if leaf.ndim else 1
        if size >= min_size:
            total += codec.nbytes(n_workers, storage_shape(leaf.shape, layout))
    return total
