"""The blockwise causal attention kernel (kernels/flash_attention.py) in
interpret mode, against the XLA path of ``attention.attention_core``, and the
rule by which training attention takes it (``attention.flash_applies``).

Parity runs at the highest matmul precision, where both paths keep float32
operands; at the default precision the kernel rounds its matmul operands to
bfloat16, as XLA's default does on a TPU (the CPU's XLA path does not). The
dispatch tests stand the trace on a TPU by patching ``kernels.ops.on_tpu``;
the kernel still runs in interpret mode, since JAX's backend is the CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends.introspect import count_pallas_launches
from repro.configs import registry
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig
from repro.kernels import flash_attention
from repro.kernels import ops as kernel_ops
from repro.models import attention, build_model
from repro.optim import make_optimizer, schedule
from repro.training import init_train_state
from repro.training.train_step import build_train_step

# (B, H, KV, hd, vd): MLA's wider query/key head over its value head, and
# grouped-query heads (G = 3)
SHAPES = {"mla": (1, 4, 4, 192, 128), "gqa": (2, 6, 2, 128, 128)}
BLOCK = 128


def _inputs(B, S, H, KV, hd, vd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(ks[0], (B, S, H, hd)),
        jax.random.normal(ks[1], (B, S, KV, hd)),
        jax.random.normal(ks[2], (B, S, KV, vd)),
        jax.random.normal(ks[3], (B, S, H, vd)),
    )


def _xla(q, k, v):
    pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    return attention.attention_core(q, k, v, pos, pos, causal=True, window=None,
                                    q_chunk=BLOCK)


def _with_grads(fn, q, k, v, do):
    o, back = jax.vjp(fn, q, k, v)
    return (o,) + back(do)


def _kernel(q, k, v):
    return flash_attention.causal_attention(q, k, v, block=BLOCK)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("blocks", [1, 2, 4])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_matches_xla_path_at_highest_precision(shape, blocks):
    """Output and the gradients of q, k and v over 1, 2 and 4 key blocks."""
    B, H, KV, hd, vd = SHAPES[shape]
    args = _inputs(B, blocks * BLOCK, H, KV, hd, vd)
    with jax.default_matmul_precision("highest"):
        got = _with_grads(_kernel, *args)
        want = _with_grads(_xla, *args)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.dtype == jnp.float32, name
        assert g.shape == w.shape, name
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_kernel_rounds_operands_only_at_default_precision():
    """At the default precision the matmul operands are bfloat16 (a
    bfloat16-sized gap from the float32 answer, and no larger); at the
    highest they stay float32."""
    assert flash_attention.mxu_dtype() == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        assert flash_attention.mxu_dtype() == jnp.float32
        args = _inputs(1, 2 * BLOCK, 4, 2, 128, 128, seed=3)
        want = _with_grads(_xla, *args)
    got = _with_grads(_kernel, *args)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert 1e-4 < _rel(g, w) < 3e-2, (name, _rel(g, w))


def test_kernel_grid_visits_only_the_lower_triangle():
    """Block pairs above the diagonal are never visited: n(n+1)/2 pairs,
    and the dk/dv kernel's triples repeat them per head of the group."""
    qi, kj = flash_attention._row_pairs(4)
    assert len(qi) == 10 and np.all(kj <= qi)
    kj, gh, qi = flash_attention._column_triples(4, 3)
    assert len(kj) == 30 and np.all(qi >= kj)
    # a key block's triples are consecutive: its dk/dv accumulator is
    # written once, after its last
    assert np.all(np.diff(kj) >= 0)


@pytest.mark.parametrize("seq,fits", [(128, False), (512, False), (1024, True),
                                      (4096, True), (1536, True), (1000, False)])
def test_fits_whole_blocks_over_more_than_one(seq, fits):
    assert flash_attention.fits(seq) is fits


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(kernel_ops, "on_tpu", lambda: True)


def _train_step(cfg, batch, seq):
    """One compressed training step (jnp reduce, one learner), with the
    shapes of its state and of a batch x seq batch."""
    model = build_model(cfg, compute_dtype="float32", loss_chunk=64)
    sc = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=64), min_size=1024,
                        backend="jnp")
    opt = make_optimizer("sgdm", momentum=0.9)
    state = jax.eval_shape(
        lambda: init_train_state(model, opt, sc, jax.random.PRNGKey(0), n_workers=1)[0]
    )
    shapes = {
        "tokens": jax.ShapeDtypeStruct((1, batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((1, batch, seq), jnp.int32),
        "mask": jax.ShapeDtypeStruct((1, batch, seq), jnp.float32),
    }
    step = build_train_step(model, opt, schedule.constant(0.05), sc, mode="scalecom",
                            n_workers=1)
    return step, state, shapes


def _step_launches(cfg, seq):
    return count_pallas_launches(*_train_step(cfg, 1, seq))


# a layer stack is one scanned body: its forward launches flash_fwd once, and
# its rematerialised backward flash_fwd, flash_dq and flash_dkv
PER_STACK = 4


@pytest.mark.parametrize(
    "name,seq,stacks",
    [
        ("paper-transformer-base", 128, 0),  # S 128: one block, the XLA path
        ("starcoder2-3b", 1024, 1),
        ("moonlight-16b-a3b", 1024, 2),  # the leading dense stack and the MoE stack
    ],
)
def test_training_step_launches(on_tpu, name, seq, stacks):
    assert _step_launches(registry.smoke(name), seq) == PER_STACK * stacks


def test_training_step_off_tpu_has_no_launch():
    assert _step_launches(registry.smoke("starcoder2-3b"), 1024) == 0


def test_window_keeps_the_xla_path(on_tpu):
    cfg = dataclasses.replace(registry.smoke("starcoder2-3b"), sliding_window=256)
    assert _step_launches(cfg, 1024) == 0


def _attn_launches(cfg, seq, **kw):
    model = build_model(cfg, compute_dtype="float32")
    params, _ = model.init(jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jnp.zeros((1, seq, cfg.d_model))

    def f(x):
        return attention.attention_train(cfg, p, x, jnp.arange(seq), dtype=jnp.float32, **kw)

    return count_pallas_launches(f, x)


def test_attention_train_dispatch(on_tpu):
    cfg = registry.smoke("starcoder2-3b")
    seq = 1024
    assert _attn_launches(cfg, seq, in_order=True) == 1
    # arbitrary positions: the XLA path masks by absolute position
    assert _attn_launches(cfg, seq) == 0
    # cross-attention
    enc = jnp.zeros((1, seq, cfg.d_model))
    assert _attn_launches(cfg, seq, in_order=True, kv_x=enc,
                          kv_positions=jnp.arange(seq)) == 0
    # non-causal, windowed
    assert _attn_launches(cfg, seq, in_order=True, causal=False) == 0
    assert _attn_launches(cfg, seq, in_order=True, window=128) == 0


def test_program_over_several_devices_keeps_the_xla_path(on_tpu, monkeypatch):
    """A program over a mesh (the worker-sharded step) is partitioned by
    GSPMD, which cannot partition a Mosaic kernel."""
    cfg = registry.smoke("starcoder2-3b")
    assert _attn_launches(cfg, 1024, in_order=True) == 1
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert _attn_launches(cfg, 1024, in_order=True) == 0


@pytest.mark.parametrize("name", ["starcoder2-3b", "moonlight-16b-a3b"])
def test_model_loss_and_grads_match_the_xla_path(monkeypatch, name):
    """The whole model at S over one block: loss and every parameter's
    gradient with the kernel taken equal the XLA path's."""
    cfg = registry.smoke(name)
    seq = 2 * flash_attention.BLOCK
    model = build_model(cfg, compute_dtype="float32", loss_chunk=256)
    params, _ = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(key, (1, seq), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.fold_in(key, 1), (1, seq), 0, cfg.vocab),
        "mask": jnp.ones((1, seq)),
    }
    grad = jax.value_and_grad(lambda p: model.loss(p, batch)[0])
    with jax.default_matmul_precision("highest"):
        want_loss, want = grad(params)
        monkeypatch.setattr(kernel_ops, "on_tpu", lambda: True)
        assert count_pallas_launches(grad, params) > 0
        got_loss, got = grad(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = want[path]
        scale = max(float(jnp.max(jnp.abs(w))), 1e-6)
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * scale, jax.tree_util.keystr(path)


def test_paper_step_lowers_unchanged(monkeypatch):
    """The paper cell's step (published widths, batch 32 x 128) lowers to the
    same module whether or not the trace stands on a TPU: at S 128 no
    attention takes the kernel, so nothing of the dispatch reaches it."""
    step, state, batch = _train_step(registry.arch("paper-transformer-base"), 32, 128)

    def lowered():
        return jax.jit(step).lower(state, batch).as_text(debug_info=False)

    off_tpu = lowered()
    monkeypatch.setattr(kernel_ops, "on_tpu", lambda: True)
    assert count_pallas_launches(step, state, batch) == 0
    assert lowered() == off_tpu
