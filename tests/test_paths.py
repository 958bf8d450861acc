"""Every path through the reduce trains the same model.

The reduce's path is a function of its explicit configuration and the
platform: ``ScaleComConfig.backend``, ``layout`` and ``fused``, and the
``buckets`` argument of ``build_train_step``. This file runs the train step
down each of the 16 combinations for 3 steps and holds each to its
contracts:

  - fused vs unfused and flat vs rowwise (last dims a chunk multiple) are
    BITWISE identical on one backend and one bucketing: every path matches
    its (backend, flat, unfused, same buckets) twin exactly;
  - every path agrees with the (jnp, flat, unfused, unbucketed) path to the
    fp32 tolerance of tests/test_backends.py's cross-backend trajectory test,
    and exactly where it differs from it in layout or fusion alone.

Bucketing is bitwise at the reduce (tests/test_overlap.py), but inside a
whole step the bucket barriers move the fusion boundaries of the optimizer
update that follows the reduce, and XLA's CPU backend then rounds some
parameters differently in the last bit (seen after one step); the pallas
backend runs in interpret mode here and differs from jnp within fp32
tolerance (tests/test_backends.py). Those two comparisons take the tolerance.
"""

import dataclasses
import functools
import itertools

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig
from repro.data import make_batches
from repro.models import build_model
from repro.optim import make_optimizer, schedule
from repro.training import init_train_state
from repro.training.train_step import build_train_step

N_WORKERS = 2
STEPS = 3
BUCKETS = (None, 8 << 20)
PATHS = list(itertools.product(
    ("jnp", "pallas"), ("flat", "rowwise"), (False, True), BUCKETS
))
BASE = ("jnp", "flat", False, None)

# one layer at widths whose every compressed tensor has a last dim that is a
# multiple of the chunk, so flat and rowwise chunk identically
ARCH = dataclasses.replace(
    registry.smoke("paper-transformer-base"),
    n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
    tie_embeddings=True,
)


@functools.lru_cache(maxsize=None)
def _train(backend, layout, fused, buckets):
    """(losses, params, residues) after STEPS steps down one path, each
    array flattened per worker so the two layouts compare element-wise."""
    model = build_model(ARCH, compute_dtype="float32", loss_chunk=16)
    sc_cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=16), beta=0.1, min_size=512,
        layout=layout, backend=backend, fused=fused,
    )
    opt = make_optimizer("sgdm")
    state, _ = init_train_state(
        model, opt, sc_cfg, jax.random.PRNGKey(0), n_workers=N_WORKERS
    )
    step = jax.jit(build_train_step(
        model, opt, schedule.constant(0.05), sc_cfg, n_workers=N_WORKERS,
        buckets=buckets,
    ))
    batches = make_batches(ARCH.vocab, N_WORKERS, 2, 16, seed=0)
    losses = []
    for _ in range(STEPS):
        state, metrics = step(state, next(batches))
        losses.append(float(metrics["loss"]))
    params = [np.asarray(p).ravel() for p in jax.tree.leaves(state.params)]
    residues = {
        path: np.asarray(enc["q"]).reshape(N_WORKERS, -1)
        for path, enc in state.sc_state.residues.items()
    }
    return np.asarray(losses), params, residues


def _assert_same(got, want, bitwise):
    check = (
        np.testing.assert_array_equal if bitwise
        else functools.partial(np.testing.assert_allclose, rtol=1e-5, atol=1e-6)
    )
    check(got[0], want[0], err_msg="loss")
    assert len(got[1]) == len(want[1])
    for i, (a, b) in enumerate(zip(got[1], want[1])):
        check(a, b, err_msg=f"param leaf {i}")
    assert got[2].keys() == want[2].keys()
    for path in want[2]:
        check(got[2][path], want[2][path], err_msg=f"residue {path}")


@pytest.mark.parametrize(
    "backend,layout,fused,buckets", PATHS,
    ids=[
        f"{b}-{lay}-{'fused' if f else 'unfused'}-{'8mb' if bk else 'nobuckets'}"
        for b, lay, f, bk in PATHS
    ],
)
def test_train_step_same_on_every_path(backend, layout, fused, buckets):
    got = _train(backend, layout, fused, buckets)
    assert np.all(np.isfinite(got[0]))
    _assert_same(got, _train(backend, "flat", False, buckets), bitwise=True)
    exact = backend == BASE[0] and buckets == BASE[3]
    _assert_same(got, _train(*BASE), bitwise=exact)
