"""End-to-end behaviour of the paper's system (the headline claims at proxy
scale): Fig. 2 similarity dynamics, Fig. 3 Hamming range, and the CLI drivers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import metrics
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig
from repro.core.state import CODECS
from repro.data import make_batches
from repro.models import build_model
from repro.optim import make_optimizer, schedule
from repro.training import init_train_state
from repro.training.train_step import build_train_step


def _residue_matrix(state, path):
    """Worker-stacked residue as (n, size), whatever the storage layout —
    the similarity metrics are layout-independent."""
    enc = state.sc_state.residues[path]
    m = CODECS["fp32"].decode(enc, enc["q"].shape[1:])
    return m.reshape(m.shape[0], -1)


def _train(beta, lr, steps, n=4, seed=0):
    cfg = registry.smoke("paper-transformer-base")
    model = build_model(cfg, compute_dtype="float32", loss_chunk=16)
    sc = ScaleComConfig(compressor=CompressorConfig("clt_k", chunk=16), beta=beta,
                        min_size=512)
    opt = make_optimizer("sgdm")
    step = jax.jit(build_train_step(model, opt, schedule.constant(lr), sc, n_workers=n))
    state, _ = init_train_state(model, opt, sc, jax.random.PRNGKey(seed), n_workers=n)
    snaps = {}
    for i, b in zip(range(steps), make_batches(cfg.vocab, n, 4, 64, seed=seed)):
        state, m = step(state, b)
        snaps[i] = state
    return state, snaps


def test_memory_similarity_grows_over_training():
    """Fig. 2a: pairwise cosine distance of worker residues decreases as
    training progresses — the property CLT-k exploits."""
    state, snaps = _train(beta=1.0, lr=0.05, steps=40)
    path = [p for p in state.sc_state.residues if "mlp_up" in p][0]
    d_early = float(metrics.pairwise_cosine_distance(_residue_matrix(snaps[2], path)))
    d_late = float(metrics.pairwise_cosine_distance(_residue_matrix(snaps[39], path)))
    assert d_late < d_early, (d_early, d_late)


def test_lowpass_filter_improves_similarity_at_high_lr():
    """Fig. 2c: at an aggressive (10x) learning rate, beta=0.1 keeps worker
    residues more similar than classic error feedback (beta=1)."""
    s_f, _ = _train(beta=0.1, lr=0.5, steps=25)
    s_c, _ = _train(beta=1.0, lr=0.5, steps=25)
    path = [p for p in s_f.sc_state.residues if "mlp_up" in p][0]
    d_f = float(metrics.pairwise_cosine_distance(_residue_matrix(s_f, path)))
    d_c = float(metrics.pairwise_cosine_distance(_residue_matrix(s_c, path)))
    assert d_f < d_c, (d_f, d_c)


def test_hamming_distance_in_paper_range():
    """Fig. 3: leader-vs-global top-k normalized Hamming distance < 1 after
    some training (the paper reports d/k ≈ 0.2-0.4 at full scale)."""
    state, _ = _train(beta=1.0, lr=0.05, steps=20)
    path = [p for p in state.sc_state.residues if "mlp_up" in p][0]
    m = _residue_matrix(state, path)
    y = jnp.mean(m, axis=0)
    k = max(m.shape[1] // 16, 8)
    d = float(metrics.hamming_distance_topk(m[0], y, k))
    assert d < 0.9


def test_cli_train_driver(tmp_path, monkeypatch):
    from repro.launch.train import main

    # main() turns on the persistent compile cache; keep it out of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    hist = main([
        "--arch", "paper-transformer-base", "--workers", "2", "--steps", "6",
        "--local-batch", "2", "--seq", "32", "--warmup-steps", "2",
        "--history-out", str(tmp_path / "h.json"), "--log-every", "5",
    ])
    assert np.isfinite(hist[-1]["loss"])
    assert (tmp_path / "h.json").exists()


def test_cli_full_width_loads_published_widths(monkeypatch):
    """--full-width builds registry.arch; the default builds registry.smoke.
    (The published widths are stood in for by the smoke config so the CPU
    never builds the full-size model.)"""
    from repro.launch import train

    asked = []

    def fake_arch(name):
        asked.append(name)
        return registry.smoke(name)

    monkeypatch.setattr(registry, "arch", fake_arch)
    argv = ["--arch", "paper-transformer-base", "--workers", "2",
            "--local-batch", "1", "--seq", "8"]
    cfg, _, _, _ = train.build(train.parse_args(argv))
    assert asked == [] and cfg == registry.smoke("paper-transformer-base")
    train.build(train.parse_args(argv + ["--full-width"]))
    assert asked == ["paper-transformer-base"]


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(tmp_path, monkeypatch, env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; unset, the
    cache goes to the one fixed, git-ignored directory in the checkout."""
    import pathlib

    from repro.launch.train import enable_compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cli_serve_driver():
    from repro.launch.serve import main

    gen = main(["--arch", "recurrentgemma-2b", "--batch", "2",
                "--prompt-len", "16", "--gen", "4"])
    assert gen.shape == (2, 4)
