"""The one traffic generator: seeded synthetic token streams, batched per step.

Every traffic mix is a JSON file beside this module that gives the job's
sizes; this code reads none of them by name. Tokens come from an order-1
Markov source with heavy-tailed transitions (a copy of the program's
``data.pipeline.SyntheticLM``, kept here so the benchmark's inputs cannot
change with the program). Step ``i`` of seed ``s`` is drawn from its own
generator ``(s, i)``, so every step's rows differ, the same seed gives the
same batches in every run, and any step can be drawn again on its own.
"""

from __future__ import annotations

import numpy as np


class MarkovSource:
    """Order-1 Markov token source with heavy-tailed transitions."""

    def __init__(self, vocab: int, seed: int, branching: int = 16):
        rng = np.random.default_rng(seed)
        self.vocab = vocab
        self.branching = branching
        self.succ = rng.integers(0, vocab, size=(vocab, branching))
        probs = rng.dirichlet(np.full(branching, 0.3), size=vocab)
        self.cum = np.cumsum(probs, axis=1)

    def sample(self, rng: np.random.Generator, batch: int, seq: int) -> np.ndarray:
        out = np.empty((batch, seq + 1), np.int32)
        cur = rng.integers(0, self.vocab, size=batch)
        out[:, 0] = cur
        for t in range(1, seq + 1):
            u = rng.random(batch)[:, None]
            choice = (u > self.cum[cur]).sum(axis=1)
            cur = self.succ[cur, np.minimum(choice, self.branching - 1)]
            out[:, t] = cur
        return out


class Traffic:
    """Worker-stacked training batches of one traffic mix for one seed.

    ``batch(i)`` returns {"tokens", "labels": (workers, local_batch, seq)
    int32, "mask": ones float32}: the layout the program's train step takes.
    """

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.seed = seed
        self.source = MarkovSource(vocab, seed, mix.get("branching", 16))

    @property
    def tokens_per_step(self) -> int:
        m = self.mix
        return m["workers"] * m["local_batch"] * m["seq"]

    def batch(self, step: int) -> dict:
        m = self.mix
        n, b, s = m["workers"], m["local_batch"], m["seq"]
        rng = np.random.default_rng((self.seed, step))
        toks = self.source.sample(rng, n * b, s).reshape(n, b, s + 1)
        return {
            "tokens": toks[..., :-1],
            "labels": toks[..., 1:],
            "mask": np.ones((n, b, s), np.float32),
        }
