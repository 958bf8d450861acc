"""Faults planted under the timed path, to show that the comparison that
decides ``correct`` catches them.

Each fault wraps ``TrainLoop.step(state, batch, i)`` (the call the window
drives) and breaks one guarantee of a training step:

- ``unchanged``: the step returns the state it was given;
- ``half_batch``: the step sees only the first half of each worker's rows,
  so its loss and gradient are means over the rest;
- ``dropped_leaf``: one parameter tensor is left out of the reduce, so its
  reduced gradient is zero and it does not move.

A cell on one chip has no exchange between chips to leave out.
"""

from __future__ import annotations

FAULTS = ("unchanged", "half_batch", "dropped_leaf")


def wrap(step, fault: str):
    """``step(self, state, batch, i)`` with ``fault`` planted."""
    import jax
    import jax.numpy as jnp

    if fault == "unchanged":

        def broken(self, state, batch, i):
            keep = jax.tree.map(jnp.copy, state)
            _, metrics = step(self, state, batch, i)
            return keep, metrics

    elif fault == "half_batch":

        def broken(self, state, batch, i):
            half = batch["tokens"].shape[1] // 2
            return step(self, state, {k: v[:, :half] for k, v in batch.items()}, i)

    elif fault == "dropped_leaf":

        def broken(self, state, batch, i):
            # the first leaf of the layer stack: attention's query projection
            p0 = jnp.copy(state.params["blocks"]["attn_wq"])
            state, metrics = step(self, state, batch, i)
            state.params["blocks"]["attn_wq"] = p0
            state.opt_state["m"]["blocks"]["attn_wq"] = jnp.zeros_like(p0)
            return state, metrics

    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    return broken
