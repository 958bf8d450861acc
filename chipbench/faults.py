"""Faults planted under the timed path, to show that the comparison that
decides ``correct`` catches them.

Each fault wraps ``TrainLoop.step(state, batch, i)`` (the call the window
drives) and breaks one guarantee of a training step:

- ``unchanged``: the step returns the state it was given;
- ``half_batch``: the step sees only the first half of each worker's rows,
  so its loss and gradient are means over the rest;
- ``dropped_leaf``: one parameter tensor is left out of the reduce, so its
  reduced gradient is zero and it does not move;
- ``no_exchange``: the exchange between learners is left out, so the step
  updates with learner 0's own contribution alone: every learner is given
  learner 0's rows, and the worker mean of identical contributions is
  learner 0's.

A cell of one learner has no exchange to leave out.
"""

from __future__ import annotations

FAULTS = ("unchanged", "half_batch", "dropped_leaf", "no_exchange")


def applicable(workers: int):
    """The faults a cell of ``workers`` learners can have."""
    return FAULTS if workers > 1 else FAULTS[:-1]


def planted(workers: int):
    """The faults that need a run to be read: a state left unchanged reads 1
    on every norm by construction."""
    return tuple(f for f in applicable(workers) if f != "unchanged")


def wrap(step, fault: str):
    """``step(self, state, batch, i)`` with ``fault`` planted."""
    import jax
    import jax.numpy as jnp

    def like(new, old):
        """``new`` where ``old`` lies: a step compiled for the placement of
        its arguments takes no other."""
        return jax.device_put(new, old.sharding)

    if fault == "unchanged":

        def broken(self, state, batch, i):
            keep = jax.tree.map(jnp.copy, state)
            _, metrics = step(self, state, batch, i)
            return keep, metrics

    elif fault == "half_batch":

        def broken(self, state, batch, i):
            half = batch["tokens"].shape[1] // 2
            return step(self, state, {k: like(v[:, :half], v) for k, v in batch.items()}, i)

    elif fault == "dropped_leaf":

        def broken(self, state, batch, i):
            # the first leaf of the layer stack: attention's query projection
            p0 = jnp.copy(state.params["blocks"]["attn_wq"])
            state, metrics = step(self, state, batch, i)
            state.params["blocks"]["attn_wq"] = p0
            state.opt_state["m"]["blocks"]["attn_wq"] = like(jnp.zeros_like(p0), p0)
            return state, metrics

    elif fault == "no_exchange":

        def broken(self, state, batch, i):
            return step(self, state, {k: like(jnp.broadcast_to(v[:1], v.shape), v)
                                      for k, v in batch.items()}, i)

    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    return broken
