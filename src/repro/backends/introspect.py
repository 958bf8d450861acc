"""Jaxpr introspection helpers for backend tests and benchmarks.

``count_pallas_launches`` answers "how many Pallas kernel launches does this
function make?" by tracing it to a jaxpr and counting ``pallas_call``
equations, recursing into nested jaxprs (pjit bodies, scans, conds, custom
derivatives). Counting the *trace* instead of spying on ``pl.pallas_call``
at runtime makes the answer immune to jit caching — a monkeypatched wrapper
never fires when jax replays a compiled executable, which is exactly when a
regression would hide — and keeps this module off the pallas import
(scalecheck's compat-boundary rule applies: only compat/ and kernels/ touch
``jax.experimental``).

Used by the launch-count tripwire in tests/test_kernels.py (fused reduce
must be 1 launch, the composed path 3).
"""

from __future__ import annotations

import jax

__all__ = ["count_pallas_launches"]


def _count_in(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            n += _count_in_value(v)
    return n


def _count_in_value(v) -> int:
    # Duck-typed descent: ClosedJaxpr carries .jaxpr, Jaxpr carries .eqns,
    # and params like cond branches hold sequences of either.
    if hasattr(v, "jaxpr"):
        return _count_in(v.jaxpr)
    if hasattr(v, "eqns"):
        return _count_in(v)
    if isinstance(v, (list, tuple)):
        return sum(_count_in_value(x) for x in v)
    return 0


def count_pallas_launches(fn, *args, **kwargs) -> int:
    """Number of pallas_call equations in the jaxpr of ``fn(*args)``."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _count_in(closed.jaxpr)
