"""Pluggable kernel backends for the ScaleCom hot path.

    from repro.backends import resolve_backend
    be = resolve_backend("auto")          # pallas on a TPU, else jnp
    idx, vals = be.select(ef, chunk)      # one launch, worker axis included

See base.py for the protocol and resolution rules, jnp_backend.py /
pallas_backend.py for the two shipped implementations.
``ScaleComConfig.backend`` threads a spec through ``scalecom_reduce``.
"""

from repro.backends.base import (
    KernelBackend,
    available_backends,
    pallas_available,
    register_backend,
    resolve_backend,
)

# Importing the implementation modules registers them.
from repro.backends import jnp_backend as _jnp_backend  # noqa: F401
from repro.backends import pallas_backend as _pallas_backend  # noqa: F401

__all__ = [
    "KernelBackend",
    "available_backends",
    "pallas_available",
    "register_backend",
    "resolve_backend",
]
