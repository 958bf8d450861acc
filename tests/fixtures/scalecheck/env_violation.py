"""Seeded violation for the ``env-at-import`` rule (never imported)."""

import os

PLATFORMS = os.environ.get("JAX_PLATFORMS", "cpu")  # read at import time

if "XLA_FLAGS" in os.environ:  # membership read at import time
    FLAGS = os.environ["XLA_FLAGS"]


def fine():
    # call-time probes are the sanctioned pattern
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
