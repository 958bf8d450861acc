"""Shared provenance stamps for every exported record.

Before this module each BENCH_*.json writer hand-rolled its own
device/backend/interpret fields (and BENCH_scenarios.json carried none), so
records from different legs could not be compared — an interpret-mode pallas
number with no ``interpret`` flag reads like a TPU result. One helper, used
by benchmarks/*, the harness CLI, and the telemetry run header:

  ``provenance()``            git sha + jax/python versions + device — the
                              full run header;
  ``device_tags(backend)``    the per-record subset the benches stamp on
                              every entry, including the load-bearing
                              ``interpret`` flag (pallas off-TPU times the
                              interpreter, not kernels).

jax is imported inside the functions: ``repro.obs`` must stay importable (and
cheap) in tooling contexts that never touch jax, and provenance of a run is a
call-time question anyway.
"""

from __future__ import annotations

import platform
import subprocess
from typing import Any, Dict, Optional

__all__ = ["git_sha", "device_tags", "provenance"]


def git_sha(cwd: Optional[str] = None) -> Optional[str]:
    """Short commit sha of the working tree, or None outside a git checkout
    (installed wheels, tarball exports). Never raises."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True,
            text=True,
            timeout=5,
            cwd=cwd,
        )
    except (OSError, subprocess.SubprocessError):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def device_tags(backend_name: Optional[str] = None) -> Dict[str, Any]:
    """Per-record device tags: device kind, jax platform, and — when a kernel
    backend name is given — whether pallas would run in interpret mode here
    (any non-TPU host: the timings measure the interpreter). Which inner-loop
    path a run took is its config's (``ScaleComConfig.fused``), which the
    caller stamps itself."""
    import jax

    tags: Dict[str, Any] = {
        "device_kind": jax.devices()[0].device_kind,
        "jax_backend": jax.default_backend(),
    }
    if backend_name is not None:
        tags["interpret"] = (
            backend_name == "pallas" and jax.default_backend() != "tpu"
        )
    return tags


def provenance(backend_name: Optional[str] = None) -> Dict[str, Any]:
    """The full run header stamped on every BENCH_*.json / event log."""
    import jax

    return {
        "git_sha": git_sha(),
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
        **device_tags(backend_name),
    }
