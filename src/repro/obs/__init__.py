"""repro.obs — the telemetry subsystem (ISSUE 9).

Three pieces behind one zero-overhead-when-disabled API:

  taps        jit-safe metric taps: traced values leave the hot path as aux
              pytree leaves of the reduce's stats dict (never host
              callbacks); no-ops entirely when telemetry is off
              (``ScaleComConfig.telemetry``).
  tracing     host spans around host-side phases (train step, checkpoint),
              on the profiler's clock while a capture is open, and exported
              as Chrome-trace-event JSON + JSONL events.
  registry /  host-side metric aggregation, the JSONL event log, shared
  events /    provenance stamps for every BENCH_*.json, and the
  report      ``python -m repro.obs.report`` summarizer.

``TelemetryRun`` bundles the sinks for one run; ``get_logger`` /
``enable_console_logging`` are the repo-wide logging handles the training
loop routes through (quiet by default — no handlers — so benches and the
harness don't spam stdout; the launch CLI turns the console on).

This package imports no jax at module scope: ``repro.core`` depends on
``repro.obs.taps``, and the report CLI must run where jax isn't installed.

ROADMAP.md "Observability" documents the tap API, the span/event schema, and
how to add a metric. The scalecheck rule ``obs-hot-path`` statically enforces
the hot-path contract: no host callbacks / prints / timers reachable from
``scalecom_reduce`` — taps only.
"""

from __future__ import annotations

import logging
import sys

from repro.obs import events, provenance, registry, taps, tracing
from repro.obs.events import EventLog, read_events
from repro.obs.provenance import device_tags, git_sha
from repro.obs.provenance import provenance as provenance_stamp
from repro.obs.registry import MetricRegistry
from repro.obs.run import TelemetryRun
from repro.obs.tracing import Tracer

__all__ = [
    "EventLog",
    "MetricRegistry",
    "TelemetryRun",
    "Tracer",
    "device_tags",
    "enable_console_logging",
    "events",
    "get_logger",
    "git_sha",
    "provenance",
    "provenance_stamp",
    "read_events",
    "registry",
    "taps",
    "tracing",
]

_LOGGER_ROOT = "repro"


def get_logger(name: str = "") -> logging.Logger:
    """The repo's logger tree (root ``repro``). With no handler configured
    (the default) INFO records are dropped silently — which is exactly the
    satellite contract: benches/harness importing the training loop are quiet
    unless a consumer opts in via ``enable_console_logging``."""
    return logging.getLogger(f"{_LOGGER_ROOT}.{name}" if name else _LOGGER_ROOT)


def enable_console_logging(level: int = logging.INFO) -> logging.Logger:
    """Attach one stderr handler to the ``repro`` logger (idempotent) — the
    launch CLI's opt-in to visible step logs."""
    logger = get_logger()
    logger.setLevel(level)
    if not any(
        isinstance(h, logging.StreamHandler) for h in logger.handlers
    ):
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(logging.Formatter("%(name)s: %(message)s"))
        logger.addHandler(handler)
    return logger
