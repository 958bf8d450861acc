"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing
but JAX, into plain event tuples; ``reduce`` turns those into device busy
time, per-operation totals, kernel time and idle gaps inside the traced
window. The arithmetic takes plain tuples so that it can be checked on
hand-made events as well as on a recorded trace.

What a TPU trace holds (read by hand from a v5e trace of the paper cell):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
event per executed HLO operation, named by the instruction's whole HLO text
(``%select_trailing.33 = (...) custom-call(...), custom_call_target=
"tpu_custom_call", ...``). Events nest: a ``while`` op spans the ops of its
body. A Pallas kernel compiled by Mosaic is a ``tpu_custom_call``. A
collective is known by its instruction's opcode in the compiled module
(``chipbench.scopes.collective_map``); an asynchronous one is two events,
its ``-start`` and its ``-done``, with other ops between them. The host
plane ``/host:CPU`` has a line per thread; ``jax.profiler.TraceAnnotation``
spans are events on the thread that opened them (``python3``). Device and
host events share one clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

# (name, start_ns, duration_ns)
Event = Tuple[str, float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_names: Sequence[str]) -> dict:
    """{"devices": {chip: {"ops": [Event], "kernels": [Event]}}, "host": [Event]}.

    ``kernels`` are the operations whose HLO is a Mosaic custom call; host
    events are kept only where their name is in ``host_names``.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host: List[Event] = []
    wanted = set(host_names)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, kernels = [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    e = (ev.name, ev.start_ns, ev.duration_ns)
                    ops.append(e)
                    if is_kernel(ev.name):
                        kernels.append(e)
            devices[int(m.group(1))] = {"ops": ops, "kernels": kernels}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (ev.name, ev.start_ns, ev.duration_ns)
                    for ev in line.events
                    if ev.name in wanted
                )
    return {"devices": devices, "host": host}


def is_kernel(name: str) -> bool:
    """A Mosaic kernel: an XLA op whose HLO is a ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in name


def op_kind(name: str) -> str:
    """``%select_trailing.33 = (...) custom-call(...)`` -> ``select_trailing``:
    the HLO instruction's name without its ``%`` and its numeric suffix."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, dot, suffix = head.rpartition(".")
    return base if dot and suffix.isdigit() else head


def instruction(event_name: str) -> str:
    """``%select_trailing.33 = (...) custom-call(...)`` -> ``select_trailing.33``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.removeprefix("ROOT ").lstrip("%")


def parents(events: Sequence[Event]) -> List[int]:
    """The index of each event's direct parent on one line, where events
    nest (a ``while`` op spans the ops of its body), or -1."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    parent = [-1] * len(events)
    stack: List[int] = []
    for i in order:
        _, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    return parent


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float, float]]:
    """(name, start, duration, self time) of each event of one line: self
    time is the duration less that of the direct children."""
    child = [0.0] * len(events)
    for (_, _, dur), p in zip(events, parents(events)):
        if p >= 0:
            child[p] += dur
    return [(n, s, d, d - c) for (n, s, d), c in zip(events, child)]


def collective_spans(events: Sequence[Event], collectives: Dict[str, Tuple[str, str]]):
    """(collective events, other ops' events) of one chip's line.

    ``collectives`` maps an instruction to (part, pair) as
    ``chipbench.scopes.collective_map`` gives it: a ``sync`` collective is
    its own event; an async one spans from its ``start`` event to the end of
    the next ``done`` event of the same pair. The other ops are the events
    that are no collective and hold no other event (a loop spans ops that
    may be collectives)."""
    spans: List[Event] = []
    opened: Dict[str, float] = {}
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        part, pair = collectives.get(instruction(name), (None, None))
        if part == "start":
            opened[pair] = start
        elif part == "done" and pair in opened:
            s = opened.pop(pair)
            spans.append((pair, s, start + dur - s))
        elif part == "sync":
            spans.append((name, start, dur))
    holders = set(parents(events))
    others = [
        e for i, e in enumerate(events)
        if i not in holders and instruction(e[0]) not in collectives
    ]
    return spans, others


def overlap(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals (start, end) of ``events`` cut to the window [lo, hi]."""
    out = []
    for _, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping or touching intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _host_label(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """The host span that overlaps the gap most, or ``none``."""
    best, label = 0.0, "none"
    for name, start, dur in host:
        ov = min(gap[1], start + dur) - max(gap[0], start)
        if ov > best:
            best, label = ov, name
    return label


def reduce(trace: dict, window: Tuple[float, float], top: int = 10,
           collectives: Dict[str, Tuple[str, str]] = None) -> dict:
    """Per-chip busy and kernel time inside ``window`` (ns), averaged over the
    chips; the operation kinds with the most self time among the operations
    that lie wholly inside the window; the longest idle gaps, each named
    by the host span that overlaps it most; and, of the ``collectives``
    (``collective_spans``), the union of their intervals and the part of it
    in which no other op runs on the chip, also averaged over the chips."""
    lo, hi = window
    chips = sorted(trace["devices"])
    if not chips:
        raise ValueError("the trace has no TPU device plane")
    busy_ns = kernel_ns = collective_ns = exposed_ns = 0.0
    op_ns: Dict[str, float] = {}
    all_gaps: List[Tuple[float, str]] = []
    host = [e for e in trace["host"] if e[0] != "window"]
    for chip in chips:
        dev = trace["devices"][chip]
        merged = union(clip(dev["ops"], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        kernel_ns += sum(e - s for s, e in union(clip(dev["kernels"], lo, hi)))
        spans, others = collective_spans(dev["ops"], collectives or {})
        held = union(clip(spans, lo, hi))
        collective_ns += sum(e - s for s, e in held)
        exposed_ns += sum(e - s for s, e in held) - overlap(held, union(clip(others, lo, hi)))
        for name, start, dur, own in self_times(dev["ops"]):
            if start >= lo and start + dur <= hi:
                kind = op_kind(name)
                op_ns[kind] = op_ns.get(kind, 0.0) + own
        longest = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:top]
        all_gaps.extend((g[1] - g[0], _host_label(g, host)) for g in longest)
    n = len(chips)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    all_gaps.sort(key=lambda g: -g[0])
    return {
        "chips": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "kernel_s": kernel_ns / n * 1e-9,
        "collective_s": collective_ns / n * 1e-9,
        "collective_exposed_s": exposed_ns / n * 1e-9,
        "device_ops": [[name, ns / n * 1e-9] for name, ns in top_ops],
        "idle_gaps": [[label, ns * 1e-9] for ns, label in all_gaps[:top]],
    }
