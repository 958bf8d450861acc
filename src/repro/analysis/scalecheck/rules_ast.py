"""Engine 1: the AST rules — the repo's source-level invariants, machine-checked.

Each rule turns one of the codebase's load-bearing conventions (previously a
grep tripwire inside a test, or enforced by review alone) into a registered
check with a stable name, a per-line suppression handle, and a precise
location in its findings:

  compat-boundary   no ``jax.experimental.*`` import/use and no version-gated
                    JAX symbol outside ``compat/`` and ``kernels/``. The
                    compat layer is the single import point of JAX API that
                    moves between releases, so an upgrade that moves one
                    is a one-file change.
  env-at-import     no ``os.environ`` *reads* at module top level. Every
                    env-driven choice in this repo (SCALECOM_LAYOUT /
                    SCALECOM_BACKEND / SCALECOM_BUCKET_MB / autotune cache)
                    is probed at CALL time so tests can monkeypatch and
                    long-lived processes honour late exports. Top-level env
                    *writes* stay legal — launch/dryrun.py must pin XLA_FLAGS
                    before jax initialises.
  no-rw-surface     no ``rw_*`` symbol anywhere: the dual flat/rowwise op
                    surface is gone for good (PR 3); a reappearing rw_ helper
                    means a feature is about to land twice, once per layout.
  tracer-hygiene    inside functions reachable from the jitted reduce path:
                    no host-side numpy coercions (``np.asarray``/``np.array``),
                    no ``float()``/``int()``/``bool()`` around jnp/jax array
                    expressions (concretization error / silent host sync),
                    and no Python ``if``/``while`` tests built from jnp/jax
                    array calls (TracerBoolConversionError at best, silent
                    retrace-per-value at worst — the recompilation failure
                    mode Agarwal et al. 2021 blame for erased compression
                    wins).
  payload-coverage  cross-module: the compressor registry
                    (core/compressors.py COMPRESSORS) and the wire-byte rule
                    (core/plan.py _INDEX_BYTES) name exactly the same set —
                    a compressor without an index-byte case would crash the
                    plan stage; an index-byte case without a compressor is a
                    stale wire-format entry.
  obs-hot-path      inside functions reachable from the jitted reduce path:
                    no host callbacks (``print``, ``jax.debug.print``,
                    ``io_callback``, ``pure_callback``), no wall-clock reads
                    (``time.perf_counter`` & co.), and no obs timer spans
                    (``tracer.span(...)`` / ``.instant(...)``). The telemetry
                    contract (repro.obs): in-trace observability is TAPS ONLY
                    (repro.obs.taps — pure pytree leaves); wall-clock spans
                    wrap jitted calls from OUTSIDE. A callback in the hot
                    path costs a device sync per step; a clock read there
                    times trace construction, not execution.
"""

from __future__ import annotations

import ast
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.scalecheck.callgraph import _dotted, reachable_functions
from repro.analysis.scalecheck.engine import SourceFile, register_rule
from repro.analysis.scalecheck.findings import Finding

# ---------------------------------------------------------------------------
# compat-boundary
# ---------------------------------------------------------------------------

# Version-gated jax symbols: API that has moved or been renamed between JAX
# releases (see compat/jax_compat.py's module docstring).
_GATED_ATTRS = {
    "jax.sharding.AxisType",
    "jax.set_mesh",
    "jax.shard_map",
    "jax.make_mesh",
    "jax.sharding.use_mesh",
    "jax.lax.axis_size",
    "jnp.float8_e4m3fn",
    "jax.numpy.float8_e4m3fn",
}

# Directory names whose files may touch jax.experimental / gated symbols:
# the compat layer (the probes live there) and the Pallas kernels (pallas is
# jax.experimental by definition, and kernels are per-accelerator anyway).
_COMPAT_ALLOWED_DIRS = {"compat", "kernels"}


def _compat_allowed(src: SourceFile) -> bool:
    return any(part in _COMPAT_ALLOWED_DIRS for part in src.path.parts)


@register_rule(
    "compat-boundary",
    "ast",
    "jax.experimental / version-gated jax API outside compat/ and kernels/",
)
def check_compat_boundary(sources: Sequence[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for src in sources:
        if _compat_allowed(src):
            continue
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.split(".")[:2] == ["jax", "experimental"]:
                        out.append(
                            src.finding(
                                "compat-boundary",
                                node.lineno,
                                f"import of {alias.name!r}: jax.experimental is "
                                "version-unstable; probe it in repro.compat (or a "
                                "kernels/ module) instead",
                            )
                        )
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "jax" and any(a.name == "experimental" for a in node.names):
                    mod = "jax.experimental"
                if mod.split(".")[:2] == ["jax", "experimental"]:
                    out.append(
                        src.finding(
                            "compat-boundary",
                            node.lineno,
                            f"import from {mod!r}: jax.experimental is "
                            "version-unstable; probe it in repro.compat (or a "
                            "kernels/ module) instead",
                        )
                    )
            elif isinstance(node, ast.Attribute):
                dotted = _dotted(node)
                if dotted.startswith("jax.experimental"):
                    out.append(
                        src.finding(
                            "compat-boundary",
                            node.lineno,
                            f"use of {dotted!r} outside compat/ and kernels/",
                        )
                    )
                elif dotted in _GATED_ATTRS:
                    out.append(
                        src.finding(
                            "compat-boundary",
                            node.lineno,
                            f"version-gated symbol {dotted!r} outside repro.compat; "
                            "use the jax_compat wrapper",
                        )
                    )
    return out


# ---------------------------------------------------------------------------
# env-at-import
# ---------------------------------------------------------------------------

_ENV_READ_CALLS = {
    "os.getenv",
    "os.environ.get",
    "environ.get",
    "os.environ.setdefault",
    "environ.setdefault",
}
_ENV_OBJECTS = {"os.environ", "environ"}


def _env_read(node: ast.AST) -> Optional[str]:
    """Describe an env READ at this node, or None."""
    if isinstance(node, ast.Call):
        dotted = _dotted(node.func)
        if dotted in _ENV_READ_CALLS:
            return f"{dotted}(...)"
    if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        if _dotted(node.value) in _ENV_OBJECTS:
            return "os.environ[...]"
    if isinstance(node, ast.Compare):
        for op, comp in zip(node.ops, node.comparators):
            if isinstance(op, (ast.In, ast.NotIn)) and _dotted(comp) in _ENV_OBJECTS:
                return "membership test on os.environ"
    return None


def _walk_module_scope(body: Sequence[ast.stmt]):
    """Yield every node at module scope, skipping function/lambda bodies
    (those run at call time — exactly what the convention wants)."""
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            yield node

    # ast.walk descends into function bodies; filter by re-walking with a
    # scope-aware stack instead.


def _module_scope_nodes(tree: ast.AST):
    """All nodes evaluated at import time (module + class bodies, top-level
    control flow), excluding anything inside a def/lambda."""
    stack = list(getattr(tree, "body", []))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            # default argument values DO evaluate at import time
            if not isinstance(node, ast.Lambda):
                stack.extend(node.args.defaults)
                stack.extend(d for d in node.args.kw_defaults if d is not None)
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


@register_rule(
    "env-at-import",
    "ast",
    "os.environ read at module import time (repo convention: call-time probes)",
)
def check_env_at_import(sources: Sequence[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for src in sources:
        for node in _module_scope_nodes(src.tree):
            desc = _env_read(node)
            if desc:
                out.append(
                    src.finding(
                        "env-at-import",
                        node.lineno,
                        f"{desc} read at import time: env vars must be probed "
                        "at call time (compat-layer style) so late exports and "
                        "test monkeypatching take effect",
                    )
                )
    return out


# ---------------------------------------------------------------------------
# no-rw-surface
# ---------------------------------------------------------------------------

_RW_RE = re.compile(r"\brw_\w+")


@register_rule(
    "no-rw-surface",
    "ast",
    "rw_* symbol (the deleted per-layout backend surface) resurfacing",
)
def check_no_rw_surface(sources: Sequence[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for src in sources:
        flagged: Set[int] = set()

        def add(line: int, what: str, name: str):
            if line not in flagged:
                flagged.add(line)
                out.append(
                    src.finding(
                        "no-rw-surface",
                        line,
                        f"{what} {name!r}: the per-layout rw_* surface was "
                        "unified away (one trailing-axis op set); a feature "
                        "implemented per-layout lands twice",
                    )
                )

        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                if node.name.startswith("rw_"):
                    add(node.lineno, "definition of", node.name)
            elif isinstance(node, ast.arg) and node.arg.startswith("rw_"):
                add(node.lineno, "argument", node.arg)
            elif isinstance(node, ast.Name) and node.id.startswith("rw_"):
                add(node.lineno, "symbol", node.id)
            elif isinstance(node, ast.Attribute) and node.attr.startswith("rw_"):
                add(node.lineno, "attribute", f".{node.attr}")
            elif isinstance(node, ast.keyword) and (node.arg or "").startswith("rw_"):
                add(node.lineno, "keyword argument", node.arg)
            elif isinstance(node, ast.alias):
                nm = node.asname or node.name
                if nm.startswith("rw_"):
                    add(node.lineno, "import alias", nm)
        # comments and string literals keep the historical grep's strength
        for ln, line in enumerate(src.lines, 1):
            m = _RW_RE.search(line)
            if m and ln not in flagged:
                add(ln, "text mention of", m.group(0))
    return out


# ---------------------------------------------------------------------------
# tracer-hygiene
# ---------------------------------------------------------------------------

# Entry points of the jitted reduce path; jax.jit/pmap-decorated functions
# are roots automatically (callgraph._is_jit_decorator).
_TRACED_ROOTS = ("scalecom_reduce",)

# Call roots that produce traced arrays. Bare "jax." is NOT traced-ish
# (jax.default_backend() and friends are host-side config probes).
_TRACED_CALL_PREFIXES = ("jnp.", "jax.lax.", "jax.numpy.", "jax.random.", "jax.nn.")

_NUMPY_COERCIONS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def _is_traced_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    dotted = _dotted(node.func)
    return bool(dotted) and any(
        dotted.startswith(p) or dotted + "." == p for p in _TRACED_CALL_PREFIXES
    )


def _contains_traced_expr(node: ast.AST) -> bool:
    return any(_is_traced_call(n) for n in ast.walk(node))


@register_rule(
    "tracer-hygiene",
    "ast",
    "host coercion / Python control flow on traced values in the reduce path",
)
def check_tracer_hygiene(sources: Sequence[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for fn, reached in reachable_functions(sources, _TRACED_ROOTS):
        if not reached:
            continue
        src = fn.src
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if dotted in _NUMPY_COERCIONS:
                    out.append(
                        src.finding(
                            "tracer-hygiene",
                            node.lineno,
                            f"{dotted}(...) in {fn.name!r} (reachable from the "
                            "jitted reduce path): host numpy coercion forces a "
                            "device sync / breaks under jit — use jnp",
                        )
                    )
                elif (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and any(_contains_traced_expr(a) for a in node.args)
                ):
                    out.append(
                        src.finding(
                            "tracer-hygiene",
                            node.lineno,
                            f"{node.func.id}() around a jnp/jax expression in "
                            f"{fn.name!r}: concretizes a tracer "
                            "(ConcretizationTypeError under jit, silent host "
                            "sync in eager)",
                        )
                    )
            elif isinstance(node, (ast.If, ast.While)) and _contains_traced_expr(
                node.test
            ):
                kind = "if" if isinstance(node, ast.If) else "while"
                out.append(
                    src.finding(
                        "tracer-hygiene",
                        node.lineno,
                        f"Python `{kind}` on a jnp/jax array expression in "
                        f"{fn.name!r}: traced values cannot drive Python control "
                        "flow (use jnp.where / lax.cond)",
                    )
                )
    return out


# ---------------------------------------------------------------------------
# payload-coverage
# ---------------------------------------------------------------------------


def _literal_str_elts(node: ast.AST) -> Optional[List[Tuple[str, int]]]:
    """(value, line) pairs for a tuple/list of string constants, else None."""
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    out = []
    for e in node.elts:
        if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
            return None
        out.append((e.value, e.lineno))
    return out


def _find_assign(tree: ast.AST, name: str) -> Optional[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    return node.value
        elif isinstance(node, ast.AnnAssign):
            if isinstance(node.target, ast.Name) and node.target.id == name:
                return node.value
    return None


def _compressor_names(src: SourceFile) -> Optional[Tuple[Set[str], int]]:
    value = _find_assign(src.tree, "COMPRESSORS")
    elts = _literal_str_elts(value) if value is not None else None
    if elts is None:
        return None
    return {v for v, _ in elts}, value.lineno


def _index_byte_names(src: SourceFile) -> Optional[Tuple[Set[str], int]]:
    value = _find_assign(src.tree, "_INDEX_BYTES")
    if isinstance(value, ast.Dict):
        names = set()
        for k in value.keys:
            if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                return None
            names.add(k.value)
        return names, value.lineno
    return None


def _pair_by_dir(
    plans: List[SourceFile], comps: List[SourceFile]
) -> List[Tuple[SourceFile, SourceFile]]:
    """Pair each plan.py with the compressors.py sharing the longest common
    parent (fixture trees and the real tree can coexist in one scan)."""
    pairs = []
    for plan in plans:
        best, best_len = None, -1
        for comp in comps:
            common = 0
            for a, b in zip(plan.path.parent.parts, comp.path.parent.parts):
                if a != b:
                    break
                common += 1
            if common > best_len:
                best, best_len = comp, common
        if best is not None:
            pairs.append((plan, best))
    return pairs


# ---------------------------------------------------------------------------
# obs-hot-path
# ---------------------------------------------------------------------------

# Host-side escape hatches: each forces a device round-trip (or worse, a
# host callback embedded in the compiled computation) when called under jit.
_HOST_CALLBACKS = {
    "print",
    "jax.debug.print",
    "jax.debug.callback",
    "jax.debug.breakpoint",
    "jax.experimental.io_callback",
    "io_callback",
    "jax.pure_callback",
    "pure_callback",
}

# Wall-clock reads: meaningless inside a traced function (they time tracing,
# which happens once, not execution) — spans belong OUTSIDE the jitted call.
_WALL_CLOCKS = {
    "time.time",
    "time.perf_counter",
    "time.monotonic",
    "time.process_time",
    "perf_counter",
    "monotonic",
}

# obs timer entry points (Tracer.span / Tracer.instant): method-call names,
# matched on the attribute so `tracer.span(...)` and `self.tracer.span(...)`
# both fire.
_OBS_TIMER_ATTRS = {"span", "instant"}


@register_rule(
    "obs-hot-path",
    "ast",
    "host callback / wall-clock read / obs timer span in the jitted reduce path",
)
def check_obs_hot_path(sources: Sequence[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for fn, reached in reachable_functions(sources, _TRACED_ROOTS):
        if not reached:
            continue
        src = fn.src
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            if dotted in _HOST_CALLBACKS:
                out.append(
                    src.finding(
                        "obs-hot-path",
                        node.lineno,
                        f"{dotted}(...) in {fn.name!r} (reachable from the "
                        "jitted reduce path): host callbacks embed a device "
                        "sync per step — thread values out as obs taps "
                        "(repro.obs.taps) instead",
                    )
                )
            elif dotted in _WALL_CLOCKS:
                out.append(
                    src.finding(
                        "obs-hot-path",
                        node.lineno,
                        f"{dotted}(...) in {fn.name!r} (reachable from the "
                        "jitted reduce path): a wall clock inside a traced "
                        "function times trace construction, not execution — "
                        "span the jitted call from outside (repro.obs.tracing)",
                    )
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _OBS_TIMER_ATTRS
            ):
                out.append(
                    src.finding(
                        "obs-hot-path",
                        node.lineno,
                        f".{node.func.attr}(...) in {fn.name!r} (reachable from "
                        "the jitted reduce path): obs timer spans wrap jitted "
                        "calls from outside; in-trace observability is taps "
                        "only (repro.obs.taps)",
                    )
                )
    return out


@register_rule(
    "payload-coverage",
    "ast",
    "compressor registry vs wire-byte rule drift (COMPRESSORS <-> _INDEX_BYTES)",
)
def check_payload_coverage(sources: Sequence[SourceFile]) -> List[Finding]:
    plans = [s for s in sources if s.path.name == "plan.py"]
    comps = [s for s in sources if s.path.name == "compressors.py"]
    out: List[Finding] = []
    for plan_src, comp_src in _pair_by_dir(plans, comps):
        comp_names = _compressor_names(comp_src)
        idx_names = _index_byte_names(plan_src)
        if comp_names is None or idx_names is None:
            # only meaningful when both registries are present and literal
            continue
        compressors = comp_names[0] - {"none"}  # "none" == dense, no payload
        index_cases = idx_names[0]
        for missing in sorted(compressors - index_cases):
            out.append(
                plan_src.finding(
                    "payload-coverage",
                    idx_names[1],
                    f"compressor {missing!r} (registered in "
                    f"{comp_src.display}) has no index-byte case in "
                    "_INDEX_BYTES: its wire bytes are unplanned and "
                    "payload_bytes will KeyError",
                )
            )
        for stale in sorted(index_cases - compressors):
            out.append(
                plan_src.finding(
                    "payload-coverage",
                    idx_names[1],
                    f"index-byte case {stale!r} has no matching compressor in "
                    f"{comp_src.display}'s COMPRESSORS: stale wire-format entry",
                )
            )
    return out
