"""scalecheck: the static invariant checker (AST + jaxpr engines).

Coverage map:

  * every AST rule fires on its seeded-violation fixture
    (tests/fixtures/scalecheck/) and the CLI exits non-zero on each;
  * the merged tree is clean: ``run(["src/repro"])`` returns no findings —
    the acceptance bar for the whole subsystem;
  * per-line ``# scalecheck: ignore[rule]`` suppressions are honoured;
  * CLI exit codes (0 clean / 1 findings / 2 usage), text + json formats,
    ``--list-rules``, and real ``python -m`` invocation;
  * the call-graph reachability feeding tracer-hygiene (transitive, jit
    roots);
  * the jaxpr engine verifies the bucketed schedule contract on a >= 3
    bucket trace in BOTH layouts, and fails the overlap=False trace (the
    negative control that proves the checks are not vacuous).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.analysis import scalecheck
from repro.analysis.scalecheck import callgraph, cli, engine
from repro.analysis.scalecheck.findings import parse_suppressions

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "scalecheck"
SRC = REPO / "src" / "repro"


def _run(path, rule):
    return scalecheck.run([str(path)], rules=[rule])


def _mem_sources(text, name="mod.py"):
    import ast

    lines = text.splitlines()
    return [
        engine.SourceFile(
            path=pathlib.Path("/mem") / name,
            display=name,
            text=text,
            lines=lines,
            tree=ast.parse(text),
            suppressions=parse_suppressions(lines),
        )
    ]


# ---------------------------------------------------------------------------
# each AST rule fires on its seeded fixture
# ---------------------------------------------------------------------------


def test_compat_boundary_fixture():
    findings = _run(FIXTURES / "compat_violation.py", "compat-boundary")
    assert findings and all(f.rule == "compat-boundary" for f in findings)
    msgs = "\n".join(f.message for f in findings)
    assert "jax.experimental" in msgs  # the import
    assert "jax.make_mesh" in msgs  # the version-gated attribute


def test_compat_boundary_catches_pallas_leak():
    """A ``pl.pallas_call`` kernel escaping outside kernels/ must fire — the
    fused-reduce op keeps every launch site in kernels/, and this is the rule
    that keeps it that way."""
    findings = _run(FIXTURES / "pallas_leak.py", "compat-boundary")
    assert findings and all(f.rule == "compat-boundary" for f in findings)
    msgs = "\n".join(f.message for f in findings)
    assert "jax.experimental" in msgs


def test_compat_boundary_allows_compat_and_kernels_dirs():
    # the real compat layer and the pallas kernels use these symbols heavily
    # (incl. the fused single-launch reduce in kernels/fused_reduce.py)
    assert not _run(SRC / "compat", "compat-boundary")
    assert not _run(SRC / "kernels", "compat-boundary")


def test_env_at_import_fixture():
    findings = _run(FIXTURES / "env_violation.py", "env-at-import")
    lines = {f.line for f in findings}
    text = (FIXTURES / "env_violation.py").read_text().splitlines()
    # the module-scope get, the membership test, and the subscript all fire
    assert len(findings) >= 3
    # the sanctioned call-time probe inside fine() is NOT flagged
    call_time_line = next(
        i for i, line in enumerate(text, 1) if "JAX_COMPILATION_CACHE_DIR" in line
    )
    assert call_time_line not in lines


def test_no_rw_surface_fixture():
    findings = _run(FIXTURES / "rw_violation.py", "no-rw-surface")
    assert len(findings) >= 2  # the def and the call site
    assert all("rw_" in f.message for f in findings)


def test_tracer_hygiene_fixture():
    findings = _run(FIXTURES / "tracer_violation.py", "tracer-hygiene")
    msgs = "\n".join(f.message for f in findings)
    assert "float()" in msgs  # concretizing coercion
    assert "`if`" in msgs  # Python control flow on traced value
    assert "np.asarray" in msgs  # host coercion
    # helper() is only reachable THROUGH outer() — transitive reachability
    assert "bool()" in msgs and "'helper'" in msgs


def test_obs_hot_path_fixture():
    findings = _run(FIXTURES / "obs_hotpath_violation.py", "obs-hot-path")
    msgs = "\n".join(f.message for f in findings)
    assert "print(...)" in msgs  # bare host print
    assert "jax.debug.print(...)" in msgs  # jax host callback
    assert "time.perf_counter(...)" in msgs  # wall clock in the trace
    # the obs timer span fires in the TRANSITIVELY reached helper
    assert ".span(...)" in msgs and "'_compress'" in msgs
    # nothing fires in the unreachable function
    assert "'unrelated'" not in msgs


def test_payload_coverage_fixture():
    findings = _run(FIXTURES / "payload_violation", "payload-coverage")
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2
    assert any("'glt_k'" in m and "no index-byte case" in m for m in msgs)
    assert any("'random_k'" in m and "stale" in m for m in msgs)


def test_suppression_waives_only_the_named_rule():
    assert not _run(FIXTURES / "suppressed.py", "no-rw-surface")
    # same content unsuppressed fires (guards against a dead fixture)
    assert _run(FIXTURES / "rw_violation.py", "no-rw-surface")


# ---------------------------------------------------------------------------
# the acceptance bar: the merged tree is clean
# ---------------------------------------------------------------------------


def test_merged_tree_is_clean_under_all_ast_rules():
    ast_rules = [r.name for r in engine.RULES.values() if r.engine == "ast"]
    findings = scalecheck.run([str(SRC)], rules=ast_rules)
    assert not findings, scalecheck.format_text(findings)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_exit_codes(capsys):
    assert cli.main([str(FIXTURES / "rw_violation.py"), "--rules", "no-rw-surface"]) == 1
    assert cli.main([str(SRC / "core"), "--rules", "no-rw-surface"]) == 0
    assert cli.main([str(SRC), "--rules", "not-a-rule"]) == 2
    assert cli.main(["/no/such/path.txt", "--rules", "no-rw-surface"]) == 2
    capsys.readouterr()


def test_cli_json_report(capsys):
    rc = cli.main(
        [str(FIXTURES / "rw_violation.py"), "--rules", "no-rw-surface",
         "--format", "json"]
    )
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["rules_run"] == ["no-rw-surface"]
    assert report["count"] == len(report["findings"]) > 0
    assert report["counts_by_rule"] == {"no-rw-surface": report["count"]}
    f = report["findings"][0]
    assert set(f) == {"rule", "path", "line", "message"}


def test_cli_list_rules(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in (
        "compat-boundary", "env-at-import", "no-rw-surface",
        "tracer-hygiene", "payload-coverage", "obs-hot-path",
        "collective-schedule",
    ):
        assert name in out


def test_cli_module_invocation():
    """The documented entry point: python -m repro.analysis.scalecheck."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.scalecheck",
         "--rules", "no-rw-surface", str(FIXTURES / "rw_violation.py")],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert proc.returncode == 1, proc.stderr
    assert "[no-rw-surface]" in proc.stdout


def test_unknown_rule_is_an_error():
    with pytest.raises(ValueError, match="unknown scalecheck rule"):
        scalecheck.run([str(SRC)], rules=["nope"])


# ---------------------------------------------------------------------------
# call-graph reachability (feeds tracer-hygiene)
# ---------------------------------------------------------------------------

_GRAPH_SRC = """
import jax

@jax.jit
def root(x):
    return a(x)

def a(x):
    return b(x)

def b(x):
    return x

def unrelated(x):
    return x
"""


def test_reachability_is_transitive_from_jit_roots():
    sources = _mem_sources(_GRAPH_SRC)
    reach = {
        fn.name: reached
        for fn, reached in callgraph.reachable_functions(sources, ())
    }
    assert reach == {"root": True, "a": True, "b": True, "unrelated": False}


def test_named_roots_without_decorators():
    sources = _mem_sources(_GRAPH_SRC)
    reach = {
        fn.name: reached
        for fn, reached in callgraph.reachable_functions(sources, ("unrelated",))
    }
    assert reach["unrelated"] is True


# ---------------------------------------------------------------------------
# jaxpr engine: the bucketed schedule contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["flat", "rowwise"])
def test_collective_schedule_clean_on_multibucket_trace(layout):
    from repro.analysis.scalecheck import rules_jaxpr

    closed, schedule, n_leaves = rules_jaxpr.trace_schedule(layout)
    assert schedule is not None and len(schedule) >= 3  # the acceptance bar
    barriers = rules_jaxpr._barrier_eqns(closed.jaxpr)
    assert len(barriers) == 2 * len(schedule)  # stage+fence per bucket
    assert not rules_jaxpr.check_schedule(layout)


def test_collective_schedule_fails_sync_fallback():
    """overlap=False drops the barriers -> the checker must NOT stay green
    (proves the schedule checks are structural, not vacuous)."""
    from repro.analysis.scalecheck import rules_jaxpr

    findings = rules_jaxpr.check_schedule("flat", overlap=False)
    assert findings and any("optimization_barrier" in f.message for f in findings)
    assert all(f.path == "<jaxpr:flat>" for f in findings)
