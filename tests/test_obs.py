"""Telemetry subsystem (repro.obs): the zero-overhead-when-disabled contract.

The load-bearing invariants:

  * telemetry ON changes NOTHING about the reduce's primary outputs — a
    20-step jitted trajectory (both layouts x both backends x bucketed/
    unbucketed) is BITWISE identical with cfg.telemetry flipped;
  * the telemetry trace is retrace-deterministic: tracing the same reduce
    twice yields an identical jaxpr (tap keys are sorted, labels static);
  * the taps measure real things: measured wire bytes equal the plan's one
    byte rule per compressor, the codec roundtrip error is exactly 0 for
    fp32 and positive for bf16, similarity samples fire on the
    metrics_every cadence;
  * the export layer round-trips: Chrome traces load as valid Trace Event
    Format JSON, the JSONL event log survives malformed lines, and
    ``python -m repro.obs.report`` summarizes a real traced run.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.compressors import CompressorConfig
from repro.core.scalecom import ScaleComConfig, scalecom_reduce
from repro.core.state import init_state
from repro.obs import report, taps
from repro.obs.events import EventLog, read_events
from repro.obs.registry import MetricRegistry
from repro.obs.tracing import Tracer

CHUNK = 8
_TREE_SIZES = {"a": (96,), "b": (24, 16), "c": (520,), "tiny": (16,)}


def _cfg(**kw):
    base = dict(
        compressor=CompressorConfig("clt_k", chunk=CHUNK),
        beta=0.25,
        min_size=64,
    )
    base.update(kw)
    return ScaleComConfig(**base)


def _trajectory(cfg, buckets, steps=20, n=4, seed=0):
    params = {k: jnp.zeros(s) for k, s in _TREE_SIZES.items()}
    state = init_state(params, n, min_size=cfg.min_size, layout=cfg.layout)
    reduce_fn = jax.jit(lambda g, s: scalecom_reduce(g, s, cfg, buckets=buckets))
    key = jax.random.PRNGKey(seed)
    ghats, stats_hist = [], []
    for _ in range(steps):
        key, sub = jax.random.split(key)
        g = {
            k: jax.random.normal(jax.random.fold_in(sub, i), (n,) + s)
            for i, (k, s) in enumerate(_TREE_SIZES.items())
        }
        ghat, state, stats = reduce_fn(g, state)
        ghats.append(ghat)
        stats_hist.append(stats)
    return ghats, state, stats_hist


# ---------------------------------------------------------------------------
# taps
# ---------------------------------------------------------------------------


def test_tap_key_roundtrip():
    key = taps.tap_key("bytes", path="['a']", compressor="clt_k")
    assert key == "bytes{compressor=clt_k,path=['a']}"
    name, labels = taps.parse_key(key)
    assert name == "bytes"
    assert labels == {"compressor": "clt_k", "path": "['a']"}
    assert taps.parse_key("plain") == ("plain", {})


def test_tap_is_noop_without_collector():
    assert not taps.active()
    taps.tap("ignored", 1.0)  # must not raise or leak anywhere
    with taps.collect() as got:
        assert taps.active()
        taps.tap("x", 2.0, path="p")
    assert not taps.active()
    assert got == {"x{path=p}": 2.0}


def test_collectors_nest_and_shadow():
    with taps.collect() as outer:
        taps.tap("a", 1.0)
        with taps.collect() as inner:
            taps.tap("b", 2.0)
        taps.tap("c", 3.0)
    assert inner == {"b": 2.0}
    assert outer == {"a": 1.0, "c": 3.0}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_kinds_and_summary():
    reg = MetricRegistry()
    reg.counter("steps")
    reg.counter("steps")
    reg.gauge("ratio", 65.0, compressor="clt_k")
    for v in (1.0, 3.0):
        reg.histogram("wall_us", v)
    s = reg.summary()
    assert s["steps"]["total"] == 2.0
    assert s["ratio{compressor=clt_k}"]["last"] == 65.0
    h = s["wall_us"]
    assert h["count"] == 2 and h["mean"] == 2.0 and h["min"] == 1.0
    assert sum(h["buckets"].values()) == 2


def test_registry_rejects_kind_flip():
    reg = MetricRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x", 1.0)


def test_record_stats_routes_obs_keys():
    reg = MetricRegistry()
    flat = reg.record_stats(
        {"loss": 1.5, "obs/buildup_nnz{path=['a']}": jnp.float32(12.0)}
    )
    assert flat == {"loss": 1.5, "obs/buildup_nnz{path=['a']}": 12.0}
    s = reg.summary()
    assert s["loss"]["kind"] == "gauge"
    assert s["buildup_nnz{path=['a']}"]["kind"] == "histogram"
    assert s["buildup_nnz:last{path=['a']}"]["last"] == 12.0


# ---------------------------------------------------------------------------
# the bitwise contract: telemetry ON == OFF
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("buckets", [False, 1024], ids=["unbucketed", "bucketed"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("layout", ["flat", "rowwise"])
def test_telemetry_on_bitwise_identical(layout, backend, buckets):
    off = _cfg(layout=layout, backend=backend)
    on = _cfg(layout=layout, backend=backend, telemetry=True, metrics_every=4)
    ghats_off, state_off, stats_off = _trajectory(off, buckets)
    ghats_on, state_on, stats_on = _trajectory(on, buckets)
    for go, gn in zip(ghats_off, ghats_on):
        for k in _TREE_SIZES:
            np.testing.assert_array_equal(np.asarray(go[k]), np.asarray(gn[k]))
    for path in state_off.residues:
        np.testing.assert_array_equal(
            np.asarray(state_off.residues[path]["q"]),
            np.asarray(state_on.residues[path]["q"]),
        )
    # the obs/ leaves exist ONLY on the telemetry run, and the shared keys agree
    assert not any(k.startswith("obs/") for k in stats_off[0])
    assert any(k.startswith("obs/") for k in stats_on[0])
    for k in stats_off[0]:
        np.testing.assert_array_equal(
            np.asarray(stats_off[0][k]), np.asarray(stats_on[0][k])
        )


def test_telemetry_trace_is_retrace_deterministic():
    cfg = _cfg(telemetry=True, metrics_every=2)
    params = {k: jnp.zeros(s) for k, s in _TREE_SIZES.items()}
    state = init_state(params, 4, min_size=cfg.min_size)
    g = {
        k: jax.random.normal(jax.random.PRNGKey(i), (4,) + s)
        for i, (k, s) in enumerate(_TREE_SIZES.items())
    }
    fn = lambda gg, ss: scalecom_reduce(gg, ss, cfg, buckets=1024)  # noqa: E731
    j1 = str(jax.make_jaxpr(fn)(g, state))
    j2 = str(jax.make_jaxpr(fn)(g, state))
    assert j1 == j2


# ---------------------------------------------------------------------------
# the taps measure real things
# ---------------------------------------------------------------------------


def _single_tensor_stats(compressor, n=4, size=96, **cfg_kw):
    cfg = _cfg(
        compressor=CompressorConfig(compressor, chunk=CHUNK),
        min_size=1,
        telemetry=True,
        **cfg_kw,
    )
    params = {"a": jnp.zeros((size,))}
    state = init_state(params, n, min_size=1, residue_dtype=cfg.residue_dtype)
    g = {"a": jax.random.normal(jax.random.PRNGKey(0), (n, size))}
    _, _, stats = scalecom_reduce(g, state, cfg)
    return stats


@pytest.mark.parametrize(
    "compressor", ["clt_k", "true_topk", "local_topk", "random_k"]
)
def test_measured_bytes_match_plan(compressor):
    stats = _single_tensor_stats(compressor)
    measured = stats[f"obs/bytes_measured{{compressor={compressor},path=['a']}}"]
    planned = stats[f"obs/bytes_planned{{compressor={compressor},path=['a']}}"]
    assert float(measured) == float(planned) > 0
    # and the plan bytes are what the stats dict already reports per worker
    assert float(planned) == float(stats["comm_bytes_per_worker"])


def test_codec_roundtrip_error_tap():
    exact = _single_tensor_stats("clt_k", residue_dtype="fp32")
    lossy = _single_tensor_stats("clt_k", residue_dtype="bf16")
    assert float(exact["obs/codec_roundtrip_err{codec=fp32,path=['a']}"]) == 0.0
    assert float(lossy["obs/codec_roundtrip_err{codec=bf16,path=['a']}"]) > 0.0


def test_similarity_sampling_cadence():
    cfg = _cfg(min_size=1, telemetry=True, metrics_every=2)
    params = {"a": jnp.zeros((96,))}
    state = init_state(params, 4, min_size=1)
    fn = jax.jit(lambda g, s: scalecom_reduce(g, s, cfg))
    flags, cosines = [], []
    for t in range(5):
        g = {"a": jax.random.normal(jax.random.PRNGKey(t), (4, 96))}
        _, state, stats = fn(g, state)
        flags.append(float(stats["obs/similarity_sampled{path=['a']}"]))
        cosines.append(
            float(stats["obs/pairwise_cosine_distance{path=['a']}"])
        )
    assert flags == [1.0, 0.0, 1.0, 0.0, 1.0]
    # skipped steps carry the cond's zero branch; sampled steps a real value
    assert cosines[1] == cosines[3] == 0.0
    assert cosines[0] != 0.0


def test_buildup_tap_counts_union_for_local_topk():
    shared = _single_tensor_stats("clt_k", size=520)
    union = _single_tensor_stats("local_topk", size=520)
    k = float(shared["obs/buildup_k{path=['a']}"])
    assert float(shared["obs/buildup_nnz{path=['a']}"]) <= k * 1.01
    # union growth: local_topk scatters every worker's own set (paper Fig. 5)
    assert float(union["obs/buildup_nnz{path=['a']}"]) > k


def test_bucket_taps_present_only_when_bucketed():
    cfg = _cfg(telemetry=True)
    _, _, stats_u = _trajectory(cfg, buckets=False, steps=1)
    _, _, stats_b = _trajectory(cfg, buckets=1024, steps=1)
    assert not any("bucket" in k for k in stats_u[0])
    staged = [k for k in stats_b[0] if k.startswith("obs/bucket_staged_leaves")]
    dense = [k for k in stats_b[0] if k.startswith("obs/bucket_bytes_dense")]
    assert len(staged) == len(dense) >= 2  # several 1 KB buckets on this tree
    total = sum(float(stats_b[0][k]) for k in staged)
    assert total == len(_TREE_SIZES)  # every leaf staged exactly once


# ---------------------------------------------------------------------------
# tracing: spans + Chrome trace export
# ---------------------------------------------------------------------------


def test_tracer_spans_and_chrome_trace(tmp_path):
    clock = iter(float(i) for i in range(100))
    tr = Tracer(clock=lambda: next(clock))
    with tr.span("plan", n_tensors=3):
        pass
    with tr.span("bucket[0]", tid=1) as s:
        s.args["bytes"] = 1024
    tr.instant("violation", message="boom")
    path = tr.write_chrome_trace(str(tmp_path / "trace.json"), metadata={"x": 1})
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert doc["metadata"] == {"x": 1}
    events = doc["traceEvents"]
    assert [e["name"] for e in events] == ["plan", "bucket[0]", "violation"]
    for e in events:
        assert e["ph"] == "X" and e["pid"] == 1 and e["cat"] == "repro"
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert events[1]["tid"] == 1 and events[1]["args"]["bytes"] == 1024
    # the JSONL view carries the same spans
    assert [e["name"] for e in tr.to_events()] == [e["name"] for e in events]


def test_span_recorded_even_if_body_raises():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("mid-span")
    assert [s.name for s in tr.spans] == ["doomed"]


def test_spans_and_instants_open_profiler_annotations(monkeypatch):
    # each span and instant opens a jax.profiler.TraceAnnotation of its name,
    # so that it lands on the profiler's host plane while a capture is open
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    tr = Tracer()
    with tr.span("step"):
        opened.append(("body", "step"))
    tr.instant("violation", message="boom")
    assert opened == [
        ("enter", "step"), ("body", "step"), ("exit", "step"),
        ("enter", "violation"), ("exit", "violation"),
    ]
    assert [s.name for s in tr.spans] == ["step", "violation"]


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------


def test_event_log_roundtrip_and_malformed_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path) as log:
        log.emit("provenance", git_sha="abc")
        log.emit("step", step=0, metrics={"loss": jnp.float32(1.5)})
    with open(path, "a") as f:
        f.write("{not json\n")
    evs = read_events(path)
    assert [e["type"] for e in evs] == ["provenance", "step"]
    assert evs[1]["metrics"]["loss"] == 1.5  # jax scalar coerced to float
    assert all("wall_s" in e for e in evs)
    assert read_events(path, types=["step"]) == [evs[1]]


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------


def test_provenance_fields():
    p = obs.provenance_stamp("pallas")
    assert p["jax_version"] == jax.__version__
    assert p["device_kind"] and p["jax_backend"]
    assert isinstance(p["interpret"], bool)
    assert "interpret" not in obs.device_tags()
    # inside this checkout the sha resolves; never raises either way
    sha = obs.git_sha()
    assert sha is None or len(sha) >= 7


# ---------------------------------------------------------------------------
# TelemetryRun + the report CLI over a real traced run
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A real 10-step telemetry-enabled reduce driven through TelemetryRun."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    cfg = _cfg(telemetry=True, metrics_every=2)
    params = {k: jnp.zeros(s) for k, s in _TREE_SIZES.items()}
    state = init_state(params, 4, min_size=cfg.min_size)
    fn = jax.jit(lambda g, s: scalecom_reduce(g, s, cfg, buckets=1024))
    with obs.TelemetryRun(trace_dir, backend_name="jnp") as run:
        for i in range(10):
            g = {
                k: jax.random.normal(jax.random.PRNGKey(i * 10 + j), (4,) + s)
                for j, (k, s) in enumerate(_TREE_SIZES.items())
            }
            with run.step_span(i):
                _, state, stats = fn(g, state)
                run.record_step(i, {k: float(v) for k, v in stats.items()})
        paths = run.close()
    return paths


def test_telemetry_run_artifacts(traced_run):
    doc = json.load(open(traced_run["trace"]))
    step_spans = [e for e in doc["traceEvents"] if e["name"] == "step"]
    assert len(step_spans) == 10
    assert doc["metadata"]["jax_version"] == jax.__version__
    evs = read_events(traced_run["events"])
    assert evs[0]["type"] == "provenance"
    types = {e["type"] for e in evs}
    assert {"step", "span", "summary"} <= types
    # close() is idempotent: the summary event appears exactly once
    assert sum(1 for e in evs if e["type"] == "summary") == 1


def test_report_summarize_real_run(traced_run):
    s = report.summarize(traced_run["events"])
    assert s["steps"] == 10
    assert s["compression_ratio"]["mean"] > 1.0
    assert s["bytes_plan_mismatches"] == 0
    assert len(s["buildup_curve"]) == 10
    assert all(v >= 1.0 for v in s["buildup_curve"].values())
    # metrics_every=2 over 10 steps -> samples at 0,2,4,6,8
    assert sorted(s["similarity"]["pairwise_cosine_distance"]) == [0, 2, 4, 6, 8]
    assert s["contraction_gamma_mean"] is not None
    assert s["spans"]["by_name"]["step"]["count"] == 10
    assert s["violations"] == []
    text = report.format_text(s)
    assert "compression ratio" in text and "violations: none" in text


def test_report_cli_exit_codes(traced_run, tmp_path, capsys):
    assert report.main([traced_run["events"]]) == 0
    out = capsys.readouterr().out
    assert "telemetry report: 10 steps" in out
    assert report.main([traced_run["events"], "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["steps"] == 10
    # a log carrying a violation exits 1
    bad = str(tmp_path / "bad.jsonl")
    with EventLog(bad) as log:
        log.emit("violation", message="drift exceeded tolerance")
    assert report.main([bad]) == 1
    assert "drift exceeded" in capsys.readouterr().out
    assert report.main([str(tmp_path / "missing.jsonl")]) == 2
    capsys.readouterr()


def test_report_module_invocation(traced_run):
    """The documented entry point: python -m repro.obs.report."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs.report", traced_run["events"]],
        capture_output=True, text=True, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr
    assert "telemetry report" in proc.stdout


# ---------------------------------------------------------------------------
# loop integration: quiet by default, TelemetryRun wiring
# ---------------------------------------------------------------------------


def _tiny_loop(sc_cfg, **loop_kw):
    """(loop, state, batches) of the smoke paper transformer on 2 workers."""
    from repro.configs import registry as cfg_registry
    from repro.data import make_batches
    from repro.models import build_model
    from repro.optim import make_optimizer, schedule
    from repro.training import TrainLoop, init_train_state

    arch = cfg_registry.smoke("paper-transformer-base")
    model = build_model(arch, compute_dtype="float32", loss_chunk=16)
    opt = make_optimizer("sgdm")
    sched = schedule.constant(0.05)
    state, _ = init_train_state(
        model, opt, sc_cfg, jax.random.PRNGKey(0), n_workers=2
    )
    loop = TrainLoop(
        model=model, optimizer=opt, schedule=sched, sc_cfg=sc_cfg,
        n_workers=2, **loop_kw,
    )
    return loop, state, make_batches(arch.vocab, 2, 2, 16, seed=0)


def test_run_training_quiet_by_default_and_telemetry(tmp_path, capsys):
    from repro.training import run_training

    loop, state, batches = _tiny_loop(
        _cfg(telemetry=True, warmup_steps=1), log_every=1
    )
    with obs.TelemetryRun(str(tmp_path)) as run:
        _, history = run_training(loop, state, batches, 3, telemetry=run)
        paths = run.close()
    # default log routes to the handler-less repro logger: nothing printed
    assert capsys.readouterr().out == ""
    assert len(history) == 3
    steps = read_events(paths["events"], types=["step"])
    assert len(steps) == 3
    # the obs/ tap leaves ride through the train step's metrics dict
    assert any(k.startswith("obs/") for k in steps[-1]["metrics"])


def test_run_training_spans_each_checkpoint_save(tmp_path):
    from repro.training import run_training

    loop, state, batches = _tiny_loop(
        _cfg(), checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=2
    )
    with obs.TelemetryRun(str(tmp_path / "trace")) as run:
        run_training(loop, state, batches, 5, log=None, telemetry=run)
        names = [s.name for s in run.tracer.spans]
    # saves after steps 2 and 4, each inside the run's span list
    assert names.count("checkpoint") == 2
    assert names.count("step") == 5
    assert sorted(os.listdir(tmp_path / "ckpt"))


def test_train_loop_compiled_is_the_program_step_runs():
    loop, state, batches = _tiny_loop(_cfg(backend="jnp"))
    batch = next(batches)
    compiled = loop.compiled(state, batch, 0)
    assert isinstance(compiled, jax.stages.Compiled)
    text = compiled.as_text()
    for phase in ("fwd_bwd", "reduce", "optimizer"):
        assert f'op_name="jit(train_step)/{phase}/' in text
    # the same program: the compiled step reads the loss the loop's step does
    # (the compiled program donates its state, so it gets a copy)
    _, via_compiled = compiled(jax.tree.map(jnp.copy, state), batch)
    _, via_step = loop.step(state, batch, 0)
    assert float(via_compiled["loss"]) == float(via_step["loss"])
