"""Every BENCHMARK.json entry resolves to the files the harness reads."""

import json
import os
import re

import pytest

from chipbench import run

ROOT = run.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("workload", CELLS)
def test_workload_resolves(workload):
    res = run.resolve(workload)
    assert res["cell"]["name"] == workload
    model = res["config"]["model"]
    for key in ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab", "mlp"):
        assert key in model
    assert run.reference_module(res["config"]).Reference
    for key in ("workers", "local_batch", "seq", "compressor", "checked_steps", "trace_steps"):
        assert key in res["mix"]
    assert set(res["limits"]) == set(run.CHECKS)
    assert {m["name"] for m in res["end_to_end"]} >= {"setup_s", "tokens_per_s"}
    assert res["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_a_reader(metric):
    assert callable(run.reader(metric))
    assert run.reader(metric)({"mix": {}, "chips": 1}) is None  # nothing to read


def test_config_files_and_reductions():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        cfg = run.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg["model"] for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))


def test_names_and_references():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [m["name"] for m in METRICS]
    assert all(NAME.match(n) for n in names)
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    configs = {c["name"] for c in SPEC["configs"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("workload", CELLS)
def test_one_learner_per_chip(workload):
    res = run.resolve(workload)
    assert res["mix"]["workers"] == res["cell"]["chips"]


def test_resolve_refuses_workers_other_than_chips(tmp_path):
    spec = dict(SPEC, workloads=[dict(SPEC["workloads"][0], name="stacked", chips=4)])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    traffic = tmp_path / "chipbench" / "traffic"
    traffic.mkdir(parents=True)
    cell = SPEC["workloads"][0]
    (traffic / f"{cell['traffic']}.json").write_text(
        open(os.path.join(ROOT, "chipbench", "traffic", f"{cell['traffic']}.json")).read())
    with pytest.raises(ValueError, match="1 workers for 4 chips"):
        run.resolve("stacked", root=str(tmp_path))
