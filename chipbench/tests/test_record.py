"""The record a run hands its metric readers: the work counts of the cells,
a configuration's own FLOP count, and the traced record's split by phase,
read from a trace recorded on a TPU v5e (``data/small_scoped.*``, made by
``data/record_scoped_trace.py``, 3 traced steps). A new configuration and
a new reader are found by name, with no edit of ``chipbench/run.py``."""

import os
import sys
import types

import jax
import pytest

from chipbench import peaks, run, trace
from chipbench.tests import stub_reference
from chipbench.traffic.synthetic import Traffic

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACED_STEPS = 3


def work(res):
    ref = run.reference_module(res["config"])
    params = jax.eval_shape(lambda k: ref.init_params(res["config"]["model"], k),
                            jax.random.PRNGKey(0))
    return run.work(res, params, Traffic(res["mix"], res["config"]["model"]["vocab"], 1))


@pytest.mark.parametrize("workload, flops, reduce_bytes", [
    # 4,096 tokens x (6 x 37,818,368 + 12 x 6 x 8 x 64 x 128)
    ("paper.clt_k.b32s128", 948_751_564_800, 605_683_712),
    ("starcoder2.clt_k.b8s4096", 77_309_411_328_000, 5_487_230_976),
])
def test_work_of_the_one_chip_cells_is_pinned(workload, flops, reduce_bytes):
    w = work(run.resolve(workload))
    assert w["flops_per_step"] == flops
    assert w["reduce_bytes_per_step"] == reduce_bytes
    assert w["model"] == run.resolve(workload)["config"]["model"]


@pytest.fixture(scope="module")
def recorded():
    events = trace.load(os.path.join(DATA, "small_scoped.xplane.pb"), run.HOST_SPANS)
    with open(os.path.join(DATA, "small_scoped.hlo.txt")) as f:
        return run.traced_record(events, f.read())


def test_phases_and_unscoped_time_add_up_to_the_busy_time(recorded):
    rec = dict(recorded, traced_steps=TRACED_STEPS)
    metrics = run.read_metrics(
        [{"name": n, "unit": "ms"} for n in ("fwd_bwd_ms", "reduce_ms", "optimizer_ms")], rec)
    assert len(metrics) == 3 and all(m["value"] > 0 for m in metrics.values())
    spent = sum(m["value"] for m in metrics.values()) + (
        rec["scopes"]["unscoped_s"] / TRACED_STEPS * 1e3)
    busy = rec["trace"]["busy_s"] / TRACED_STEPS * 1e3
    assert spent == pytest.approx(busy, rel=0.02)
    # one chip: no collective, so the exchange's readers find nothing
    assert rec["trace"]["collective_s"] == 0
    assert run.read_metrics([{"name": "collective_ms", "unit": "ms"}], rec) == {}


def test_a_new_configuration_and_reader_need_no_edit_of_the_harness(monkeypatch, recorded):
    monkeypatch.setitem(sys.modules, "chipbench.reference.stub", stub_reference)
    attn = types.ModuleType("chipbench.metrics.attn_ms")
    attn.read = lambda rec: rec["scopes"]["stage_s"]["fwd_bwd/attn"] / rec["traced_steps"] * 1e3
    monkeypatch.setitem(sys.modules, "chipbench.metrics.attn_ms", attn)
    res = run.resolve("paper.clt_k.b32s128")
    res["config"] = dict(res["config"], name="stub", reference="stub")
    rec = {"mix": res["mix"], "peaks": peaks.PEAKS["TPU v5 lite"], "chips": 1,
           **work(res), **recorded, "traced_steps": TRACED_STEPS}
    assert rec["flops_per_step"] == 4096 * 1000.0 * 128
    metrics = run.read_metrics(
        [{"name": "attn_ms", "unit": "ms"}, {"name": "step_mfu", "unit": "%"}], rec)
    assert metrics["attn_ms"]["value"] == pytest.approx(
        recorded["scopes"]["stage_s"]["fwd_bwd/attn"] / TRACED_STEPS * 1e3)
    assert metrics["step_mfu"]["value"] == pytest.approx(
        100 * 4096 * 1000.0 * 128 * TRACED_STEPS / recorded["trace"]["window_s"] / 197e12)
