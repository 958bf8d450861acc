"""The split of device time by the step's named scopes: op_name paths on
hand-made module text and events, every variant of the compiled step on the
CPU, the host markers in a CPU profiler capture, and a small trace recorded
on a TPU v5e with its module's text (``data/small_scoped.xplane.pb`` and
``data/small_scoped.hlo.txt``, made by ``data/record_scoped_trace.py``)."""

import os
import tempfile

import jax
import pytest

from chipbench import scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")

HLO = """\
HloModule jit_train_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(train_step)/reduce/ef_sum/add"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %mul.3 = f32[8]{0} multiply(%gte.1, %gte.1), metadata={op_name="jit(train_step)/fwd_bwd/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/rematted_computation/attn/mul"}
  %gte.0 = s32[] get-tuple-element(%p), index=0
  ROOT %tuple.4 = (s32[], f32[8]{0}) tuple(%gte.0, %mul.3)
}

%relayout_body (q: (u32[], f32[8])) -> (u32[], f32[8]) {
  %q = (u32[], f32[8]{0}) parameter(0)
  %gte.2 = f32[8]{0} get-tuple-element(%q), index=1
  %dynamic-slice.3 = f32[8]{0} dynamic-slice(%gte.2, %gte.2), dynamic_slice_sizes={8}
  %gte.3 = u32[] get-tuple-element(%q), index=0
  ROOT %tuple.6 = (u32[], f32[8]{0}) tuple(%gte.3, %dynamic-slice.3)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state[0]"}
  %ef_sum_fusion = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%ef_sum_fusion)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %select_trailing.33 = (s32[1024]{0}, f32[1024]{0}) custom-call(%copy-done.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/reduce/select/pallas_call"}
  %cosine.1 = f32[8]{0} cosine(%Arg_0.1), metadata={op_name="jit(train_step)/attn/cos"}
  %while.5 = (s32[], f32[8]{0}) while(%cosine.1), condition=%cond, body=%body, metadata={op_name="jit(train_step)/fwd_bwd/vmap(jvp())/while"}
  %constant.2 = f32[] constant(0)
  %reduce.7 = f32[] reduce(%Arg_0.1, %constant.2), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(train_step)/optimizer/reduce_sum"}
  %step.1 = s32[] add(%constant.2, %constant.2), metadata={op_name="jit(train_step)/add"}
  %copy.8 = f32[8]{0} copy(%Arg_0.1)
  %update.1 = f32[8]{0} add(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(train_step)/optimizer/add"}
  %tuple.5 = (u32[], f32[8]{0}) tuple(%step.1, %update.1)
  %while.6 = (u32[], f32[8]{0}) while(%tuple.5), condition=%cond, body=%relayout_body
  %gte.7 = f32[8]{0} get-tuple-element(%while.6), index=1
  ROOT %tuple.9 = (f32[], f32[8]{0}, f32[8]{0}) tuple(%reduce.7, %copy.8, %gte.7)
}
"""


@pytest.mark.parametrize("part, name", [
    ("attn", "attn"),
    ("transpose(jvp(attn))", "attn"),
    ("vmap(jvp())", ""),
    ("jit(_where)", "_where"),
])
def test_component_unwraps_transforms(part, name):
    assert scopes.component(part) == name


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/reduce/ef_update/pallas_call", ("reduce", "ef_update")),
    ("jit(train_step)/fwd_bwd/jvp(vmap())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", ("fwd_bwd", "mlp")),
    ("jit(train_step)/fwd_bwd/vmap(transpose(jvp(loss)))/mul", ("fwd_bwd", "loss")),
    ("jit(train_step)/optimizer/mul", ("optimizer", "other")),
    # the blocks a sparse-expert or latent-attention configuration names
    ("jit(train_step)/fwd_bwd/jvp(vmap())/while/body/closed_call/moe/dot_general",
     ("fwd_bwd", "moe")),
    ("jit(train_step)/fwd_bwd/vmap(transpose(jvp(mla)))/mul", ("fwd_bwd", "mla")),
    # a block's loop-invariant work hoisted out of the step's scopes
    ("jit(train_step)/attn/cos", ("fwd_bwd", "attn")),
    ("jit(train_step)/add", ("unscoped", "other")),
    ("reduce", ("unscoped", "other")),
    ("", ("unscoped", "other")),
])
def test_classify_reads_phase_and_stage(op_name, scope):
    assert scopes.classify(op_name) == scope


def test_scope_map_on_hand_made_module():
    m = scopes.scope_map(HLO)
    # every instruction of every computation, whether or not it ever runs
    assert {"add.1", "add.2", "mul.3", "tuple.4", "Arg_0.1", "tuple.9"} <= set(m)
    # a fusion without metadata takes its fused computation's root's
    assert m["ef_sum_fusion"] == ("reduce", "ef_sum")
    # an op inside a loop body keeps the scopes autodiff and remat wrap
    assert m["mul.3"] == ("fwd_bwd", "attn")
    assert m["while.5"] == ("fwd_bwd", "other")
    assert m["select_trailing.33"] == ("reduce", "select")
    assert m["cosine.1"] == ("fwd_bwd", "attn")
    # ops XLA made without metadata take their first scoped user's
    assert m["copy-start.1"] == m["copy-done.1"] == ("reduce", "select")
    assert m["constant.2"] == ("optimizer", "other")
    # ... else the loop's that runs them, else their first scoped operand's:
    # XLA's relayout loop of the optimizer's output
    assert m["dynamic-slice.3"] == m["while.6"] == m["gte.7"] == ("optimizer", "other")
    # a named argument, a parameter and a reducer's region place nothing
    assert m["Arg_0.1"] == m["copy.8"] == m["q"] == ("unscoped", "other")
    assert m["step.1"] == ("unscoped", "other")
    assert m["add.2"] == ("unscoped", "other")


COLLECTIVE_HLO = """\
HloModule jit_train_step, is_scheduled=true

%async_computation (p: f32[8]) -> f32[2] {
  %p = f32[8]{0} parameter(0)
  ROOT %reduce-scatter.1 = f32[2]{0} reduce-scatter(%p), dimensions={0}, to_apply=%add
}

%gather_fusion (q: f32[2]) -> f32[8] {
  %q = f32[2]{0} parameter(0)
  ROOT %all-gather.2 = f32[8]{0} all-gather(%q), dimensions={0}
}

%fused_add (r: f32[8]) -> f32[8] {
  %r = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%r, %r)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %all-reduce.1 = f32[8]{0} all-reduce(%x), channel_id=1, to_apply=%add
  %all-reduce-start.2 = f32[8]{0} all-reduce-start(%all-reduce.1), to_apply=%add
  %mul.3 = f32[8]{0} multiply(%x, %x)
  %all-reduce-done.2 = f32[8]{0} all-reduce-done(%all-reduce-start.2)
  %async-start.4 = ((f32[8]{0}), f32[2]{0}, u32[]) async-start(%mul.3), calls=%async_computation
  %async-update.4 = ((f32[8]{0}), f32[2]{0}, u32[]) async-update(%async-start.4), calls=%async_computation
  %async-done.4 = f32[2]{0} async-done(%async-update.4), calls=%async_computation
  %fusion.5 = f32[8]{0} fusion(%async-done.4), kind=kCustom, calls=%gather_fusion
  %all-reduce-fusion.6 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_add
  %collective-permute.7 = f32[8]{0} collective-permute(%x), source_target_pairs={{0,1}}
  ROOT %tuple.8 = (f32[8]{0}, f32[8]{0}) tuple(%fusion.5, %collective-permute.7)
}
"""


def test_collective_map_reads_opcodes_not_names():
    m = scopes.collective_map(COLLECTIVE_HLO)
    assert m["all-reduce.1"] == ("sync", "all-reduce.1")
    assert m["all-reduce-start.2"] == ("start", "all-reduce-start.2")
    assert m["all-reduce-done.2"] == ("done", "all-reduce-start.2")
    # a generic async pair around a collective, through its update
    assert m["async-start.4"] == ("start", "async-start.4")
    assert m["async-done.4"] == ("done", "async-start.4")
    assert "async-update.4" not in m
    # a fusion of a collective is one; a fusion named like one is not
    assert m["fusion.5"] == ("sync", "fusion.5")
    assert "all-reduce-fusion.6" not in m
    assert m["collective-permute.7"] == ("sync", "collective-permute.7")
    assert not {"x", "mul.3", "tuple.8", "add.3"} & set(m)


@pytest.mark.parametrize("line, op", [
    ("  %a = f32[8]{0:T(1024)} all-reduce(%x), to_apply=%r", "all-reduce"),
    ("  %b = (f32[8]{0}, u32[]) all-reduce-start(%x)", "all-reduce-start"),
    ("  ROOT %c = f32[8,128]{1,0:T(8,128)} fusion(%x), kind=kLoop", "fusion"),
])
def test_opcode_of_an_instruction_line(line, op):
    assert scopes.opcode(line) == op


def _event(instruction, start, dur):
    return (f"%{instruction} = f32[8]{{0}} op()", start, dur)


def test_scope_times_with_a_while_op_nesting_its_body():
    # chip 0: the while op [0, 100) holds its body's multiply [10, 40) and
    # a kernel [50, 70); chip 1 runs the same ops 10 later; the copy at 190
    # on chip 0 ends outside the window [0, 200)
    ops = [_event("while.5", 0, 100), _event("mul.3", 10, 30),
           _event("select_trailing.33", 50, 20), _event("reduce.7", 110, 10),
           _event("copy.8", 130, 5)]
    tr = {
        "devices": {
            0: {"ops": ops + [_event("copy.8", 190, 20)], "kernels": []},
            1: {"ops": [(n, s + 10, d) for n, s, d in ops], "kernels": []},
        },
        "host": [],
    }
    r = scopes.scope_times(tr, (0, 200), scopes.scope_map(HLO))
    assert r["scope_s"] == pytest.approx(
        {"fwd_bwd": 80e-9, "reduce": 20e-9, "optimizer": 10e-9})
    assert r["stage_s"] == pytest.approx({
        "fwd_bwd/other": 50e-9, "fwd_bwd/attn": 30e-9,
        "reduce/select": 20e-9, "optimizer/other": 10e-9,
    })
    assert r["unscoped_s"] == pytest.approx(5e-9)
    assert r["unscoped_ops"] == [["copy", pytest.approx(5e-9)]]
    # phases and unscoped ops add up to the self time of the ops inside
    inside = [
        own for chip in tr["devices"].values()
        for _, s, d, own in trace.self_times(chip["ops"]) if s >= 0 and s + d <= 200
    ]
    assert sum(r["scope_s"].values()) + r["unscoped_s"] == pytest.approx(
        sum(inside) / 2 * 1e-9)


def test_scope_times_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError, match="no TPU"):
        scopes.scope_times({"devices": {}, "host": []}, (0, 1), {})


# ---------------------------------------------------------------------------
# the compiled step on the CPU
# ---------------------------------------------------------------------------


def _tiny_loop(**sc):
    from repro.configs import registry
    from repro.core.compressors import CompressorConfig
    from repro.core.scalecom import ScaleComConfig
    from repro.data import make_batches
    from repro.models import build_model
    from repro.optim import make_optimizer, schedule
    from repro.training import TrainLoop, init_train_state

    buckets = sc.pop("buckets", False)
    arch = registry.smoke("paper-transformer-base")
    model = build_model(arch, compute_dtype="float32", loss_chunk=16)
    sc_cfg = ScaleComConfig(
        compressor=CompressorConfig("clt_k", chunk=16), beta=0.1, min_size=512,
        **{"backend": "jnp", "fused": False, "layout": "flat", **sc},
    )
    opt = make_optimizer("sgdm")
    state, _ = init_train_state(model, opt, sc_cfg, jax.random.PRNGKey(0), n_workers=2)
    loop = TrainLoop(model=model, optimizer=opt, schedule=schedule.constant(0.05),
                     sc_cfg=sc_cfg, n_workers=2, buckets=buckets)
    return loop, state, next(make_batches(arch.vocab, 2, 2, 16, seed=0))


@pytest.mark.parametrize("variant, stages", [
    ({}, {"select", "ef_update", "worker_mean", "scatter"}),
    ({"fused": True}, {"fused"}),
    ({"layout": "rowwise"}, {"select", "ef_update", "worker_mean", "scatter"}),
    ({"buckets": 1024}, {"select", "ef_update", "worker_mean", "scatter"}),
], ids=["three_launch", "fused", "rowwise", "bucketed"])
def test_compiled_step_is_covered_by_phases(variant, stages):
    loop, state, batch = _tiny_loop(**variant)
    text = loop.compiled(state, batch, 0).as_text()
    m = scopes.scope_map(text)
    work = [
        line for line in text.splitlines()
        if scopes._INSTRUCTION.match(line)
        and scopes.opcode(line) in ("fusion", "dot", "custom-call", "reduce", "scatter", "while")
    ]
    assert work
    for line in work:
        assert m[scopes._INSTRUCTION.match(line).group(1)][0] in scopes.PHASES, line
    seen = {stage for phase, stage in m.values() if phase == "reduce"}
    assert stages <= seen, seen
    blocks = {stage for phase, stage in m.values() if phase == "fwd_bwd"}
    assert {"embed", "attn", "mlp", "loss"} <= blocks


def test_profiler_capture_puts_train_step_inside_dispatch():
    from repro.obs import Tracer

    loop, state, batch = _tiny_loop()
    state, metrics = loop.step(state, batch, 0)
    jax.block_until_ready(metrics)
    tracer = Tracer()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            for i in (1, 2):
                with tracer.span("operator"), jax.profiler.TraceAnnotation("dispatch"):
                    state, metrics = loop.step(state, batch, i)
                jax.block_until_ready(metrics)
        finally:
            jax.profiler.stop_trace()
        events = trace.load(trace.find_xplane(tmp), ("dispatch", "train_step", "operator"))
    spans = {name: [e for e in events["host"] if e[0] == name]
             for name in ("dispatch", "train_step", "operator")}
    assert [len(v) for v in spans.values()] == [2, 2, 2]

    def within(inner, outer):
        return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]

    # one clock: each step marker inside a dispatch, inside the Tracer's span
    for dispatch, step, span in zip(*(sorted(v, key=lambda e: e[1]) for v in spans.values())):
        assert within(step, dispatch) and within(dispatch, span)


# ---------------------------------------------------------------------------
# a trace recorded on a TPU v5e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    events = trace.load(os.path.join(DATA, "small_scoped.xplane.pb"), ("window",))
    with open(os.path.join(DATA, "small_scoped.hlo.txt")) as f:
        smap = scopes.scope_map(f.read())
    window = [e for e in events["host"] if e[0] == "window"]
    assert len(window) == 1
    lo, hi = window[0][1], window[0][1] + window[0][2]
    return events, smap, (lo, hi)


def test_recorded_scoped_trace_phases_cover_the_busy_time(recorded):
    events, smap, window = recorded
    busy = trace.reduce(events, window)["busy_s"]
    r = scopes.scope_times(events, window, smap)
    assert set(r["scope_s"]) == set(scopes.PHASES)
    assert sum(r["scope_s"].values()) >= 0.9 * busy
    assert sum(r["scope_s"].values()) + r["unscoped_s"] <= busy * (1 + 1e-9)
    stages = {k.split("/")[1] for k in r["stage_s"]}
    assert {"select", "ef_update", "scatter", "attn", "mlp", "loss"} <= stages


def test_recorded_scoped_trace_reduce_kernels_are_the_kernel_time(recorded):
    events, smap, window = recorded
    lo, hi = window
    kernel_s = trace.reduce(events, window)["kernel_s"]
    assert kernel_s > 0
    in_reduce = [
        own for name, start, dur, own in trace.self_times(events["devices"][0]["ops"])
        if trace.is_kernel(name) and lo <= start and start + dur <= hi
        and smap[trace.instruction(name)][0] == "reduce"
    ]
    assert sum(in_reduce) * 1e-9 == pytest.approx(kernel_s, rel=1e-9)
