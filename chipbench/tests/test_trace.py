"""The trace reduction: interval arithmetic on hand-made events, and the whole
reduction on a small trace recorded on a TPU v5e (``data/small.xplane.pb``,
made by ``data/record_small_trace.py``)."""

import os

import pytest

from chipbench import trace

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_merges_overlapping_and_touching():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [(0, 4), (5, 7), (8, 9)]


def test_clip_cuts_to_the_window():
    events = [("a", 0, 10), ("b", 15, 10), ("c", 30, 5), ("d", 2, 0)]
    assert trace.clip(events, 5, 20) == [(5, 10), (15, 20)]


def test_gaps_between_busy_intervals():
    assert trace.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace.gaps([(0, 10)], 0, 10) == []


def test_reduce_on_hand_made_events():
    # chip 0 busy [10, 50) of the window [0, 100), with a kernel [20, 30)
    # overlapping a fusion; chip 1 busy [0, 20) and [60, 100)
    tr = {
        "devices": {
            0: {"ops": [("fusion", 10, 30), ("kernel", 20, 10), ("copy", 40, 10)],
                "kernels": [("kernel", 20, 10)]},
            1: {"ops": [("fusion", -5, 25), ("kernel", 60, 40)],
                "kernels": [("kernel", 60, 40)]},
        },
        "host": [("window", 0, 100), ("wait", 50, 50), ("batch", 0, 10)],
    }
    r = trace.reduce(tr, (0, 100))
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((40 + 60) / 2 * 1e-9)
    assert r["kernel_s"] == pytest.approx((10 + 40) / 2 * 1e-9)
    # self time: chip 0's fusion holds its kernel; chip 1's fusion starts
    # before the window and is left out of the totals
    ops = dict(r["device_ops"])
    assert ops["kernel"] == pytest.approx(25e-9) and ops["fusion"] == pytest.approx(10e-9)
    assert ops["copy"] == pytest.approx(5e-9)
    # chip 0 idles [0, 10) in "batch" and [50, 100) in "wait"; chip 1 [20, 60)
    assert [g[0] for g in r["idle_gaps"]] == ["wait", "wait", "batch"]
    assert r["idle_gaps"][0][1] == pytest.approx(50e-9)


def test_self_times_of_nested_ops():
    events = [("while", 0, 100), ("a", 10, 20), ("b", 15, 5), ("c", 50, 10), ("d", 100, 5)]
    own = {n: t for n, _, _, t in trace.self_times(events)}
    assert own == {"while": 70, "a": 15, "b": 5, "c": 10, "d": 5}


def test_op_names_from_hlo_text():
    name = ('%select_trailing.33 = (s32[296960]{0:T(1024)}, f32[296960]{0:T(1024)}) '
            'custom-call(f32[296960,64]{1,0:T(8,128)} %pad.87), '
            'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert trace.op_kind(name) == "select_trailing" and trace.is_kernel(name)
    fusion = "%add_reduce_fusion.1 = f32[18944000]{0:T(1024)} fusion(f32[1,18944000] %x), kind=kLoop"
    assert trace.op_kind(fusion) == "add_reduce_fusion" and not trace.is_kernel(fusion)


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError, match="no TPU"):
        trace.reduce({"devices": {}, "host": []}, (0, 1))


def test_recorded_tpu_trace():
    events = trace.load(SMALL, ("window", "batch", "dispatch", "wait"))
    assert list(events["devices"]) == [0]
    window = [e for e in events["host"] if e[0] == "window"]
    assert len(window) == 1
    lo, dur = window[0][1], window[0][2]
    r = trace.reduce(events, (lo, lo + dur))
    assert 0 < r["kernel_s"] < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    # as read when the trace was recorded: 3 steps of 8 compressed tensors,
    # 3 Pallas launches each (select, EF update, scatter)
    assert len(events["devices"][0]["kernels"]) == 72
    assert r["device_ops"][0][0] == "select_trailing"
    assert r["kernel_s"] == pytest.approx(269.168e-6)
    assert r["busy_s"] == pytest.approx(584.625e-6)
    assert r["window_s"] == pytest.approx(10829.68e-6)
    assert {g[0] for g in r["idle_gaps"]} <= {"batch", "dispatch", "wait", "none"}
    # the device ops all lie inside the host's window on the same clock
    dev = events["devices"][0]["ops"]
    inside = trace.clip(dev, lo, lo + dur)
    assert len(inside) >= len(dev) // 2


def _op(name, start, dur):
    return (f"%{name} = f32[8]{{0}} op(%x)", start, dur)


# as chipbench.scopes.collective_map gives them
COLLECTIVES = {
    "all-reduce.1": ("sync", "all-reduce.1"),
    "all-reduce-start.2": ("start", "all-reduce-start.2"),
    "all-reduce-done.2": ("done", "all-reduce-start.2"),
    "collective-permute.7": ("sync", "collective-permute.7"),
}


def test_collective_time_and_the_part_no_other_op_hides():
    # chip 0, window [0, 100): a sync all-reduce [0, 10) alone; an async
    # all-reduce from its start at 20 to its done's end at 55, with a multiply
    # [22, 42) under way; a collective-permute [60, 70) inside a while op
    # [60, 90) that also holds a multiply [75, 85): the loop hides nothing.
    # chip 1: an async all-reduce started before the window, done at 8.
    tr = {
        "devices": {
            0: {"ops": [_op("all-reduce.1", 0, 10), _op("all-reduce-start.2", 20, 2),
                        _op("mul.3", 22, 20), _op("all-reduce-done.2", 50, 5),
                        _op("while.9", 60, 30), _op("collective-permute.7", 60, 10),
                        _op("mul.3", 75, 10)], "kernels": []},
            1: {"ops": [_op("all-reduce-start.2", -10, 2), _op("all-reduce-done.2", 5, 3)],
                "kernels": []},
        },
        "host": [],
    }
    r = trace.reduce(tr, (0, 100), collectives=COLLECTIVES)
    assert r["collective_s"] == pytest.approx((10 + 35 + 10 + 8) / 2 * 1e-9)
    assert r["collective_exposed_s"] == pytest.approx((10 + 15 + 10 + 8) / 2 * 1e-9)
    # busy time counts ops alone: not the in-flight gaps [42, 50) and [0, 5)
    assert r["busy_s"] == pytest.approx((10 + 22 + 5 + 30 + 3) / 2 * 1e-9)
    # without the module's collectives there is nothing to read
    r = trace.reduce(tr, (0, 100))
    assert r["collective_s"] == r["collective_exposed_s"] == 0


def test_overlap_of_interval_lists():
    assert trace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert trace.overlap([(0, 10)], []) == 0
    assert trace.overlap([(0, 10)], [(0, 2), (4, 6), (9, 12)]) == 5


def test_recorded_four_chip_trace():
    # four learners, one a chip, at a tiny size (``data/record_four_chip_trace.py``)
    from chipbench import run, scopes

    events = trace.load(os.path.join(os.path.dirname(SMALL), "four_chip.xplane.pb"),
                        run.HOST_SPANS)
    with open(os.path.join(os.path.dirname(SMALL), "four_chip.hlo.txt")) as f:
        hlo = f.read()
    collectives = scopes.collective_map(hlo)
    # as read when the trace was recorded: the leader's indices and the
    # worker mean, two synchronous all-reduces a step, on every chip
    assert sorted(collectives) == ["all-reduce.26", "all-reduce.27"]
    assert list(events["devices"]) == [0, 1, 2, 3]
    for dev in events["devices"].values():
        assert sum(trace.instruction(n) in collectives for n, _, _ in dev["ops"]) == 6
    r = run.traced_record(events, hlo)["trace"]
    assert r["chips"] == 4
    assert 0 < r["collective_exposed_s"] <= r["collective_s"] < r["busy_s"] <= r["window_s"]
    assert r["collective_s"] == pytest.approx(112.084e-6)
    assert r["collective_exposed_s"] == pytest.approx(112.084e-6)
    assert r["busy_s"] == pytest.approx(415.83475e-6)
