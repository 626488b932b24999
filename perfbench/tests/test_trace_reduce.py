"""The trace reduction on hand-made events, and on one small recorded
trace: the first 50 ms of a traced step of the train cell on the v5e
(``tests/data/small_trace.json``, written by ``run.py --dump-trace``)."""

import json
import os

import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_merges_overlaps():
    total, merged = tr.union_seconds([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert total == 12 + 11 and merged == [(0, 12), (20, 31)]


def test_self_time_subtracts_children():
    evs = [("while", 0.0, 100.0), ("a", 10.0, 20.0), ("b", 40.0, 50.0), ("c", 200.0, 5.0)]
    st = tr.self_times(evs)
    assert st == {"while": 30.0, "a": 20.0, "b": 50.0, "c": 5.0}


def test_short_keeps_name_shapes_and_kind():
    hlo = ("%fusion.20 = (bf16[4096,32000]{1,0:T(8,128)(2,1)}, f32[4096,32000]{1,0:T(8,128)}) "
           "fusion(bf16[4096,32000]{1,0:T(8,128)(2,1)} %p), kind=kOutput")
    assert tr.short(hlo) == "%fusion.20 = (bf16[4096,32000], f32[4096,32000]) fusion"
    assert tr.short("%copy.3 = f32[8]{0} copy(f32[8]{0} %x)") == "%copy.3 = f32[8] copy"
    assert tr.short("PjitFunction(step)") == "PjitFunction(step)"


def planes():
    dev = {
        tr.OPS_LINE: [("%a.1 = f32[2] fusion(x)", 0.0, 4e6), ("%a.2 = f32[2] fusion(x)", 4e6, 4e6),
                      ("%k = bf16[2] custom-call(x)", 10e6, 2e6)],
        tr.MODULES_LINE: [("jit_step(1)", 0.0, 12e6)],
    }
    host = {"python3": [("pb:next_batch", 8.1e6, 1.8e6), ("other", 0.0, 1e6)]}
    return {"/device:TPU:0": dev, "/host:CPU": host}


def test_summary_of_hand_made_planes():
    s = tr.summarize(planes())
    assert s["chips"] == 1
    assert s["busy_s"] == pytest.approx(10e-3) and s["window_s"] == pytest.approx(12e-3)
    assert s["idle_gaps"] == [["next_batch", pytest.approx(2e-3)]]
    assert s["modules"] == {"jit_step(1)": [1, pytest.approx(12e-3)]}
    # operations that differ only in their number are one line of the breakdown
    assert s["device_ops"][0] == ["%a = f32[2] fusion", pytest.approx(8e-3)]
    assert s["ops"]["%k = bf16[2] custom-call"] == [1, pytest.approx(2e-3)]


def test_no_device_plane_gives_nothing():
    assert tr.summarize({"/host:CPU": {"t": [("x", 0.0, 1.0)]}}) is None


def test_recorded_trace():
    path = os.path.join(HERE, "data", "small_trace.json")
    with open(path) as f:
        recorded = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
                    for p, lines in json.load(f).items()}
    s = tr.summarize(recorded)
    assert s is not None and s["chips"] == 1
    assert 0 < s["busy_s"] <= s["window_s"] <= 0.05
    # self times of one lane add up to its busy time (nothing counted twice)
    lane = recorded["/device:TPU:0"][tr.OPS_LINE]
    assert sum(tr.self_times(lane).values()) * 1e-9 == pytest.approx(s["busy_s"], rel=1e-6)
    assert len(s["device_ops"]) <= 10 and all(len(n) <= 120 for n, _ in s["device_ops"])
