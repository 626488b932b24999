"""``decode_kv_read_pct.serve``: what the decode steps fetch of the K/V
cache over what they span, read from the engine's two counters. Through a
traced rehearsal of the serve driver at ``tiny`` (on the CPU the einsum
reads every position: 100), and the reader against hand-made records."""

import json
import os

import pytest

from perfbench import harness
from perfbench.readers import registry_ratio_pct
from perfbench.tests.test_runner import run_cell

NAME = "decode_kv_read_pct.serve"


def _metric():
    with open(os.path.join(harness.ROOT, "perfbench", "metrics", NAME + ".json")) as f:
        return json.load(f)


def test_traced_rehearsal_reads_every_position_on_the_einsum_path(monkeypatch):
    load_cell = harness.load_cell

    def with_the_metric(cell):
        workload, config, traffic = load_cell(cell)
        return {**workload, "per_layer": workload["per_layer"] + [NAME]}, config, traffic

    monkeypatch.setattr(harness, "load_cell", with_the_metric)
    line = run_cell("tiny.serve", trace=1, seconds=2)
    assert line["correct"] is True
    assert line["metrics"]["rehearsal." + NAME]["value"] == pytest.approx(100.0)


def test_reader_on_the_two_counters():
    params = _metric()["params"]

    def registry(read, span):
        return {"registry": {
            "engine_decode_kv_positions_read_total": {"series": {"": {"delta": read}}},
            "engine_decode_kv_positions_span_total": {"series": {"": {"delta": span}}}}}

    assert registry_ratio_pct.read(registry(857.0, 2560.0), params) == pytest.approx(33.4765625)
    # a program without the counters (the parent), or a window without a step
    assert registry_ratio_pct.read({"registry": {"engine_decode_steps_total": {
        "series": {"": {"delta": 8}}}}}, params) is None
    assert registry_ratio_pct.read(registry(0.0, 0.0), params) is None
    assert registry_ratio_pct.read({}, params) is None


def test_metric_file_and_benchmark_entry_agree():
    m = _metric()
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == NAME]
    assert entry == [{k: m[k] for k in entry[0]}]
    assert m["better"] == "lower" and m["layer"] == "cached-decode program"
