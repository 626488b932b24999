"""The five per-layer metrics that read the engine's completion clock, its
discarded slot-steps and the two child phases of an admission: through a
traced rehearsal of the serve driver at ``tiny``, against a program
without them, and each file against its ``BENCHMARK.json`` entry."""

import importlib
import json
import os

import pytest

from perfbench import harness
from perfbench.tests.test_runner import run_cell

SERVE_CELLS = [
    "mistral7b-d16-serve.closed16", "falconh1-34b-d6-serve.reason48",
    "pangu-ultra-718b-ep16-d5-serve.reason128", "solar-open2-250b-ep8-d4-serve.reason128",
]
NAMES = [
    "decode_stopped_pct.serve", "device_starved_pct.serve", "slot_discard_pct.serve",
    "prefill_stage_ms.serve", "prefill_launch_ms.serve",
]


def _metric(name):
    return harness.load_json("metrics", name + ".json")


def test_traced_rehearsal_reports_the_clock_the_discards_and_the_two_phases(monkeypatch):
    load_cell = harness.load_cell

    def with_the_new_metrics(cell):
        workload, config, traffic = load_cell(cell)
        # answers of two to four blocks, so that rows retire inside
        # blocks in flight and admissions land under a live window
        output = {**traffic["output"], "median": 24, "sigma": 0.3, "min": 16, "max": 32}
        check = {**workload["check"], "tokens": 32, "lengths": [64]}
        return ({**workload, "per_layer": workload["per_layer"] + NAMES, "check": check},
                config, {**traffic, "output": output})

    monkeypatch.setattr(harness, "load_cell", with_the_new_metrics)
    line = run_cell("tiny.serve", trace=1, seconds=3)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in NAMES:
        assert "rehearsal." + name in got, (name, sorted(got))
    stopped = got["rehearsal.decode_stopped_pct.serve"]
    assert 0 < got["rehearsal.device_starved_pct.serve"] <= stopped < 100
    assert 0 < got["rehearsal.slot_discard_pct.serve"] < 100
    assert got["rehearsal.prefill_stage_ms.serve"] > 0
    assert got["rehearsal.prefill_launch_ms.serve"] > 0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_series_reports_nothing(name):
    """The parent's registry has none of the new series: the metric is
    left out of its line, and nothing raises."""
    m = _metric(name)
    reader = importlib.import_module("perfbench.readers." + m["reader"])
    parent = {"slots": 16, "registry": {
        "engine_decode_steps_total": {"kind": "counter", "series": {"": {"value": 8, "delta": 8}}},
        "engine_request_phase_seconds": {"kind": "histogram", "series": {
            '{phase="prefill"}': {"delta_count": 3, "delta_sum": 0.012}}},
    }}
    assert reader.read(parent, m["params"]) is None
    assert reader.read({}, m["params"]) is None


def test_the_three_shares_are_ratios_of_the_window_s_deltas():
    def counter(delta):
        return {"kind": "counter", "series": {"": {"value": 100 + delta, "delta": delta}}}

    record = {"slots": 16, "registry": {
        "engine_device_decode_seconds_total": counter(28.0),
        "engine_device_prefill_seconds_total": counter(10.0),
        "engine_device_starved_seconds_total": counter(2.0),
        "engine_slot_steps_discarded_total": counter(2400.0),
        "engine_decode_steps_total": counter(2000.0),
    }}

    def read(name):
        m = _metric(name)
        return importlib.import_module("perfbench.readers." + m["reader"]).read(record, m["params"])

    assert read("decode_stopped_pct.serve") == pytest.approx(30.0)
    assert read("device_starved_pct.serve") == pytest.approx(5.0)
    assert read("slot_discard_pct.serve") == pytest.approx(7.5)


@pytest.mark.parametrize("name", NAMES)
def test_metric_file_and_benchmark_entry_agree(name):
    m = _metric(name)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == name]
    assert entry == [{k: m[k] for k in entry[0]}]
    assert m["workloads"] == SERVE_CELLS and m["better"] == "lower"
    assert m["moves"] == "serve_tokens_per_s"
    # accepted readers only: this PR brings no code of the benchmark's
    assert m["reader"] in ("registry_ratio_pct", "registry_mean_ms")
