"""The six per-layer metrics that read the engine's own account of its
admission cycle: through a traced rehearsal of the serve driver at
``tiny``, and each new reader against a hand-made record."""

import pytest

from perfbench import harness
from perfbench.readers import module_share_pct, registry_total
from perfbench.tests.test_runner import run_cell

FROM_THE_PROGRAM = [
    "prefill_pad_pct.serve", "slot_live_pct.serve", "decode_fallback_step_pct.serve",
    "drain_wait_ms.serve", "warmup_s.serve",
]
FROM_THE_DEVICE = "prefill_device_share_pct.serve"


def test_traced_rehearsal_reports_what_the_program_counts(monkeypatch):
    load_cell = harness.load_cell

    def with_the_new_metrics(cell):
        workload, config, traffic = load_cell(cell)
        listed = workload["per_layer"] + FROM_THE_PROGRAM + [FROM_THE_DEVICE]
        # answers of two to four blocks, so that an admission finds rows
        # still decoding and has to drain (the mix's own answers end
        # inside one block, and a window of discards is dropped unfetched)
        output = {**traffic["output"], "median": 24, "sigma": 0.3, "min": 16, "max": 32}
        check = {**workload["check"], "tokens": 32, "lengths": [64]}
        return ({**workload, "per_layer": listed, "check": check}, config,
                {**traffic, "output": output})

    monkeypatch.setattr(harness, "load_cell", with_the_new_metrics)
    line = run_cell("tiny.serve", trace=1, seconds=3)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in FROM_THE_PROGRAM:
        assert "rehearsal." + name in got, (name, sorted(got))
    # no device plane in a CPU trace: left out, never 0
    assert "rehearsal." + FROM_THE_DEVICE not in got
    assert 0 < got["rehearsal.prefill_pad_pct.serve"] < 100
    assert (got["rehearsal.slot_occupancy_pct.serve"]
            <= got["rehearsal.slot_live_pct.serve"] <= 100)
    assert 0 <= got["rehearsal.decode_fallback_step_pct.serve"] <= 100
    assert got["rehearsal.drain_wait_ms.serve"] > 0
    assert 0 < got["rehearsal.warmup_s.serve"] < line["notes"]["setup_s"]


def test_module_share_is_the_matching_programs_over_every_chips_window():
    trace = {"window_s": 2.0, "chips": 2, "modules": {
        "jit_prefill(7)": [3, 0.5], "jit_admit(9)": [3, 0.1], "jit_block(4)": [10, 3.0]}}
    params = {"module_pattern": "jit_prefill|jit_admit"}
    assert module_share_pct.read({"trace": trace}, params) == pytest.approx(15.0)
    # nothing to read is nothing, never 0
    assert module_share_pct.read({"trace": trace}, {"module_pattern": "jit_chunk"}) is None
    assert module_share_pct.read({"trace": None}, params) is None
    assert module_share_pct.read({}, params) is None


def test_registry_total_reads_the_cumulative_sum_not_the_windows():
    series = {"count": 1, "sum": 12.5, "delta_count": 0, "delta_sum": 0.0}
    record = {"registry": {"engine_warmup_seconds": {"kind": "histogram", "series": {"": series}}}}
    assert registry_total.read(record, {"metric": "engine_warmup_seconds"}) == 12.5
    # a program without the histogram (the parent), or one never observed
    assert registry_total.read({"registry": {}}, {"metric": "engine_warmup_seconds"}) is None
    assert registry_total.read({}, {"metric": "engine_warmup_seconds"}) is None
    empty = {"registry": {"engine_warmup_seconds": {"kind": "histogram", "series": {}}}}
    assert registry_total.read(empty, {"metric": "engine_warmup_seconds"}) is None
