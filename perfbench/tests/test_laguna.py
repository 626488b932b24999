"""What the Laguna cell adds to the benchmark, on the CPU: the counts
against a hand sum and the seeded leaves, the ``tiny-laguna`` rehearsal of
the driver (traced: the program-counter metrics read, the device ones are
left out), the planted faults and the reference-side sensitivity (each
fault has to come out ``correct: false``), the new reader on hand-made
records and on records that lack what it reads, and the files against
``BENCHMARK.json``."""

import json
import os

import numpy as np
import pytest

from perfbench import counts_laguna as counts
from perfbench import harness, weights_laguna
from perfbench.drivers import _llama, serve_laguna
from perfbench.readers import laguna_roofline_pct
from perfbench.tests.test_runner import ROOT, check_contract, run_cell
from perfbench.tools import faults_laguna

CELL = "laguna-xs2-d5-serve.long8k"
CONFIG = "laguna-xs2-d5-serve"
NEW = ("laguna_decode_hbm_roofline_pct.serve", "laguna_plane_attention_hbm_roofline_pct.serve",
       "laguna_experts_hbm_roofline_pct.serve", "laguna_prefill_mfu_pct.serve")
PEAK = {"hbm_bytes_per_s": 819e9, "flops": 197e12}


def model_cfg(name):
    return _llama.model_keys(harness.load_json("configs", name + ".json"))


@pytest.mark.parametrize("name,total", [(CONFIG, 3_869_857_792), ("tiny-laguna", None)])
def test_n_params_is_the_sum_of_the_seeded_leaves(name, total):
    cfg = model_cfg(name)
    leaves = sum(int(np.prod(s)) for _, s, _ in weights_laguna.leaf_specs(cfg))
    assert counts.n_params(cfg) == leaves
    if total:
        assert leaves == total == harness.load_json(
            "configs", name + ".json")["deployment"]["parameters"]


def test_counts_against_a_hand_sum():
    """The cell's five layers, reckoned by hand, number for number."""
    cfg = model_cfg(CONFIG)
    h, d, kv = 2048, 128, 8
    full = 2 * h * 48 * d + 2 * h * kv * d + h * 48
    window = 2 * h * 64 * d + 2 * h * kv * d + h * 64
    assert counts.attention_matmul_params(cfg, 0) == full == 29_458_432
    assert counts.attention_matmul_params(cfg, 1) == window == 37_879_808
    expert_layer = 256 * 3 * h * 512 + 3 * h * 512 + h * 256
    assert expert_layer == 808_976_384
    assert counts.n_params(cfg) == (
        full + 3 * h * 8192 + 2 * h + 3 * (window + expert_layer + 2 * h)
        + full + expert_layer + 2 * h + 2 * 100352 * h + h)
    # a token meets 8 experts and the shared one in each sparse layer
    assert counts.token_matmul_params(cfg) == (
        2 * full + 3 * window + 3 * h * 8192 + 4 * (h * 256 + 9 * 3 * h * 512))
    assert counts.slot_cache_bytes(cfg, 14336) == 2 * 14336 * 4096 + 3 * 512 * 4096
    # attention of a 1,000-position prompt: causal in the two full layers,
    # at most 512 keys a query in the three windowed ones
    causal = 1000 * 1001 // 2
    windowed = 512 * 513 // 2 + (1000 - 512) * 512
    assert counts.attention_flops(cfg, [1000], counts.FULL) == 2 * 4 * causal * 48 * d
    assert counts.attention_flops(cfg, [1000], counts.WINDOW) == 3 * 4 * windowed * 64 * d


def test_decode_step_bytes_at_the_published_widths():
    """A step at the mix's mean context of about 8.2k: some 9.4 GB by
    hand (PERF.md section 6), within the precision of that arithmetic."""
    cfg = model_cfg(CONFIG)
    reached = 4 * 256 * (1 - (1 - 8 / 256) ** 48)
    parts = counts.decode_step_parts(cfg, 48 * 2 * 8200, 48 * 3 * 512, reached, 48 * 8 * 4)
    assert parts["experts"] == pytest.approx(5.0e9, rel=0.03)
    assert parts["planes"] == pytest.approx(3.2e9, rel=0.03)
    assert parts["rings"] == pytest.approx(0.3e9, rel=0.03)
    assert parts["fixed"] == pytest.approx(0.9e9, rel=0.1)


def test_laguna_serve_line_meets_the_contract():
    line = run_cell("tiny-laguna.serve", seconds=2)
    check_contract(line, ["setup_s", "serve_tokens_per_s", "itl_p95_ms"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["notes"]["longest_row_compared"] > 8 * 2  # past the ring twice


def test_traced_rehearsal_reads_the_counters_and_leaves_out_the_device():
    """Every metric of the rehearsal that a CPU run can read is there,
    under ``rehearsal.<name>``; those of the device trace, the four new
    ones among them, are left out (no device trace on the CPU)."""
    line = run_cell("tiny-laguna.serve", trace=1, seconds=2)
    got = line["metrics"]
    workload = harness.load_json("workloads", "tiny-laguna.serve.json")
    device = {m for m in workload["per_layer"]
              if harness.load_json("metrics", m + ".json")["source"] == "device_trace"}
    assert set(NEW) <= device
    want = {"rehearsal." + m for m in workload["per_layer"]} - {"rehearsal." + m for m in device}
    assert set(got) == want - {"rehearsal.serve_mfu_pct"}  # a peak, none here
    assert got["rehearsal.decode_kv_read_pct.serve"]["value"] == 100.0
    assert line["correct"] is True


@pytest.mark.parametrize("fault", sorted(faults_laguna.FAULTS))
def test_planted_fault_comes_out_not_correct(fault):
    with faults_laguna.FAULTS[fault]():
        line = run_cell("tiny-laguna.serve", seconds=2)
    assert line["correct"] is False and line["failed"] == 0
    assert not all(c["ok"] for c in line["compared"].values())


def test_each_fault_and_the_control_fail_in_the_reference_alone():
    got = faults_laguna.sensitivity("tiny-laguna.serve", 3, None, sorted(faults_laguna.FAULTS))
    assert set(faults_laguna.IN_REFERENCE) == set(faults_laguna.FAULTS)
    for name in [*faults_laguna.FAULTS, "control_fp8"]:
        assert got[name]["fails_by"], name


def hand_made_record():
    cfg = model_cfg(CONFIG)
    full = "%decode_attention.3 = bf16[48,48,128] custom-call"
    ring = "%decode_attention.5 = bf16[48,64,128] custom-call"
    gmm = "%gmm.2 = bf16[384,512] custom-call"
    gmm_down = "%gmm.4 = bf16[384,2048] custom-call"
    prefill_gmm = "%gmm.9 = bf16[98304,512] custom-call"
    series = lambda v: {"series": {"": {"delta": v, "value": v}}}  # noqa: E731
    kinds = {"series": {'{kind="full"}': {"delta": 1000 * 2 * 48 * 8000.0},
                        '{kind="window"}': {"delta": 1000 * 3 * 48 * 512.0}}}
    return cfg, {
        "cfg": cfg, "slots": 48, "peak": PEAK,
        "traced": {"steps": 120, "live_kv_tokens": 48 * 8000.0,
                   "prefill_flops": {"count": 4, "flops": 4 * 7.6e12}},
        "registry": {
            "engine_decode_steps_total": series(1000.0),
            "engine_decode_kv_positions_read_by_kind_total": kinds,
            "engine_moe_experts_reached_total": series(1000 * 4 * 200.0),
            "engine_moe_local_assignments_total": series(1000 * 4 * 384.0),
        },
        "trace": {
            "modules": {"jit_block(12)": [20, 2.4], "jit_prefill(3)": [5, 0.5],
                        "jit_admit(4)": [5, 0.01]},
            "ops": {full: [320, 0.5], ring: [480, 0.2], gmm: [1280, 0.7],
                    gmm_down: [640, 0.35], prefill_gmm: [40, 0.3]},
        },
    }


def metric_params(name):
    return harness.load_json("metrics", name + ".json")["params"]


def test_new_reader_on_a_hand_made_record():
    """800 decode-attention calls of the 48-row batch over five layers:
    the trace holds 160 steps, whatever the host counted."""
    cfg, rec = hand_made_record()
    rec["traced"]["steps"] = 999
    parts = counts.decode_step_parts(cfg, 2 * 48 * 8000.0, 3 * 48 * 512.0, 800.0, 1536.0)
    got = laguna_roofline_pct.read(rec, metric_params("laguna_decode_hbm_roofline_pct.serve"))
    assert got == pytest.approx(100 * 160 * sum(parts.values()) / 819e9 / 2.4)
    got = laguna_roofline_pct.read(
        rec, metric_params("laguna_plane_attention_hbm_roofline_pct.serve"))
    assert got == pytest.approx(100 * 160 * parts["planes"] / 819e9 / 0.5)
    got = laguna_roofline_pct.read(rec, metric_params("laguna_experts_hbm_roofline_pct.serve"))
    assert got == pytest.approx(100 * 160 * parts["experts"] / 819e9 / 1.05)
    got = laguna_roofline_pct.read(rec, metric_params("laguna_prefill_mfu_pct.serve"))
    assert got == pytest.approx(100 * 5 * 7.6e12 / 197e12 / 0.5)


def test_new_reader_returns_nothing_where_nothing_matches():
    _, rec = hand_made_record()
    params = [metric_params(n) for n in NEW]
    # another model's record (the accepted cells', traced with these files
    # laid over the parent), an untraced one, an empty one
    others = [dict(rec, cfg=model_cfg(n)) for n in (
        "mistral7b-d16-serve", "pangu-ultra-718b-ep16-d5-serve", "phi4-mini-flash-d32-serve")]
    for r in (*others, dict(rec, trace=None), dict(rec, traced=None), {}):
        for p in params:
            assert laguna_roofline_pct.read(r, p) is None
    # a program without the counter by kind (the parent's): no decode share
    bare = dict(rec, registry={k: v for k, v in rec["registry"].items()
                               if k != "engine_decode_kv_positions_read_by_kind_total"})
    for p in params[:3]:
        assert laguna_roofline_pct.read(bare, p) is None
    assert laguna_roofline_pct.read(bare, params[3]) is not None
    no_prefill = dict(rec, traced=dict(rec["traced"], prefill_flops={"count": 0, "flops": 0}))
    assert laguna_roofline_pct.read(no_prefill, params[3]) is None
    rec["trace"]["ops"] = {}  # no kernel, no steps to count
    for p in params[:3]:
        assert laguna_roofline_pct.read(rec, p) is None


def test_prefills_counted_are_those_whose_first_token_came_inside():
    cfg = model_cfg(CONFIG)
    recs = [{"prompt": [0] * 3000, "times": [0.5, 0.6]},
            {"prompt": [0] * 5000, "times": [1.5]},
            {"prompt": [0] * 7000, "times": []}]
    got = serve_laguna.prefill_flops(cfg, recs, 1.0, 2.0)
    assert got == {"count": 1, "flops": counts.prefill_flops(cfg, 5000)}


def test_benchmark_json_lists_the_cell_where_its_metrics_are_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = harness.load_json("workloads", CELL + ".json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    from perfbench import run

    read = {m["name"] for m in run.per_layer_metrics(CELL, workload)}
    assert listed == read and set(NEW) <= read and len(read) == 23
    assert "moe_expert_load_imbalance_pct.serve" in read
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            on_file = harness.load_json("metrics", m["name"] + ".json")
            assert {k: on_file[k] for k in m} == m
    # no metric file of this PR lists an accepted cell
    for name in NEW:
        assert harness.load_json("metrics", name + ".json")["workloads"] == [CELL]
    for name in workload["end_to_end"]:
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    entry = bench["workloads"][-1]
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "long8k", "chips": 1,
                     "why": workload["why"]} and len(entry["why"]) <= 200
    config = bench["configs"][-1]
    on_file = harness.load_json("configs", CONFIG + ".json")
    assert config["file"] == f"perfbench/configs/{CONFIG}.json" and len(config["why"]) <= 200
    assert config["source"] == on_file["source"] and config["reduced"] == on_file["reduced"]
    run_cfg = on_file["run"]
    assert run_cfg["slots"] == 48 and run_cfg["max_seq_len"] == 14336
    assert run_cfg["prompt_widths"] == list(range(2048, 12289, 2048))
