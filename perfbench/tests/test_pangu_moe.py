"""What the latent-attention expert cell adds to the benchmark, on the CPU:
the counts against a hand sum and the seeded leaves, the ``tiny-latent-moe``
rehearsal of the driver (traced: the program-counter metric reads, the
device ones are left out), the control and the seven planted faults (each
has to come out ``correct: false``), the new readers on hand-made records,
and the files against ``BENCHMARK.json``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import counts_pangu_moe, harness, weights_pangu_moe
from perfbench.drivers import serve_pangu_moe
from perfbench.readers import (
    latent_decode_roofline_pct,
    moe_hbm_roofline_pct,
    registry_imbalance_pct,
)
from perfbench.tests.test_runner import ROOT, check_contract, run_cell
from perfbench.tools import faults_pangu_moe

CELL = "pangu-ultra-718b-ep16-d5-serve.reason128"
CONFIG = "pangu-ultra-718b-ep16-d5-serve"
PEAK = {"hbm_bytes_per_s": 819e9, "flops": 197e12}


def model_cfg(name):
    return serve_pangu_moe.model_keys(harness.load_json("configs", name + ".json"))


@pytest.mark.parametrize("name,total", [(CONFIG, 4_919_139_840), ("tiny-latent-moe", None)])
def test_n_params_is_the_sum_of_the_seeded_leaves(name, total):
    cfg = model_cfg(name)
    leaves = sum(int(np.prod(s)) for _, s, _ in weights_pangu_moe.leaf_specs(cfg))
    assert counts_pangu_moe.n_params(cfg) == leaves
    if total:
        assert leaves == total


def test_counts_against_a_hand_sum():
    """ISSUE 31's reckoning, number for number."""
    cfg = model_cfg(CONFIG)
    h = 7680
    attn = (h * 1536 + 1536 + 1536 * 128 * 192 + h * 576 + 512 + 512 * 128 * 256
            + 128 * 128 * h)
    assert counts_pangu_moe.attention_params(cfg) == attn == 196_577_280
    assert counts_pangu_moe.expert_params(cfg) == 3 * h * 2048 == 47_185_920
    assert counts_pangu_moe.dense_layer_params(cfg) == attn + 3 * h * 18432 + 4 * h == 621_281_280
    assert counts_pangu_moe.expert_layer_params(cfg) == (
        attn + 47_185_920 + h * 256 + 16 * 47_185_920 + 4 * h) == 1_000_734_720
    assert counts_pangu_moe.n_params(cfg) == (
        621_281_280 + 4 * 1_000_734_720 + 2 * 19200 * h + h) == 4_919_139_840
    # the uncut model: the catalog's 718B, without the next-token module
    full = dict(cfg, n_routed_experts=256, router_experts=256, first_k_dense_replace=3,
                num_hidden_layers=61, vocab_size=153600)
    assert counts_pangu_moe.expert_layer_params(full) == 12_325_355_520
    assert round(counts_pangu_moe.n_params(full) / 1e9, 2) == 719.09
    # a cached position a layer: 278,528 FLOPs and 1,152 B, 242 FLOP/B
    assert counts_pangu_moe.latent_position_flops(cfg) == 2 * 128 * (576 + 512) == 278_528
    assert counts_pangu_moe.latent_position_bytes(cfg) == 1152
    assert counts_pangu_moe.prefill_pair_flops(cfg) == 2 * 128 * (192 + 128)
    # half an expert a token a layer: the work done here, never all eight
    assert counts_pangu_moe.held_share(cfg) == 0.5
    per_token = counts_pangu_moe.token_matmul_params(cfg)
    assert per_token == (5 * (attn - 2048) + 3 * h * 18432
                         + 4 * (h * 256 + 1.5 * 47_185_920))
    one = counts_pangu_moe.serve_flops(cfg, 100, 1)  # the prompt and one head
    assert one == 2 * 100 * per_token + 2 * h * 19200 + 5 * 5050 * 81_920
    two = counts_pangu_moe.serve_flops(cfg, 100, 2) - one
    assert two == 2 * per_token + 2 * h * 19200 + 5 * 101 * 278_528


def test_decode_step_bytes_at_the_published_widths():
    cfg = model_cfg(CONFIG)
    fixed = counts_pangu_moe.fixed_step_bytes(cfg)
    # everything but the banks, the embedding's rows not looked up; the
    # four routers in float32
    assert fixed == 2 * (4_919_139_840 - 64 * 47_185_920 - 19200 * 7680) + 2 * 4 * 7680 * 256
    assert fixed == 3_519_298_560
    assert counts_pangu_moe.experts_step_bytes(cfg, 64, 0) == 64 * 94_371_840
    rows = counts_pangu_moe.experts_step_bytes(cfg, 0, 256)
    assert rows == 256 * (2 * 7680 + 4 * 2048) * 2
    step = counts_pangu_moe.decode_step_bytes(cfg, 128 * 1000.0, 62.8, 256)
    assert step == fixed + 62.8 * 94_371_840 + rows + 128 * 1000 * 5 * 1152
    # the kernel at the ridge: the FLOPs of the live positions and their
    # bytes ask the same time to a percent, and the queries in and the
    # contexts out (128 rows x 128 heads x 1,088 values) tip it to the bytes
    least = counts_pangu_moe.latent_call_least_s(cfg, 128_000.0, 128, PEAK)
    flops_s, entries_s = 128_000 * 278_528 / 197e12, 128_000 * 1152 / 819e9
    assert flops_s == pytest.approx(entries_s, rel=0.01)
    assert least == pytest.approx(entries_s + 128 * 128 * 1088 * 2 / 819e9) and least > flops_s


def test_configuration_holds_the_catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(json.loads(l) for l in f if '"openPangu-Ultra-MoE-718B"' in l)
    cfg = harness.load_json("configs", CONFIG + ".json")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    reduced = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert sorted(differs) == sorted(reduced) and cfg["reduced"] == reduced
    assert cfg["published"] == {k: row["config"][k] for k in reduced}
    assert cfg["deployment"]["router_experts"] == row["config"]["n_routed_experts"]
    assert cfg["deployment"]["parameters"] == 4_919_139_840
    # the seeded weights' table is repeated under `assumed`, number for number
    for kind, std in weights_pangu_moe.STD.items():
        assert f"{kind} {std}" in cfg["assumed"]["weights"], kind


def test_traffic_is_the_issues_mix():
    """ISSUE 31's mix, number for number: 128 clients on 128 slots and a
    pool of 512 (prompt, output) pairs in rotation."""
    mix = harness.load_json("traffic", "reason128.json")
    assert (mix["clients"], mix["pool_size"], mix["pool_seed"]) == (128, 512, 20261003)
    assert mix["order"] == "rotate"
    assert mix["prompt"] == {"dist": "lognormal", "median": 384, "sigma": 0.8,
                             "min": 64, "max": 1024}
    assert mix["output"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                             "min": 256, "max": 2048}
    run = harness.load_json("configs", CONFIG + ".json")["run"]
    assert run["slots"] == mix["clients"] and run["max_seq_len"] == 1024 + 2048
    assert max(run["prompt_widths"]) == mix["prompt"]["max"]


def test_every_seed_serves_the_pool_from_its_first_request():
    """A window ends before the pool does, so where the rotation starts
    decides how much work a run does: the driver hands ``serve.drive`` a
    seed under which the accepted generator starts at the pool's first
    request. Lengths and order are then every seed's; tokens differ."""
    from perfbench import traffic

    mix = harness.load_json("traffic", "reason128.json")
    sizes = traffic.request_sizes(mix)
    heads = [tuple(sizes[(i + j) % len(sizes)] for j in range(4)) for i in range(len(sizes))]
    assert heads.count(heads[0]) == 1  # the first four name the start
    seen = []
    for seed in (0, 7, 3290000011, 2**31 + 12345, 2**32 - 1):
        start = serve_pangu_moe.pool_start_seed(mix, seed, 19200)
        assert start % (1 << 32) == seed
        assert start == serve_pangu_moe.pool_start_seed(mix, seed, 19200)
        source = traffic.requests(mix, start, 19200)
        got = [next(source) for _ in range(130)]
        assert [(len(p), o) for p, o in got] == sizes[:130]
        assert all(0 <= t < 19200 for p, _ in got for t in p)
        seen.append(tuple(got[0][0][:8]))
    assert len(set(seen)) == len(seen)  # the seed still decides the tokens
    # a mix that is shuffled keeps its seed
    tiny = harness.load_json("traffic", "tiny-latent-moe-closed.json")
    assert serve_pangu_moe.pool_start_seed(tiny, 9, 100) == 9


def test_latent_moe_serve_line_meets_the_contract():
    line = run_cell("tiny-latent-moe.serve", seconds=2)
    check_contract(line, ["setup_s", "serve_tokens_per_s", "itl_p95_ms"])
    assert line["correct"] is True and line["failed"] == 0
    kinds = line["notes"]["cache_bytes"]
    assert kinds['{kind="latent"}']["value"] > 0 and kinds['{kind="kv"}']["value"] == 0


def test_traced_rehearsal_reads_the_counters_and_leaves_out_the_device():
    line = run_cell("tiny-latent-moe.serve", trace=1, seconds=2)
    got = line["metrics"]
    assert got["rehearsal.moe_expert_load_imbalance_pct.serve"]["value"] >= 0
    assert got["rehearsal.decode_kv_read_pct.serve"]["value"] == 100.0  # the einsum here
    assert not any("roofline" in k or "mfu" in k or "idle" in k for k in got)
    assert line["correct"] is True


@pytest.mark.parametrize("fault", sorted(faults_pangu_moe.FAULTS))
def test_planted_fault_comes_out_not_correct(fault):
    with faults_pangu_moe.FAULTS[fault]():
        line = run_cell("tiny-latent-moe.serve", seconds=2)
    assert line["correct"] is False and line["failed"] == 0
    assert not all(c["ok"] for c in line["compared"].values())


def test_control_fails_the_limits():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "tools", "limits.py"),
         "--workload", "tiny-latent-moe.serve", "--seeds", "2", "--controls", "2",
         "--seconds", "2", "--rehearse"],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])["summary"]
    limits = harness.load_json("workloads", "tiny-latent-moe.serve.json")["check"]["limits"]
    assert all(summary["program_max"][k] <= v for k, v in limits.items())
    assert all(summary["control_fp8_min"][k] > v for k, v in limits.items())


def test_window_notes_say_when_the_tokens_came():
    """Two slots: the second fills at 1.5 s, the chip stands still from 3.0
    to 5.5 s, and a token after the window's end counts nowhere."""
    records = [{"times": [10.5, 11.0, 13.0, 15.5, 16.1]}, {"times": [11.5, 12.0]}, {"times": []}]
    notes = serve_pangu_moe.window_notes(records, 10.0, 16.0, 2)
    assert notes["slots_full_at_s"] == 1.5
    assert notes["longest_silence_ms"] == 2500.0 and notes["longest_silence_at_s"] == 3.0
    assert notes["tokens_per_2s"] == [3, 2, 1]
    assert serve_pangu_moe.window_notes(records, 10.0, 16.0, 3) is None  # never full


def test_the_two_checks_that_a_few_far_off_tokens_cannot_move():
    """A hundredth of the tokens far off (top-8 flips) moves neither; a
    tenth of them does move the share, and all of them a little the
    median."""
    check = {"off_by": 0.15}
    limits = {"logprob_diff_p50": 0.05, "logprob_off_pct": 5.0}
    rng = np.random.default_rng(0)
    base = np.abs(rng.normal(0, 0.02, 5000))
    flips = base.copy()
    flips[:50] = 3.0
    p50, off = serve_pangu_moe.robust_checks(flips, check, limits)
    assert (p50.name, off.name) == ("logprob_diff_p50", "logprob_off_pct")
    assert p50.ok and off.ok and off.value == 1.0 and flips.max() == 3.0
    dropped = base.copy()
    dropped[:600] = 0.4
    p50, off = serve_pangu_moe.robust_checks(dropped, check, limits)
    assert p50.ok and not off.ok and off.value == 12.0
    p50, off = serve_pangu_moe.robust_checks(base + 0.1, check, limits)
    assert not p50.ok
    picked = [{"logprobs": [-1.0, -2.0]}, {"logprobs": [-0.5]}]
    diff = serve_pangu_moe.served_diff(picked, {"logp": np.asarray([-1.25, -2.0, -0.4])})
    assert diff == pytest.approx([0.25, 0.0, 0.1])


def hand_made_record():
    cfg = model_cfg(CONFIG)
    kernel = "%latent_decode_attention.51 = bf16[128,128,512] custom-call"
    grouped = "%gmm.7 = bf16[1024,2048] custom-call"
    series = lambda v: {"series": {"": {"delta": v, "value": v}}}  # noqa: E731
    return cfg, kernel, grouped, {
        "cfg": cfg, "slots": 128, "peak": PEAK,
        "traced": {"steps": 160, "live_kv_tokens": 128 * 1000.0},
        "registry": {
            "engine_decode_steps_total": series(2000.0),
            "engine_moe_experts_reached_total": series(2000 * 62.8),
            "engine_moe_local_assignments_total": series(2000 * 256.0),
            "engine_moe_expert_tokens_total": {"series": {
                '{expert="%d"}' % e: {"delta": 32000.0 + (4000.0 if e == 3 else 0.0)}
                for e in range(16)}},
        },
        "trace": {
            "modules": {"jit_block(123)": [20, 3.2], "jit_prefill(9)": [3, 0.1]},
            "ops": {kernel: [800, 0.4], grouped: [1920, 1.6],
                    "%while.3 = (bf16[128,3072,640]) while": [20, 3.2],
                    "%fusion.7 = bf16[128,7680] fusion": [160, 0.01]},
        },
    }


def metric_params(name):
    return harness.load_json("metrics", name + ".json")["params"]


def test_new_readers_on_a_hand_made_record():
    cfg, _, _, rec = hand_made_record()
    got = latent_decode_roofline_pct.read(rec, metric_params("latent_decode_roofline_pct.serve"))
    least = counts_pangu_moe.latent_call_least_s(cfg, 128_000.0, 128, PEAK)
    assert got == pytest.approx(100 * 800 * least / 0.4) and 0 < got < 100
    # 800 kernel calls over 5 layers: the trace holds 160 steps, whatever
    # the host counted
    rec["traced"]["steps"] = 999
    got = moe_hbm_roofline_pct.read(rec, metric_params("moe_experts_hbm_roofline_pct.serve"))
    need = 160 * counts_pangu_moe.experts_step_bytes(cfg, 62.8, 256.0)
    assert got == pytest.approx(100 * need / 819e9 / 1.6) and 0 < got < 100
    got = moe_hbm_roofline_pct.read(rec, metric_params("moe_decode_hbm_roofline_pct.serve"))
    need = 160 * counts_pangu_moe.decode_step_bytes(cfg, 128_000.0, 62.8, 256.0)
    assert got == pytest.approx(100 * need / 819e9 / 3.2) and 0 < got < 100
    got = registry_imbalance_pct.read(rec, metric_params("moe_expert_load_imbalance_pct.serve"))
    assert got == pytest.approx(100 * (36000 / 32250 - 1))


def test_new_readers_return_nothing_where_nothing_matches():
    _, kernel, grouped, rec = hand_made_record()
    latent = metric_params("latent_decode_roofline_pct.serve")
    experts = metric_params("moe_experts_hbm_roofline_pct.serve")
    step = metric_params("moe_decode_hbm_roofline_pct.serve")
    imbalance = metric_params("moe_expert_load_imbalance_pct.serve")
    # another model's record (the Mistral cells'), an untraced one, a
    # program without the counters (the parent's)
    mistral = dict(rec, cfg=harness.load_json("configs", "mistral7b-d16-serve.json"))
    for r in (mistral, dict(rec, trace=None), dict(rec, registry={})):
        assert moe_hbm_roofline_pct.read(r, experts) is None
        assert moe_hbm_roofline_pct.read(r, step) is None
    assert latent_decode_roofline_pct.read(mistral, latent) is None
    assert latent_decode_roofline_pct.read(dict(rec, trace=None), latent) is None
    assert registry_imbalance_pct.read(dict(rec, registry={}), imbalance) is None
    assert registry_imbalance_pct.read({}, imbalance) is None
    del rec["trace"]["ops"][grouped]
    assert moe_hbm_roofline_pct.read(rec, experts) is None
    del rec["trace"]["ops"][kernel]  # the einsum ran: no kernel, no steps to count
    assert latent_decode_roofline_pct.read(rec, latent) is None
    assert moe_hbm_roofline_pct.read(rec, step) is None


def test_benchmark_json_lists_the_cell_where_its_metrics_are_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = harness.load_json("workloads", CELL + ".json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    from perfbench import run

    read = {m["name"] for m in run.per_layer_metrics(CELL, workload)}
    new = {"latent_decode_roofline_pct.serve", "moe_experts_hbm_roofline_pct.serve",
           "moe_decode_hbm_roofline_pct.serve", "moe_expert_load_imbalance_pct.serve"}
    assert listed == read and new <= read and len(read) == 16
    assert {"serve_mfu_pct", "decode_kv_read_pct.serve", "device_idle_pct.serve"} <= read
    assert not {"decode_hbm_roofline_pct.serve", "hybrid_decode_hbm_roofline_pct.serve"} & read
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            on_file = harness.load_json("metrics", m["name"] + ".json")
            assert {k: on_file[k] for k in m} == m
    for name in workload["end_to_end"]:
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    entry = bench["workloads"][-1]
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "reason128", "chips": 1,
                     "why": workload["why"]} and len(entry["why"]) <= 200
    config = bench["configs"][-1]
    on_file = harness.load_json("configs", CONFIG + ".json")
    assert config["name"] == CONFIG and config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["source"] == on_file["source"] and config["reduced"] == on_file["reduced"]
    assert [w["chips"] for w in bench["workloads"]] == [1, 1, 1, 1]
    assert len(bench["configs"]) == 4
