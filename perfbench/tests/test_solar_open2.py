"""What the delta-rule expert cell adds to the benchmark, on the CPU: the
counts against a hand sum and the seeded leaves, the ``tiny-kda-moe``
rehearsal of the driver (traced: the program-counter metrics read, the
device ones are left out), the control and the nine planted faults (each
has to come out ``correct: false``), the new reader on hand-made records,
and the files against ``BENCHMARK.json`` and the catalog."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import counts_solar_open2 as counts
from perfbench import harness, weights_solar_open2
from perfbench.drivers import serve_solar_open2
from perfbench.readers import kda_hbm_roofline_pct
from perfbench.tests.test_runner import ROOT, check_contract, run_cell
from perfbench.tools import faults_solar_open2

CELL = "solar-open2-250b-ep8-d4-serve.reason128"
CONFIG = "solar-open2-250b-ep8-d4-serve"
PEAK = {"hbm_bytes_per_s": 819e9, "flops": 197e12}


def model_cfg(name):
    return serve_solar_open2.model_keys(harness.load_json("configs", name + ".json"))


@pytest.mark.parametrize("name,total", [(CONFIG, 3_308_376_640), ("tiny-kda-moe", None)])
def test_n_params_is_the_sum_of_the_seeded_leaves(name, total):
    cfg = model_cfg(name)
    leaves = sum(int(np.prod(s)) for _, s, _ in weights_solar_open2.leaf_specs(cfg))
    assert counts.n_params(cfg) == leaves
    if total:
        assert leaves == total


def test_counts_against_a_hand_sum():
    """ISSUE 33's reckoning, number for number."""
    cfg = model_cfg(CONFIG)
    h, c = 4096, 8192
    assert counts.expert_params(cfg) == 3 * h * 1280 == 15_728_640
    assert counts.gqa_matmul_params(cfg) == 3 * h * c + 2 * h * 1024 == 109_051_904
    mixer = (4 * h * c + 2 * (h * 128 + 128 * c) + c + h * 64 + 3 * 4 * c + 64 + c + 128)
    assert counts.linear_matmul_params(cfg) + counts.linear_small_params(cfg) == mixer == 137_740_480
    outside = h * 320 + 15_728_640 + 2 * h
    assert outside == 17_047_552
    assert counts.layer_fixed_params(cfg, True) == 109_051_904 + outside == 126_099_456
    assert counts.layer_fixed_params(cfg, False) == mixer + outside == 154_788_032
    assert counts.n_params(cfg) == (
        4 * 629_145_600 + 126_099_456 + 3 * 154_788_032 + 201_326_592 + 4096) == 3_308_376_640
    # the uncut model: the catalog's 250B
    full = dict(cfg, n_routed_experts=320, num_hidden_layers=48,
                gqa_layers=list(range(0, 48, 4)), vocab_size=196608)
    assert round(counts.n_params(full) / 1e9, 1) == 250.3
    # one held expert of a token's eight: the work done here
    assert counts.held_share(cfg) == 1.0
    per_token = counts.token_matmul_params(cfg)
    assert per_token == (109_051_904 + 3 * 137_625_600 + 4 * (h * 320 + 2 * 15_728_640))
    assert counts.delta_rule_flops_per_token(cfg) == 7 * 64 * 128 * 128
    one = counts.serve_flops(cfg, 100, 1)  # the prompt and one head
    assert one == (2 * 100 * per_token + 2 * h * 24576 + 4 * 5050 * 64 * 128
                   + 3 * 100 * 7 * 64 * 128 * 128)
    two = counts.serve_flops(cfg, 100, 2) - one
    assert two == 2 * per_token + 2 * h * 24576 + 4 * 101 * 64 * 128 + 3 * 7 * 64 * 128 * 128


def test_decode_step_bytes_at_the_published_widths():
    cfg = model_cfg(CONFIG)
    parts = counts.decode_step_parts(cfg, 128 * 1000.0, 128, 4 * 38.4, 4 * 128.0)
    # everything but the banks and the embedding; the four routers in float32
    assert parts["fixed"] == 2 * (3_308_376_640 - 160 * 15_728_640 - 24576 * 4096) + 2 * 4 * 4096 * 320
    assert parts["state"] == 3 * 2 * 128 * 64 * 128 * 128 * 4 == 3_221_225_472
    assert parts["windows"] == 3 * 2 * 128 * 3 * 24576 * 2
    assert parts["kv"] == 128 * 1000 * 2 * 8 * 128 * 2  # one layer holds K/V
    assert parts["experts"] == 4 * 38.4 * 31_457_280 + 4 * 128 * (2 * 4096 + 4 * 1280) * 2
    assert counts.decode_step_bytes(cfg, 128 * 1000.0, 128, 4 * 38.4, 4 * 128.0) == sum(parts.values())
    # a slot of the cache, as the configuration's file states it
    slot = (counts.state_bytes(cfg, 1) + counts.window_bytes(cfg, 1)) * 3 + 3072 * 4096
    assert round(slot / 1e6, 1) == 25.6 and round(128 * slot / 1e9, 2) == 3.28


def test_configuration_holds_the_catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(json.loads(l) for l in f if '"Solar-Open2-250B"' in l)
    cfg = harness.load_json("configs", CONFIG + ".json")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    reduced = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    assert sorted(differs) == sorted(reduced) and cfg["reduced"] == reduced
    assert cfg["published"] == {k: row["config"][k] for k in reduced}
    assert cfg["deployment"]["router_experts"] == row["config"]["n_routed_experts"]
    assert cfg["deployment"]["parameters"] == 3_308_376_640
    # one whole period of the published pattern, from its start
    assert cfg["gqa_layers"] == [l for l in row["config"]["gqa_layers"] if l < 4]
    # the seeded weights' table is repeated under `assumed`, number for number
    for std in weights_solar_open2.STD.values():
        assert str(std) in cfg["assumed"]["weights"], std
    run, mix = cfg["run"], harness.load_json("traffic", "reason128.json")
    assert run["slots"] == mix["clients"] and run["max_seq_len"] == 1024 + 2048
    assert max(run["prompt_widths"]) == mix["prompt"]["max"]


def test_seeded_decays_spread_as_the_weights_say():
    """``A_log`` and ``dt_bias`` as drawn: a position's decays lie between
    about 0.85 and 0.999 before the data moves them."""
    import jax
    import jax.numpy as jnp

    key = weights_solar_open2.seed_key(5)
    A = jnp.exp(weights_solar_open2.make_leaf(key, 1, (64,), "A_log", jnp.float32))
    dt = jax.nn.softplus(weights_solar_open2.make_leaf(key, 2, (8192,), "dt_bias", jnp.float32))
    alpha = np.exp(-np.asarray(A)[:, None] * np.asarray(dt).reshape(64, 128))
    assert 0.9 <= alpha.min() and alpha.max() <= 0.9991
    assert np.percentile(alpha, 5) < 0.96 and np.percentile(alpha, 95) > 0.995


def test_kda_moe_serve_line_meets_the_contract():
    line = run_cell("tiny-kda-moe.serve", seconds=2)
    check_contract(line, ["setup_s", "serve_tokens_per_s", "itl_p95_ms"])
    assert line["correct"] is True and line["failed"] == 0
    kinds = line["notes"]["cache_bytes"]
    assert kinds['{kind="recurrent"}']["value"] > 0 and kinds['{kind="kv"}']["value"] > 0
    assert kinds['{kind="latent"}']["value"] == 0


def test_traced_rehearsal_reads_the_counters_and_leaves_out_the_device():
    line = run_cell("tiny-kda-moe.serve", trace=1, seconds=2)
    got = line["metrics"]
    assert got["rehearsal.moe_expert_load_imbalance_pct.serve"]["value"] >= 0
    assert got["rehearsal.decode_kv_read_pct.serve"]["value"] == 100.0  # the einsum here
    assert not any("roofline" in k or "mfu" in k or "idle" in k for k in got)
    assert line["correct"] is True


@pytest.mark.parametrize("fault", sorted(faults_solar_open2.FAULTS))
def test_planted_fault_comes_out_not_correct(fault):
    with faults_solar_open2.FAULTS[fault]():
        line = run_cell("tiny-kda-moe.serve", seconds=2)
    assert line["correct"] is False and line["failed"] == 0
    assert not all(c["ok"] for c in line["compared"].values())


def test_control_fails_the_limits():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "tools", "limits.py"),
         "--workload", "tiny-kda-moe.serve", "--seeds", "2", "--controls", "2",
         "--seconds", "2", "--rehearse"],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])["summary"]
    limits = harness.load_json("workloads", "tiny-kda-moe.serve.json")["check"]["limits"]
    assert all(summary["program_max"][k] <= v for k, v in limits.items())
    # by one of the limits, not by each: a short sample may share its best tokens
    assert any(summary["control_fp8_min"][k] > v for k, v in limits.items())


def hand_made_record():
    cfg = model_cfg(CONFIG)
    kernel = "%kda_step.5 = (f32[128,64,128], f32[128,64,128,128]) custom-call"
    grouped = "%gmm.7 = bf16[1024,1280] custom-call"
    series = lambda v: {"series": {"": {"delta": v, "value": v}}}  # noqa: E731
    row = 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    return cfg, kernel, grouped, {
        "cfg": cfg, "slots": 128, "peak": PEAK,
        "traced": {"steps": 160, "live_kv_tokens": 128 * 1000.0},
        "registry": {
            "engine_decode_steps_total": series(2000.0),
            "engine_moe_experts_reached_total": series(2000 * 4 * 38.4),
            "engine_moe_local_assignments_total": series(2000 * 4 * 128.0),
            "engine_recurrent_state_bytes_total": series(2000 * 127.0 * row),
        },
        "trace": {
            "modules": {"jit_block(123)": [20, 3.2], "jit_prefill(9)": [3, 0.1]},
            "ops": {kernel: [480, 0.84], grouped: [1920, 1.2],
                    "%fusion.7 = bf16[128,4096] fusion": [160, 0.01]},
        },
    }


def metric_params(name):
    return harness.load_json("metrics", name + ".json")["params"]


def test_new_reader_on_a_hand_made_record():
    cfg, _, _, rec = hand_made_record()
    # 480 kernel calls over 3 linear layers: the trace holds 160 steps,
    # whatever the host counted
    rec["traced"]["steps"] = 999
    got = kda_hbm_roofline_pct.read(rec, metric_params("kda_step_hbm_roofline_pct.serve"))
    assert got == pytest.approx(100 * 160 * 3_221_225_472 / 819e9 / 0.84) and 0 < got < 100
    got = kda_hbm_roofline_pct.read(rec, metric_params("kda_experts_hbm_roofline_pct.serve"))
    need = 160 * counts.experts_step_bytes(cfg, 4 * 38.4, 4 * 128.0)
    assert got == pytest.approx(100 * need / 819e9 / 1.2) and 0 < got < 100
    # the whole step: the recurrent leaves of the 127 live slots, in and out
    got = kda_hbm_roofline_pct.read(rec, metric_params("kda_decode_hbm_roofline_pct.serve"))
    parts = counts.decode_step_parts(cfg, 128_000.0, 128, 4 * 38.4, 4 * 128.0)
    live = (parts["state"] + parts["windows"]) * 127 / 128
    need = 160 * (parts["fixed"] + parts["experts"] + parts["kv"] + live)
    assert got == pytest.approx(100 * need / 819e9 / 3.2) and 0 < got < 100


def test_new_reader_returns_nothing_where_nothing_matches():
    _, kernel, grouped, rec = hand_made_record()
    state = metric_params("kda_step_hbm_roofline_pct.serve")
    experts = metric_params("kda_experts_hbm_roofline_pct.serve")
    step = metric_params("kda_decode_hbm_roofline_pct.serve")
    # another model's record (the accepted cells', traced with these
    # files laid over the parent), an untraced one
    others = [dict(rec, cfg=harness.load_json("configs", n + ".json")) for n in (
        "mistral7b-d16-serve", "falconh1-34b-d6-serve", "pangu-ultra-718b-ep16-d5-serve")]
    for r in (*others, dict(rec, trace=None), dict(rec, traced=None), {}):
        for params in (state, experts, step):
            assert kda_hbm_roofline_pct.read(r, params) is None
    # a program without the counters: the state's share needs none
    bare = dict(rec, registry={})
    assert kda_hbm_roofline_pct.read(bare, state) is not None
    assert kda_hbm_roofline_pct.read(bare, experts) is None
    assert kda_hbm_roofline_pct.read(bare, step) is None
    del rec["registry"]["engine_recurrent_state_bytes_total"]
    assert kda_hbm_roofline_pct.read(rec, step) is None
    del rec["trace"]["ops"][grouped]
    assert kda_hbm_roofline_pct.read(rec, experts) is None
    del rec["trace"]["ops"][kernel]  # no kernel, no steps to count
    assert kda_hbm_roofline_pct.read(rec, state) is None


def test_benchmark_json_lists_the_cell_where_its_metrics_are_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = harness.load_json("workloads", CELL + ".json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    from perfbench import run

    read = {m["name"] for m in run.per_layer_metrics(CELL, workload)}
    new = {"kda_step_hbm_roofline_pct.serve", "kda_decode_hbm_roofline_pct.serve",
           "kda_experts_hbm_roofline_pct.serve"}
    assert listed == read and new <= read and len(read) == 17
    assert {"serve_mfu_pct", "decode_kv_read_pct.serve", "device_idle_pct.serve",
            "moe_expert_load_imbalance_pct.serve"} <= read
    assert not {"decode_hbm_roofline_pct.serve", "hybrid_decode_hbm_roofline_pct.serve",
                "moe_decode_hbm_roofline_pct.serve", "ssm_step_hbm_roofline_pct.serve"} & read
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in new}
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            assert m["layer"] in layers  # a layer the benchmark already names
            on_file = harness.load_json("metrics", m["name"] + ".json")
            assert {k: on_file[k] for k in m} == m
    for name in workload["end_to_end"]:
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "reason128", "chips": 1,
                     "why": workload["why"]} and len(entry["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    on_file = harness.load_json("configs", CONFIG + ".json")
    assert config["file"] == f"perfbench/configs/{CONFIG}.json" and len(config["why"]) <= 200
    assert config["source"] == on_file["source"] and config["reduced"] == on_file["reduced"]
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_limits_lie_between_the_readings():
    """The cell's limits as the workload file states them: above what the
    program read on the chip, below what the float8 control read."""
    check = harness.load_json("workloads", CELL + ".json")["check"]
    assert set(check["limits"]) == {
        "logit_gap", "logprob_diff", "logprob_diff_p50", "logprob_off_pct"}
    assert all(0 < v < 100 for v in check["limits"].values())
    assert "control" in check["readings"] and "PR 33" in check["readings"]
