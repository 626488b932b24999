"""What the Falcon-H1 cell adds to the benchmark, on the CPU: the counts
against the seeded leaves, the ``tiny-hybrid`` rehearsal of the driver,
the control and the three planted faults (each has to come out ``correct:
false``), and both new readers on hand-made records."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import counts_falcon_h1, harness, weights_falcon_h1
from perfbench.drivers import _llama
from perfbench.readers import hybrid_decode_hbm_roofline_pct, op_bytes_roofline_pct
from perfbench.tests.test_runner import ROOT, check_contract, run_cell
from perfbench.tools import faults_falcon_h1

CELL = "falconh1-34b-d6-serve.reason48"


def model_cfg(name):
    return _llama.model_keys(harness.load_json("configs", name + ".json"))


@pytest.mark.parametrize("name,total", [
    ("falconh1-34b-d6-serve", 5_254_594_112), ("tiny-hybrid", None)])
def test_n_params_is_the_sum_of_the_seeded_leaves(name, total):
    cfg = model_cfg(name)
    leaves = sum(int(np.prod(s)) for _, s, _ in weights_falcon_h1.leaf_specs(cfg))
    assert counts_falcon_h1.n_params(cfg) == leaves
    if total:
        assert leaves == total and counts_falcon_h1.layer_params(cfg) == 430_120_032


def test_configuration_holds_the_catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(json.loads(l) for l in f if '"Falcon-H1-34B-Instruct"' in l)
    cfg = harness.load_json("configs", "falconh1-34b-d6-serve.json")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
    # the seeded weights' table is repeated under `assumed`, number for number
    for kind, std in weights_falcon_h1.STD.items():
        assert f"{kind.replace('in_proj.', '')} {std}" in cfg["assumed"]["weights"], kind


def test_decode_step_bytes_at_the_published_widths():
    """ISSUE 27's reckoning: 7.83 GB of weights, 2.42 GB of recurrent
    state read and written, 0.44 GB of live K/V at 912 tokens a row."""
    cfg = model_cfg("falconh1-34b-d6-serve")
    zero = counts_falcon_h1.decode_step_bytes(cfg, 0, 0)
    assert zero == 2 * (6 * 430_120_032 + 5120 + 5120 * 261_120) == 7_835_319_424
    state = counts_falcon_h1.decode_step_bytes(cfg, 0, 48) - zero
    assert state == 6 * 2 * 48 * (32 * 128 * 256 * 4 + 3 * 5120 * 2) == 2_433_613_824
    assert state > counts_falcon_h1.ssm_step_bytes(cfg, 48)
    assert counts_falcon_h1.ssm_step_bytes(cfg, 48) == 6 * 2 * 48 * 32 * 128 * 256 * 4
    kv = counts_falcon_h1.decode_step_bytes(cfg, 48 * 912, 48) - zero - state
    assert kv == 48 * 912 * 12_288  # 6 layers x 2 x 4 heads x 128 x 2 B a token
    assert counts_falcon_h1.scan_flops_per_token(cfg, True) == 4 * 4096 * 256 + 2 * 4096
    assert counts_falcon_h1.scan_flops_per_token(cfg, False) == (
        2 * 2 * 256 * 128 + 2 * 4096 * 128 + 4 * 4096 * 256)


def test_hybrid_serve_line_meets_the_contract():
    line = run_cell("tiny-hybrid.serve", seconds=2)
    check_contract(line, ["setup_s", "serve_tokens_per_s", "itl_p95_ms"])
    assert line["correct"] is True and line["failed"] == 0
    kinds = line["notes"]["cache_bytes"]
    assert kinds['{kind="kv"}']["value"] > 0 and kinds['{kind="recurrent"}']["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults_falcon_h1.FAULTS))
def test_planted_fault_comes_out_not_correct(fault):
    with faults_falcon_h1.FAULTS[fault]():
        line = run_cell("tiny-hybrid.serve", seconds=2)
    assert line["correct"] is False and line["failed"] == 0
    assert not all(c["ok"] for c in line["compared"].values())


def test_control_fails_the_limits():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "tools", "limits.py"),
         "--workload", "tiny-hybrid.serve", "--seeds", "2", "--controls", "2",
         "--seconds", "2", "--rehearse"],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])["summary"]
    limits = harness.load_json("workloads", "tiny-hybrid.serve.json")["check"]["limits"]
    assert all(summary["program_max"][k] <= v for k, v in limits.items())
    assert all(summary["control_fp8_min"][k] > v for k, v in limits.items())


def hand_made_record():
    cfg = model_cfg("falconh1-34b-d6-serve")
    state = "%fusion.12 = f32[48,32,128,256] fusion"
    return cfg, state, {
        "cfg": cfg, "slots": 48, "peak": {"hbm_bytes_per_s": 819e9, "flops": 197e12},
        "traced": {"steps": 16, "live_kv_tokens": 48 * 900.0},
        "trace": {
            "modules": {"jit_block(123)": [2, 0.4], "jit_prefill(9)": [3, 0.1]},
            "ops": {state: [96, 0.06], "%while.3 = (f32[48,32,128,256]) while": [2, 0.4],
                    "%fusion.7 = bf16[48,5120] fusion": [96, 0.01]},
        },
    }


def test_new_readers_on_a_hand_made_record():
    cfg, state, rec = hand_made_record()
    got = hybrid_decode_hbm_roofline_pct.read(rec, {"module_pattern": "jit_block"})
    need = 16 * counts_falcon_h1.decode_step_bytes(cfg, 48 * 900.0, 48)
    assert got == pytest.approx(100 * need / 819e9 / 0.4) and 0 < got < 100
    metric = harness.load_json("metrics", "ssm_step_hbm_roofline_pct.serve.json")
    got = op_bytes_roofline_pct.read(rec, metric["params"])
    # the state's fusion alone: not the loop around it, not another result
    assert got == pytest.approx(100 * 16 * 6 * 2 * 201326592 / 819e9 / 0.06)
    assert 0 < got < 100


def test_new_readers_return_nothing_where_nothing_matches():
    _, state, rec = hand_made_record()
    params = harness.load_json("metrics", "ssm_step_hbm_roofline_pct.serve.json")["params"]
    del rec["trace"]["ops"][state]
    assert op_bytes_roofline_pct.read(rec, params) is None
    rec["trace"]["modules"] = {"jit_prefill(9)": [3, 0.1]}
    assert hybrid_decode_hbm_roofline_pct.read(rec, {"module_pattern": "jit_block"}) is None
    # a record of another model (the Mistral cells'), and an untraced one
    mistral = dict(rec, cfg=model_cfg("mistral7b-d16-serve"))
    assert op_bytes_roofline_pct.read(mistral, params) is None
    assert hybrid_decode_hbm_roofline_pct.read(mistral, {"module_pattern": "jit"}) is None
    assert op_bytes_roofline_pct.read(dict(rec, trace=None), params) is None


def test_benchmark_json_lists_the_cell_where_its_metrics_are_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = harness.load_json("workloads", CELL + ".json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    from perfbench import run

    read = {m["name"] for m in run.per_layer_metrics(CELL, workload)}
    assert listed == read and "decode_hbm_roofline_pct.serve" not in read
    assert {"serve_mfu_pct", "hybrid_decode_hbm_roofline_pct.serve",
            "ssm_step_hbm_roofline_pct.serve"} <= read and len(read) == 13
    for name in workload["end_to_end"]:
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    assert [w["chips"] for w in bench["workloads"]] == [1, 1, 1]
    assert len(bench["configs"]) == 3
