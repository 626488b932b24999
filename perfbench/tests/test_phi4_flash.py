"""What the SambaY cell adds to the benchmark, on the CPU: the counts
against a hand sum and the seeded leaves, the ``tiny-sambay`` rehearsal
of the driver (traced: the program-counter metrics read, the device ones
are left out), the control and the planted faults (each has to come out
``correct: false``), the new reader on hand-made records, and the files
against ``BENCHMARK.json`` and the catalog."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import counts_phi4_flash as counts
from perfbench import harness, weights_phi4_flash
from perfbench.drivers import _llama, serve_phi4_flash
from perfbench.readers import sambay_roofline_pct
from perfbench.tests.test_runner import ROOT, check_contract, run_cell
from perfbench.tools import faults_phi4_flash

CELL = "phi4-mini-flash-d32-serve.reason128"
CONFIG = "phi4-mini-flash-d32-serve"
NEW = ("sambay_decode_hbm_roofline_pct.serve", "selective_scan_roofline_pct.serve",
       "mamba_step_hbm_roofline_pct.serve")
PEAK = {"hbm_bytes_per_s": 819e9, "flops": 197e12}


def model_cfg(name):
    return _llama.model_keys(harness.load_json("configs", name + ".json"))


@pytest.mark.parametrize("name,total", [(CONFIG, 3_852_457_984), ("tiny-sambay", None)])
def test_n_params_is_the_sum_of_the_seeded_leaves(name, total):
    cfg = model_cfg(name)
    leaves = sum(int(np.prod(s)) for _, s, _ in weights_phi4_flash.leaf_specs(cfg))
    assert counts.n_params(cfg) == leaves
    if total:
        assert leaves == total == harness.load_json(
            "configs", name + ".json")["deployment"]["parameters"]


def test_counts_against_a_hand_sum():
    """The published configuration's parameters, reckoned by hand, number
    for number."""
    cfg = model_cfg(CONFIG)
    h, c, f = 2560, 5120, 10240
    mlp, norms = 3 * h * f, 4 * h
    assert counts.layer_kinds(cfg) == ["mamba", "swa"] * 8 + ["mamba", "full"] + ["gmu", "cross"] * 7
    mixer = h * 2 * c + c * 192 + 160 * c + c * h + 4 * c + c + c + 16 * c + c
    assert counts.layer_params(cfg, "mamba") == mixer + mlp + norms == 119_895_040
    assert counts.layer_params(cfg, "swa") == counts.layer_params(cfg, "full") == (
        2 * h * h + 2 * h * 1280 + 6 * 64 + mlp + norms) == 98_314_624
    assert counts.layer_params(cfg, "gmu") == 2 * h * c + mlp + norms == 104_867_840
    assert counts.layer_params(cfg, "cross") == 2 * h * h + 6 * 64 + mlp + norms == 91_761_024
    assert counts.n_params(cfg) == (
        9 * 119_895_040 + 9 * 98_314_624 + 7 * 104_867_840 + 7 * 91_761_024
        + 200064 * h + 2 * h) == 3_852_457_984
    self_mm = counts.token_matmul_params(cfg, "self")
    cross_mm = counts.token_matmul_params(cfg, "cross")
    assert self_mm == 9 * (41_123_840 + mlp) + 9 * (19_660_800 + mlp)
    assert cross_mm == 7 * (26_214_400 + mlp) + 7 * (13_107_200 + mlp)
    one = counts.serve_flops(cfg, 100, 1)  # the prompt and one head
    att = lambda pairs: 4 * pairs * 40 * 64  # noqa: E731
    assert one == (2 * 100 * self_mm + 2 * cross_mm + 2 * h * 200064
                   + 8 * att(5050) + att(5050) + 7 * att(100) + 9 * 100 * 7 * c * 16)
    two = counts.serve_flops(cfg, 100, 2) - one
    assert two == (2 * self_mm + 2 * cross_mm + 2 * h * 200064 + 8 * att(101) + att(101)
                   + 7 * att(101) + 9 * 7 * c * 16)


def test_decode_step_bytes_at_the_published_widths():
    cfg = model_cfg(CONFIG)
    parts = counts.decode_step_parts(cfg, 128 * 1000.0, 128 * 480.0, 2.5e9)
    assert parts["fixed"] == 2 * 3_852_457_984  # 7.70 GB, the head included
    assert parts["plane"] == 128 * 1000 * 5120 * 8  # the full layer and 7 cross layers
    assert parts["rings"] == 128 * 480 * 5120 * 8
    assert parts["recurrent"] == 5e9
    assert counts.mamba_step_bytes(cfg, 128) == 9 * 2 * 128 * 5120 * 16 * 4
    flops, moved = counts.scan_call(cfg, 1, 1024)
    assert flops == 1024 * 7 * 5120 * 16
    assert moved == 1024 * 5120 * 10 + 1024 * 32 * 4 + 2 * 16 * 5120 * 4


def test_configuration_holds_the_catalog_row():
    """Against the model catalog (a JSON-lines file) where ``MODEL_CATALOG``
    names one; the seeded weights and the run's sizes always."""
    cfg = harness.load_json("configs", CONFIG + ".json")
    assert cfg["reduced"] == []
    path = os.environ.get("MODEL_CATALOG", "")
    if path and os.path.exists(path):
        with open(path) as f:
            row = next(json.loads(l) for l in f if '"Phi-4-mini-flash-reasoning"' in l)
        assert cfg["source"] == row["source_url"]
        assert all(cfg[k] == v for k, v in row["config"].items())
    # the seeded weights' table is repeated under `assumed`, number for number
    for std in weights_phi4_flash.STD.values():
        assert str(std) in cfg["assumed"]["weights"], std
    run, mix = cfg["run"], harness.load_json("traffic", "reason128.json")
    assert run["slots"] == mix["clients"] and run["max_seq_len"] == 1024 + 2048
    assert max(run["prompt_widths"]) == mix["prompt"]["max"]


def test_seeded_scan_spreads_as_the_weights_say():
    """``A_log`` S4D-real and ``dt_bias`` as drawn: a step decays a state
    by between about 0.2 and 0.999 before the data moves it."""
    import jax
    import jax.numpy as jnp

    key = weights_phi4_flash.seed_key(5)
    A = np.exp(np.asarray(weights_phi4_flash.make_leaf(key, 1, (5120, 16), "A_log", jnp.float32)))
    dt = np.asarray(jax.nn.softplus(
        weights_phi4_flash.make_leaf(key, 2, (5120,), "dt_bias", jnp.float32)))
    assert 0.0009 < dt.min() and dt.max() < 0.11
    decay = np.exp(-dt[:, None] * A)
    assert decay.max() > 0.998 and decay.min() < 0.25


def test_sambay_serve_line_meets_the_contract():
    line = run_cell("tiny-sambay.serve", seconds=2)
    check_contract(line, ["setup_s", "serve_tokens_per_s", "itl_p95_ms"])
    assert line["correct"] is True and line["failed"] == 0
    kinds = line["notes"]["cache_bytes"]
    assert kinds['{kind="recurrent"}']["value"] > 0 and kinds['{kind="kv"}']["value"] > 0


def test_traced_rehearsal_reads_the_counters_and_leaves_out_the_device():
    """Every metric of the rehearsal that a CPU run can read is there,
    under ``rehearsal.<name>``; those of the device trace are left out."""
    line = run_cell("tiny-sambay.serve", trace=1, seconds=2)
    got = line["metrics"]
    workload = harness.load_json("workloads", "tiny-sambay.serve.json")
    device = {m for m in workload["per_layer"]
              if harness.load_json("metrics", m + ".json")["source"] == "device_trace"}
    assert set(NEW) <= device
    want = {"rehearsal." + m for m in workload["per_layer"]} - {"rehearsal." + m for m in device}
    assert set(got) == want - {"rehearsal.serve_mfu_pct"}  # a peak, none here
    # two rings of 8 read whole, the plane of 64 too (the einsum here)
    assert got["rehearsal.decode_kv_read_pct.serve"]["value"] == 100.0
    assert 0 < got["rehearsal.prefill_kv_scored_pct.serve"]["value"] < 100
    assert line["correct"] is True


MUST_FAIL = sorted(set(faults_phi4_flash.FAULTS) - {"state_bfloat16"})


@pytest.mark.parametrize("fault", MUST_FAIL)
def test_planted_fault_comes_out_not_correct(fault):
    with faults_phi4_flash.FAULTS[fault]():
        line = run_cell("tiny-sambay.serve", seconds=2)
    assert line["correct"] is False and line["failed"] == 0
    assert not all(c["ok"] for c in line["compared"].values())


def test_control_fails_the_limits():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "tools", "limits.py"),
         "--workload", "tiny-sambay.serve", "--seeds", "2", "--controls", "2",
         "--seconds", "2", "--rehearse"],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])["summary"]
    limits = harness.load_json("workloads", "tiny-sambay.serve.json")["check"]["limits"]
    assert all(summary["program_max"][k] <= v for k, v in limits.items())
    # by one of the limits, not by each: a short sample may share its best tokens
    assert any(summary["control_fp8_min"][k] > v for k, v in limits.items())


def hand_made_record():
    cfg = model_cfg(CONFIG)
    kernel = "%decode_attention.3 = bf16[128,48,128] custom-call"
    prefill_kernel = "%decode_attention.9 = bf16[1,48,128] custom-call"
    state = "%fusion.12 = (f32[128,5120], f32[128,16,5120]) fusion"
    scan = "%selective_scan.2 = (f32[1,1024,5120], f32[1,16,5120]) custom-call"
    series = lambda v: {"series": {"": {"delta": v, "value": v}}}  # noqa: E731
    row = 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    return cfg, kernel, state, scan, {
        "cfg": cfg, "slots": 128, "peak": PEAK,
        "traced": {"steps": 160, "live_kv_tokens": 128 * 1000.0,
                   "live_window_tokens": 128 * 480.0},
        "registry": {
            "engine_decode_steps_total": series(2000.0),
            "engine_recurrent_state_bytes_total": series(2000 * 127.0 * row),
        },
        "trace": {
            "modules": {"jit_block(123)": [20, 3.2], "jit_prefill(9)": [3, 0.1]},
            "ops": {kernel: [2560, 0.6], prefill_kernel: [24, 0.001], state: [1440, 0.4],
                    scan: [27, 0.01], "%fusion.7 = bf16[128,2560] fusion": [160, 0.01]},
        },
    }


def metric_params(name):
    return harness.load_json("metrics", name + ".json")["params"]


def test_new_reader_on_a_hand_made_record():
    cfg, _, _, _, rec = hand_made_record()
    # 2560 kernel calls of the decode steps over the 8 rings, the full
    # layer and the 7 cross layers: the trace holds 160 steps, whatever
    # the host counted
    rec["traced"]["steps"] = 999
    got = sambay_roofline_pct.read(rec, metric_params("mamba_step_hbm_roofline_pct.serve"))
    assert got == pytest.approx(100 * 160 * 9 * 2 * 128 * 5120 * 16 * 4 / 819e9 / 0.4)
    assert 0 < got < 100
    got = sambay_roofline_pct.read(rec, metric_params("sambay_decode_hbm_roofline_pct.serve"))
    row = 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    parts = counts.decode_step_parts(cfg, 128_000.0, 128 * 480.0, 127.0 * row)
    assert got == pytest.approx(100 * 160 * sum(parts.values()) / 819e9 / 3.2)
    assert 0 < got < 100
    got = sambay_roofline_pct.read(rec, metric_params("selective_scan_roofline_pct.serve"))
    flops, moved = counts.scan_call(cfg, 1, 1024)
    assert got == pytest.approx(100 * 27 * max(flops / 197e12, moved / 819e9) / 0.01)
    assert 0 < got < 100


def test_new_reader_returns_nothing_where_nothing_matches():
    _, kernel, state, scan, rec = hand_made_record()
    params = [metric_params(n) for n in NEW]
    # another model's record (the accepted cells', traced with these
    # files laid over the parent), an untraced one
    others = [dict(rec, cfg=harness.load_json("configs", n + ".json")) for n in (
        "mistral7b-d16-serve", "falconh1-34b-d6-serve", "solar-open2-250b-ep8-d4-serve")]
    for r in (*others, dict(rec, trace=None), dict(rec, traced=None), {}):
        for p in params:
            assert sambay_roofline_pct.read(r, p) is None
    decode, scan_p, step = params
    # a program without the counters: only the whole step needs them
    bare = dict(rec, registry={})
    assert sambay_roofline_pct.read(bare, decode) is None
    assert sambay_roofline_pct.read(bare, step) is not None
    assert sambay_roofline_pct.read(bare, scan_p) is not None
    del rec["trace"]["ops"][scan]
    assert sambay_roofline_pct.read(rec, scan_p) is None
    del rec["trace"]["ops"][kernel]  # no kernel, no steps to count
    assert sambay_roofline_pct.read(rec, step) is None
    assert sambay_roofline_pct.read(rec, decode) is None


def test_benchmark_json_lists_the_cell_where_its_metrics_are_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = harness.load_json("workloads", CELL + ".json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    from perfbench import run

    read = {m["name"] for m in run.per_layer_metrics(CELL, workload)}
    assert listed == read and set(NEW) <= read and len(read) == 21
    assert {"serve_mfu_pct", "decode_kv_read_pct.serve", "device_idle_pct.serve",
            "prefill_kv_scored_pct.serve"} <= read
    assert not {"decode_hbm_roofline_pct.serve", "hybrid_decode_hbm_roofline_pct.serve",
                "ssm_step_hbm_roofline_pct.serve", "kda_step_hbm_roofline_pct.serve"} & read
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            on_file = harness.load_json("metrics", m["name"] + ".json")
            assert {k: on_file[k] for k in m} == m
    assert {"cached-decode program", "recurrent-state update"} <= layers
    for name in workload["end_to_end"]:
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "reason128", "chips": 1,
                     "why": workload["why"]} and len(entry["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    on_file = harness.load_json("configs", CONFIG + ".json")
    assert config["file"] == f"perfbench/configs/{CONFIG}.json" and len(config["why"]) <= 200
    assert config["source"] == on_file["source"] and config["reduced"] == on_file["reduced"] == []
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_limits_lie_between_the_readings():
    """The cell's limits as the workload file states them, with the
    readings they were set from."""
    check = harness.load_json("workloads", CELL + ".json")["check"]
    assert set(check["limits"]) == {
        "logit_gap", "logprob_diff", "logprob_diff_p50", "logprob_off_pct"}
    assert all(0 < v < 100 for v in check["limits"].values())
    assert "control" in check["readings"] and "PR 37" in check["readings"]


def test_live_window_tokens_clips_each_context_to_the_window():
    """A request whose context grows from 300 to 700 over the traced
    second holds on average (300 + 512) / 2 over its first 212 / 400 and
    512 after: its ring's mean."""
    rec = {"prompt": [0] * 300, "times": list(np.linspace(0.0, 1.0, 400))}
    got = serve_phi4_flash.live_window_tokens([rec], 0.0, 1.0, 512)
    frac = 212 / 400
    assert got == pytest.approx((300 + 512) / 2 * frac + 512 * (1 - frac))
    long = {"prompt": [0] * 900, "times": list(np.linspace(0.0, 1.0, 50))}
    assert serve_phi4_flash.live_window_tokens([long], 0.0, 1.0, 512) == pytest.approx(512)
