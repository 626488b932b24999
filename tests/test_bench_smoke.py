"""bench.py end-to-end smoke: the driver-scored artifact's FULL code
path (llama sharded step + MNIST data plane + JSON assembly) must run on
the CPU under the explicit BENCH_ALLOW_CPU=1, and without it a run that
finds no chip must refuse."""

import glob
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.e2e

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The committed benchmarks/results/*_smoke.json artifacts are scored on
# a quiet single-chip host; every regeneration (pytest-driven included)
# must record the same environment or the drift gate below fails.
BASELINE_CHIPS = 1


def _artifact_env(results_dir: str) -> dict:
    """Subprocess env for bench e2e runs. Artifacts are REDIRECTED to
    ``results_dir`` (via TFOS_BENCH_RESULTS_DIR) so a pytest run can
    never overwrite the committed quiet-host baselines in
    benchmarks/results/ with a contended-host run — regenerating a
    committed artifact is always a deliberate direct ``bench.py``
    invocation on a quiet host. The conftest's
    ``--xla_force_host_platform_device_count=8`` is also scrubbed so
    the run records the host-true chip count instead of 8 faux devices
    (the drifted-artifact footgun the chips gate exists to catch)."""
    env = dict(
        os.environ,
        BENCH_SMOKE="1",
        BENCH_ALLOW_CPU="1",
        JAX_PLATFORMS="cpu",
        TFOS_BENCH_RESULTS_DIR=results_dir,
    )
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(flags)
    return env


@pytest.mark.e2e
def test_committed_smoke_artifacts_record_baseline_chips():
    """Environment guard: every committed chips-stamped smoke artifact
    must record the baseline environment (a quiet single-chip host) —
    a run that inherited pytest's 8-device XLA forcing fails HERE
    instead of committing a drifted artifact (the PR-17 footgun)."""
    arts = sorted(
        glob.glob(
            os.path.join(REPO, "benchmarks", "results", "*_smoke.json")
        )
    )
    assert arts, "no committed smoke artifacts found"
    for path in arts:
        with open(path) as f:
            art = json.load(f)
        if "chips" not in art:
            continue
        assert art["chips"] == BASELINE_CHIPS, (
            f"{os.path.relpath(path, REPO)} records chips="
            f"{art['chips']} (baseline {BASELINE_CHIPS}) — it was "
            "regenerated under pytest's 8-device XLA forcing; rerun "
            "bench.py directly on a quiet host (BENCH_SMOKE=1 "
            "BENCH_ALLOW_CPU=1 JAX_PLATFORMS=cpu, no "
            "xla_force_host_platform_device_count) before committing"
        )


def test_bench_smoke_emits_complete_json(tmp_path):
    env = dict(
        os.environ,
        BENCH_SMOKE="1",
        BENCH_ALLOW_CPU="1",
        JAX_PLATFORMS="cpu",
        TFOS_BENCH_RESULTS_DIR=str(tmp_path),
    )
    # a clean XLA_FLAGS: the conftest's 8-device forcing is fine but not
    # required; bench must work with whatever the driver environment has
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,  # above bench.py's 510s watchdog: a wedge still prints
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "llama1b_train_mfu"
    assert out["smoke"] is True
    assert "error" not in out
    # every field the real run reports must be present and sane
    assert out["chips"] >= 1
    assert out["step_time_ms"] > 0
    assert out["tokens_per_sec_per_chip"] > 0
    assert out["final_loss"] > 0
    assert out["mnist_examples_per_sec"] > 0
    assert out["mnist_feed_mb_s"] > 0
    assert out["mnist_final_loss"] > 0


def test_bench_serve_smoke_emits_engine_tax(tmp_path):
    """bench.py --serve end-to-end on the tiny model: the serving-tax
    measurement (engine tokens/sec at pipeline_depth 1 and 2 vs raw
    single-stream generate) must emit a finite engine_tax JSON line and
    commit the span-based trace-report artifact."""
    import math

    env = dict(
        os.environ,
        BENCH_SMOKE="1",
        BENCH_ALLOW_CPU="1",
        JAX_PLATFORMS="cpu",
        TFOS_BENCH_RESULTS_DIR=str(tmp_path),
    )
    proc = subprocess.run(
        [sys.executable, "bench.py", "--serve"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "serve_engine_tax"
    assert out["smoke"] is True
    assert math.isfinite(out["value"]) and out["value"] > 0
    assert out["raw_single_stream_tokens_per_sec"] > 0
    for leg in ("engine_depth1", "engine_depth2"):
        assert out[leg]["tokens_per_sec"] > 0
        assert out[leg]["dispatch_fetch_ms_per_token"] >= 0
    # the depth-2 engine overlapped SOMETHING (sweeps ran while blocks
    # were in flight) — the gauge the whole PR exists to move
    assert out["engine_depth2"]["overlap_hidden_ms"] > 0
    # the host-residual evidence artifact was committed
    assert os.path.exists(os.path.join(REPO, out["trace_report"]))


def test_bench_zero_smoke_ab_and_byte_identity(tmp_path):
    """bench.py --zero end-to-end on the tiny model: both knob legs run
    on a pure data-parallel mesh, the isolated optimizer span is
    measured per leg, the weight-update decomposition is BYTE-IDENTICAL
    across knobs on identical gradients (the ZeRO math owns nothing but
    placement), and the A/B artifact is committed."""
    env = _artifact_env(str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--zero"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "zero_weight_update"
    assert out["smoke"] is True
    for leg in ("zero_on", "zero_off"):
        assert out[leg]["step_time_ms"] > 0
        assert out[leg]["weight_update_ms"] > 0
    # same loss to the reported precision on both legs
    assert out["zero_on"]["final_loss"] == out["zero_off"]["final_loss"]
    # the byte-identity gate: identical grads through the sharded vs
    # replicated weight update -> identical params, bit for bit
    assert out["update_params_match"] is True
    art = os.path.join(REPO, out["artifact"])
    assert os.path.exists(art)
    on_disk = json.load(open(art))
    assert on_disk["metric"] == "zero_weight_update"
    assert on_disk["update_params_match"] is True


def test_bench_serve_slo_smoke_burn_gate_and_trace_proof(tmp_path):
    """bench.py --serve-slo end-to-end on the tiny model: a clean leg
    must leave every SLO silent, the armed (latency-failpoint) leg must
    fire exactly the latency SLO as exactly one rising edge, and the
    proof request's merged timeline must attribute >= 95% of its wall
    time across router -> engine segments."""
    env = _artifact_env(str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--serve-slo"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "serve_slo_burn_gate"
    assert out["smoke"] is True
    assert out["passed"] is True, out["checks"]
    assert all(out["checks"].values()), out["checks"]
    # the SLO plane fired exactly where the failpoint was armed
    assert not any(v["breached"] for v in out["slo_clean"])
    assert [v["slo"] for v in out["slo_armed"] if v["breached"]] == [
        "fleet_latency"
    ]
    # the end-to-end trace proof: wall time attributed, both layers on
    assert out["attribution"]["covered_fraction"] >= 0.95
    segs = set(out["attribution"]["segments_s"])
    assert "router.submit" in segs
    assert any(s.startswith("engine.") for s in segs)
    assert out["proof_wall_s"] > out["objective_s"]
    assert out["merged_trace_events"] > 0
    art = os.path.join(REPO, out["artifact"])
    assert os.path.exists(art)
    assert json.load(open(art))["metric"] == "serve_slo_burn_gate"


def test_bench_cache_smoke_readthrough_gate(tmp_path):
    """bench.py --cache end-to-end on the tiny model: the serving A/B
    must show cross-replica L2 hits (> 0) with the fleet faster than
    the L1-only leg, and the training leg's two concurrent readers
    must cost ~one backing pass, not two."""
    env = _artifact_env(str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--cache"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "cachetier_readthrough"
    assert out["smoke"] is True
    # serving: the tier must pay for itself on shared-prefix traffic
    assert out["l2_hits"] > 0
    assert out["cache_l1_only"]["l2_hits"] == 0  # control leg really off
    assert out["value"] > 1.0, (
        out["tokens_per_sec_l2"],
        out["tokens_per_sec_l1_only"],
    )
    # training: 2 readers, ~1x backing reads (2.0 = the tier saved
    # nothing; the slack absorbs one concurrent-miss race per frame)
    assert 0.99 <= out["training_backing_ratio"] <= 1.5
    assert out["cache_training"]["readers"] == 2
    art = os.path.join(str(tmp_path), os.path.basename(out["artifact"]))
    assert os.path.exists(art)
    assert json.load(open(art))["metric"] == "cachetier_readthrough"


@pytest.mark.parametrize(
    "argv",
    [
        ["bench.py"],
        ["benchmarks/real_chip.py", "--config", "mnist", "--steps", "1"],
    ],
    ids=["bench", "real_chip"],
)
def test_bench_refuses_to_measure_without_a_chip(argv):
    """No chip and no explicit BENCH_ALLOW_CPU: exit non-zero, say why,
    and print no result line — a CPU run never yields a device metric."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("BENCH_ALLOW_CPU", "BENCH_SMOKE")
    }
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, *argv],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "BENCH_ALLOW_CPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_real_chip_prefix_bench_smoke():
    """llama1b_prefix at --model-scale tiny: the full cold/prime/warm
    flow must run on CPU and prove reuse (the config itself raises if
    the warm loop misses the prefix cache)."""
    env = dict(os.environ, BENCH_ALLOW_CPU="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            "benchmarks/real_chip.py",
            "--config", "llama1b_prefix",
            "--model-scale", "tiny",
            "--steps", "3",
            "--seq", "64",
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["config"] == "llama1b_prefix"
    assert out["prefix_hits"] >= 3
    assert out["prefix_tokens_saved"] > 0
    assert out["ttft_cold_ms"] > 0 and out["step_time_ms"] > 0


def test_bench_serve_fleet_smoke_emits_scaling_and_artifact(tmp_path):
    """bench.py --serve-fleet end-to-end on the tiny model: the
    replicas=1 vs 2 saturation legs must emit a finite scaling ratio
    (uncontended projection + contended wall ratio), zero sheds/
    failovers in an unsaturated run, and commit the
    benchmarks/results/serve_fleet_*.json artifact."""
    import math

    env = _artifact_env(str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--serve-fleet"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "serve_fleet_scaling"
    assert out["smoke"] is True
    assert math.isfinite(out["value"]) and out["value"] > 0
    assert math.isfinite(out["wall_ratio_contended"])
    assert out["wall_ratio_contended"] > 0
    for leg in ("fleet_replicas1", "fleet_replicas2"):
        assert out[leg]["tokens_per_sec"] > 0
        assert out[leg]["shed"] == 0
        assert out[leg]["failovers"] == 0
    assert len(out["fleet_replicas2"]["uncontended_per_replica"]) == 2
    art = os.path.join(REPO, out["artifact"])
    assert os.path.exists(art)
    on_disk = json.load(open(art))
    assert on_disk["metric"] == "serve_fleet_scaling"


def test_bench_rollout_smoke_zero_downtime_artifact(tmp_path):
    """bench.py --rollout end-to-end on the tiny model: K=2 versions
    hot-swap through a 2-replica fleet under sustained streaming load;
    the emitted JSON (and committed artifact) must pass every
    acceptance check — zero dropped/hung requests, admitted p99 within
    the deadline budget, coherent per-completion version stamps."""
    env = _artifact_env(str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--rollout"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "rollout_zero_downtime"
    assert out["smoke"] is True
    assert out["passed"] is True, out["checks"]
    assert all(out["checks"].values()), out["checks"]
    assert out["versions_rolled"] == 2
    assert all(
        r["outcome"] == "completed" for r in out["rollouts"]
    )
    assert out["requests_ok"] > 0
    assert out["requests_hard_errors"] == 0
    assert out["hung_workers"] == 0
    assert out["admitted_p99_s"] <= out["deadline_budget_s"]
    assert set(out["version_counts"]) <= {"v0", "v1", "v2"}
    art = os.path.join(REPO, out["artifact"])
    assert os.path.exists(art)
    assert json.load(open(art))["metric"] == "rollout_zero_downtime"


def test_bench_autotune_smoke_recovers_and_audits(tmp_path):
    """bench.py --autotune end-to-end: boot BOTH legs (mnist feed
    physics, tiny-model serve fleet) with deliberately bad knobs and
    let the controller recover >=90% of the hand-tuned throughput
    online. Every knob move must be on the flight record, and at least
    one leg must exercise the revert path (hill-climb past the peak)."""
    env = _artifact_env(str(tmp_path))
    env.pop("TFOS_AUTOTUNE", None)  # the leg under test tunes live
    committed = os.path.join(
        REPO, "benchmarks", "results", "autotune_cpu_smoke.json"
    )
    with open(committed, "rb") as f:
        committed_bytes = f.read()
    proc = subprocess.run(
        [sys.executable, "bench.py", "--autotune"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "autotune_recovery"
    assert out["smoke"] is True
    assert out["feed_leg"]["recovered_frac"] >= 0.9
    assert out["serve_leg"]["recovered_frac"] >= 0.9
    assert out["value"] >= 0.9
    assert out["autotune_reverts_total"] > 0
    assert out["autotune_decisions_total"] > 0
    # every move/revert is a registered flightrec event
    assert (
        out["flightrec_autotune_events"] >= out["autotune_decisions_total"]
    )
    # the feed leg must actually have climbed off the bad boot depth
    assert out["feed_leg"]["final_depth"] > out["feed_leg"]["initial_depth"]
    # the router's pessimistic boot estimate must have been tightened
    assert (
        out["serve_leg"]["service_estimate_after_s"]
        < out["serve_leg"]["service_estimate_before_s"]
    )
    art = os.path.join(REPO, out["artifact"])
    assert os.path.exists(art)
    on_disk = json.load(open(art))
    assert on_disk["metric"] == "autotune_recovery"
    assert on_disk["value"] >= 0.9
    # redirect regression guard: the e2e run lands its artifact in the
    # scratch dir and leaves the committed quiet-host baseline
    # byte-untouched (a contended pytest rerun once clobbered it with a
    # failing run)
    assert os.path.dirname(art) == str(tmp_path)
    with open(committed, "rb") as f:
        assert f.read() == committed_bytes


def test_bench_online_smoke_continual_loop_closes(tmp_path):
    """bench.py --online end-to-end on the tiny model: a 2-replica
    fleet serves under sustained load while every beat's traffic is
    sealed, discovered, trained into a new weights version, and rolled
    out — the emitted JSON (and redirected artifact) must pass every
    acceptance check: the served generation shifts onto live-trained
    weights, zero dropped/hung requests, zero dropped log records,
    admitted p99 within the deadline, no stalls, final data age within
    the freshness objective."""
    env = _artifact_env(str(tmp_path))
    committed = os.path.join(
        REPO, "benchmarks", "results", "online_cpu_smoke.json"
    )
    with open(committed, "rb") as f:
        committed_bytes = f.read()
    proc = subprocess.run(
        [sys.executable, "bench.py", "--online"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "online_continual_loop"
    assert out["smoke"] is True
    assert out["passed"] is True, out["checks"]
    assert all(out["checks"].values()), out["checks"]
    # the loop's point: by the tail beat the fleet serves weights
    # trained from traffic logged mid-run
    assert out["fresh_share_late"] >= 0.9
    assert out["fresh_share_late"] > out["fresh_share_early"]
    assert out["records_trained"] > 0
    assert out["requests_ok"] > 0
    assert out["requests_hard_errors"] == 0
    assert out["hung_workers"] == 0
    assert out["log_records_dropped"] == 0
    assert out["admitted_p99_s"] <= out["deadline_budget_s"]
    assert out["loop_stats"]["stalls"] == 0
    assert all(
        c["rollout_outcome"] == "completed" for c in out["cycles"]
    )
    art = os.path.join(REPO, out["artifact"])
    assert os.path.exists(art)
    assert json.load(open(art))["metric"] == "online_continual_loop"
    # redirect guard: the committed quiet-host baseline stays untouched
    assert os.path.dirname(art) == str(tmp_path)
    with open(committed, "rb") as f:
        assert f.read() == committed_bytes
