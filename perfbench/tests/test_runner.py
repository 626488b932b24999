"""The runner end to end at the ``tiny`` configuration, on the CPU: the
last line against the contract, the control, and the planted faults.

``--rehearse`` skips the harness's look for a chip and drives the rest of
a run. The control is the reference in float8 put in the program's place
(here the program is float32, so the control is far outside the limits);
each fault breaks the timed path underneath and ``correct`` has to come
out false.
"""

import contextlib
import io
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cell(cell, fault=None, trace=0, seed=11, seconds=1.5):
    from perfbench import run as runner

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = runner.main(
            ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace), "--rehearse"], fault=fault)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def check_contract(line, names):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"  # the numbers compared come last
    assert isinstance(line["correct"], bool) and line["attempted"] > 0
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in line["device"]
    # a rehearsal's numbers never stand under a metric's own name
    assert set(line["metrics"]) == {"rehearsal." + n for n in names}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    for c in line["compared"].values():
        assert set(c) == {"value", "limit", "ok"}


def test_train_line_meets_the_contract():
    line = run_cell("tiny.train")
    check_contract(line, ["setup_s", "train_tokens_per_s"])
    assert line["correct"] is True and line["failed"] == 0


def test_serve_line_meets_the_contract():
    line = run_cell("tiny.serve", seconds=2)
    check_contract(line, ["setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"])
    assert line["correct"] is True and line["failed"] == 0


def test_traced_line_leaves_out_what_it_cannot_read():
    # no device plane in a CPU trace: shares of a peak and of a roofline
    # are left out, never reported as 0
    line = run_cell("tiny.train", trace=1, seconds=2)
    assert "rehearsal.data_wait_ms.train" in line["metrics"]
    assert not any("mfu" in k or "roofline" in k or "idle" in k for k in line["metrics"])
    assert line["correct"] is True


def state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        keep = jax.tree.map(jnp.copy, state)
        _, loss = step(state, batch)
        return keep, loss

    return broken


def half_batch(step):
    def broken(state, batch):
        seg = batch["segment_ids"]
        batch = {**batch, "segment_ids": seg.at[seg.shape[0] // 2:].set(0)}
        return step(state, batch)

    return broken


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_train_fault_comes_out_not_correct(fault):
    line = run_cell("tiny.train", fault=fault)
    assert line["correct"] is False
    assert not all(c["ok"] for c in line["compared"].values())


def token_altered(records):
    for r in records:
        if r["tokens"]:
            r["tokens"][-1] = (r["tokens"][-1] + 1) % 256


def answer_dropped(records):
    records[0]["tokens"].pop()


@pytest.mark.parametrize("fault", [token_altered, answer_dropped])
def test_serve_fault_comes_out_not_correct(fault):
    line = run_cell("tiny.serve", fault=fault, seconds=2)
    assert line["correct"] is False


def test_control_fails_the_limits():
    """tools/limits.py at tiny: the float8 reference in the program's
    place lies outside the limits that the program meets."""
    for cell, seconds in (("tiny.train", 1), ("tiny.serve", 2)):
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "perfbench", "tools", "limits.py"),
             "--workload", cell, "--seeds", "3", "--controls", "3",
             "--seconds", str(seconds), "--rehearse"],
            capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr[-2000:]
        summary = json.loads(out.stdout.strip().splitlines()[-1])["summary"]
        with open(os.path.join(ROOT, "perfbench", "workloads", cell + ".json")) as f:
            limits = json.load(f)["check"]["limits"]
        assert all(summary["program_max"][k] <= v for k, v in limits.items())
        assert any(summary["control_fp8_min"][k] > v for k, v in limits.items())


def test_no_chip_no_number():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload",
         "tiny.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0 and out.stdout.strip() == ""
