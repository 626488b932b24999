"""benchmarks/feed_plane.py smoke: the push-plane throughput bench's
full path (cluster up, shm + forced-TCP feed, columnar + row wires,
drain-timed JSON rows) must run at tiny sizes — and the columnar wire
must never lose to row-pickle on the shm path (the ISSUE-5 acceptance
gate at smoke scale; the CPU-host rows live in
benchmarks/results/feed_plane_columnar.jsonl)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.e2e

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_feed_plane_bench_smoke():
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "benchmarks", "feed_plane.py"),
            "--nodes", "2",
            "--mb-per-node", "8",
            "--record-kb", "16",
            "--paths", "shm,tcp",
            "--wire", "columnar,row",
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    assert [(r["path"], r["wire"]) for r in rows] == [
        ("shm", "columnar"),
        ("shm", "row"),
        ("tcp", "columnar"),
        ("tcp", "row"),
    ]
    by_leg = {(r["path"], r["wire"]): r for r in rows}
    for r in rows:
        assert r["nodes"] == 2
        assert r["mb_per_s"] > 0
        assert r["secs"] > 0
    # The point of the columnar wire: even at smoke scale (where fixed
    # cluster startup/teardown overhead dilutes the gap — the committed
    # artifact shows >=3x at real payloads) it must not LOSE to the
    # row-pickle wire on the shm path. 0.9: at 8 MB/node both legs are
    # startup-dominated and land within a few percent of each other, so
    # an exact >= flakes on shared-host timing noise; a real regression
    # (columnar slower than row) shows up far below this.
    assert (
        by_leg[("shm", "columnar")]["mb_per_s"]
        >= 0.9 * by_leg[("shm", "row")]["mb_per_s"]
    ), rows


def test_feed_plane_pull_leg_smoke():
    """The ISSUE-8 pull-sharded leg end-to-end at tiny sizes: both
    modes emit rows with per-node self-timed rates; per-node rates are
    positive and the staggered aggregate is their sum."""
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "benchmarks", "feed_plane.py"),
            "--nodes", "2",
            "--mb-per-node", "8",
            "--record-kb", "16",
            "--paths", "pull",
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    assert [(r["leg"], r["mode"]) for r in rows] == [
        ("pull-sharded", "coscheduled"),
        ("pull-sharded", "staggered"),
    ]
    for r in rows:
        assert r["nodes"] == 2
        assert len(r["per_node_mb_per_s"]) == 2
        assert all(v > 0 for v in r["per_node_mb_per_s"]), r
    staggered = rows[1]
    assert staggered["mb_per_s"] == pytest.approx(
        sum(staggered["per_node_mb_per_s"]), rel=0.01
    )
