"""Multi-process jax.distributed over the reservation control plane.

The CPU stand-in for multi-host pod wiring (SURVEY.md §4 "distributed-
without-a-cluster" / §5.8a): the roster hands every spawned node the
chief's coordinator address, run_node calls jax.distributed.initialize,
and a real cross-process collective runs — no pod needed.
"""

import json
import math

import pytest

from tensorflowonspark_tpu.cluster import tfcluster
from tensorflowonspark_tpu.cluster.tfcluster import InputMode
from tensorflowonspark_tpu.utils.device_info import (
    multiprocess_collectives_supported,
)
from tensorflowonspark_tpu.utils.util import cpu_only_env

from tests import cluster_fns

pytestmark = pytest.mark.e2e


@pytest.fixture(autouse=True, scope="module")
def _require_multiprocess_backend():
    """Backend-capability gate: some jaxlib CPU builds cannot run
    multiprocess computations at all ("Multiprocess computations aren't
    implemented on the CPU backend"). Every test in this module needs a
    REAL cross-process collective, so on such a backend the whole suite
    is an environment limitation, not a signal — skip, don't fail. The
    probe (two subprocesses, one allgather) runs once per process; see
    utils/device_info.py (TFOS_MULTIPROCESS_OK overrides it)."""
    if not multiprocess_collectives_supported():
        pytest.skip(
            "this jax backend cannot run multiprocess collectives "
            "(CPU-backend limitation)"
        )


def test_two_process_jax_distributed(tmp_path):
    cluster = tfcluster.run(
        cluster_fns.distributed_allgather_fn,
        {"out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=InputMode.TENSORFLOW,
        reservation_timeout=180,
        distributed=True,
        env=cpu_only_env(num_cpu_devices=1),  # 1 CPU device per process
    )
    cluster.shutdown(timeout=180)

    results = [
        json.load(open(tmp_path / f"node{i}.json")) for i in range(2)
    ]
    for i, r in enumerate(results):
        assert r["process_count"] == 2
        assert r["process_index"] == i
        assert r["global_devices"] == 2  # 1 local CPU device per process
        assert sorted(r["gathered"]) == [0, 1]  # real cross-process gather


def test_two_process_distributed_training(tmp_path):
    """Multi-controller DP: global mesh over 2 processes' devices, each
    process feeding its local half via make_array_from_process_local_data;
    gradients sync through the jit psum, so both converge identically."""
    cluster = tfcluster.run(
        cluster_fns.distributed_train_fn,
        {"out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=InputMode.TENSORFLOW,
        reservation_timeout=180,
        distributed=True,
        env=cpu_only_env(num_cpu_devices=1),
    )
    cluster.shutdown(timeout=180)

    results = [
        json.load(open(tmp_path / f"node{i}.json")) for i in range(2)
    ]
    for r in results:
        assert r["global_devices"] == 2
        # Trained on the GLOBAL batch: converges to y = 3x + 1.5.
        assert abs(r["w"] - 3.0) < 0.05, r
        assert abs(r["b"] - 1.5) < 0.05, r
    # Multi-controller SPMD: both processes hold identical replicated state.
    assert results[0] == results[1]


def test_spark_feed_unequal_partitions_no_deadlock(tmp_path):
    """The push feed + multi-controller combination from SURVEY §7's hard
    parts: processes receive UNEQUAL amounts of data (5 partitions round-
    robin over 2 workers), so without the all-hosts agreement the shorter
    process would exit while the longer one blocks in the psum forever.
    synchronized_batch_stream must stop both together, same step count,
    converged identical state."""
    import numpy as np

    rng = np.random.default_rng(0)

    def part(n):
        x = rng.normal(size=n).astype(np.float32)
        return [(float(xi), float(3.0 * xi + 1.5)) for xi in x]

    # alternating 32/16-record partitions round-robin over 2 workers:
    # worker0 gets 96 records/epoch (12 batches), worker1 48 (6 batches)
    partitions = [part(32), part(16)] * 3

    cluster = tfcluster.run(
        cluster_fns.distributed_spark_train_fn,
        {"out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=InputMode.SPARK,
        reservation_timeout=180,
        distributed=True,
        env=cpu_only_env(num_cpu_devices=1),
    )
    cluster.train(partitions, num_epochs=12, close_feed=True)
    cluster.shutdown(timeout=180)

    results = [
        json.load(open(tmp_path / f"node{i}.json")) for i in range(2)
    ]
    # agreement: both processes ran the same number of global steps — the
    # shorter feed's count (48*12 records / batch 8 = 72 steps)
    assert results[0]["steps"] == results[1]["steps"] == 72
    for r in results:
        assert r["global_devices"] == 2
        assert abs(r["w"] - 3.0) < 0.05, r
        assert abs(r["b"] - 1.5) < 0.05, r
    assert results[0] == results[1]


def test_spark_feed_ragged_tail_agreement(tmp_path):
    """Regression: one process's feed ends on a SHORT tail batch while the
    other still holds a full one. The agreement must treat the short tail
    as exhaustion (only full batches shard identically across processes),
    stopping both at the same full-batch count."""
    import numpy as np

    rng = np.random.default_rng(1)

    def part(n):
        x = rng.normal(size=n).astype(np.float32)
        return [(float(xi), float(3.0 * xi + 1.5)) for xi in x]

    # worker0: 100 records -> 12 full batches + 4-record tail
    # worker1: 104 records -> 13 full batches
    partitions = [part(100), part(104)]

    cluster = tfcluster.run(
        cluster_fns.distributed_spark_train_fn,
        {"out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=InputMode.SPARK,
        reservation_timeout=180,
        distributed=True,
        env=cpu_only_env(num_cpu_devices=1),
    )
    cluster.train(partitions, close_feed=True)
    cluster.shutdown(timeout=180)

    results = [
        json.load(open(tmp_path / f"node{i}.json")) for i in range(2)
    ]
    assert results[0]["steps"] == results[1]["steps"] == 12


def test_two_process_fsdp_checkpoint_resume(tmp_path):
    """Multi-controller checkpoint/restore across the process boundary:
    a tiny Llama's params + bf16-moment Adam state sharded over 2
    processes, saved COLLECTIVELY by both processes
    (chief-only saves of cross-process-sharded arrays hang/raise), then
    restored by a brand-new cluster which must replay the post-checkpoint
    steps bit-identically."""
    train_dir, resume_dir = tmp_path / "train", tmp_path / "resume"
    train_dir.mkdir(), resume_dir.mkdir()
    model_dir = str(tmp_path / "ckpt")

    def run(phase, out_dir, expect_step=None):
        cluster = tfcluster.run(
            cluster_fns.distributed_llama_ckpt_fn,
            {
                "out_dir": str(out_dir),
                "model_dir": model_dir,
                "phase": phase,
                "expect_step": expect_step,
            },
            num_executors=2,
            input_mode=InputMode.TENSORFLOW,
            reservation_timeout=180,
            distributed=True,
            env=cpu_only_env(num_cpu_devices=2),
        )
        cluster.shutdown(timeout=300)
        return [
            json.load(open(out_dir / f"node{i}.json")) for i in range(2)
        ]

    trained = run("train", train_dir)
    for r in trained:
        assert r["process_count"] == 2
        assert r["global_devices"] == 4
        assert r["latest_after"] == 4  # collective final save landed
        assert all(math.isfinite(l) for l in r["losses"])
    assert trained[0]["losses"] == trained[1]["losses"]

    # a NEW cluster (fresh processes — the "kill") restores and resumes
    resumed = run("resume", resume_dir, expect_step=4)
    for r in resumed:
        # bit-identical replay: the checkpoint captured params AND
        # optimizer state (incl. bf16 moments) exactly
        assert r["losses"] == trained[0]["losses"], (r, trained[0])


def test_two_process_llama_fsdp(tmp_path):
    """FSDP across the process boundary: a tiny Llama trained with its
    params/optimizer state sharded over 2 processes x 4 devices, bf16
    Adam moments, and chunked CE — the full production stack in true
    multi-controller mode."""
    cluster = tfcluster.run(
        cluster_fns.distributed_llama_fsdp_fn,
        {"out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=InputMode.TENSORFLOW,
        reservation_timeout=180,
        distributed=True,
        env=cpu_only_env(num_cpu_devices=4),
    )
    cluster.shutdown(timeout=300)

    results = [
        json.load(open(tmp_path / f"node{i}.json")) for i in range(2)
    ]
    for r in results:
        assert r["process_count"] == 2
        assert r["global_devices"] == 8
        assert all(math.isfinite(l) for l in r["losses"])
        assert r["losses"][-1] < r["losses"][0]  # it actually learns
    # multi-controller SPMD: identical replicated loss on every process
    assert results[0]["losses"] == results[1]["losses"]


def test_run_with_restarts_multi_controller_collective_resume(tmp_path):
    """The supervisor composes with multi-controller FSDP: attempt 1
    saves the cross-process-sharded state collectively and crashes;
    attempt 2 gets a fresh jax.distributed coordinator, restores
    collectively, and finishes identically on both processes."""
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    restarts = tfcluster.run_with_restarts(
        cluster_fns.distributed_flaky_llama_fn,
        {"out_dir": str(out_dir), "model_dir": str(tmp_path / "ckpt")},
        num_executors=2,
        input_mode=InputMode.TENSORFLOW,
        max_restarts=2,
        reservation_timeout=180,
        shutdown_timeout=180,
        distributed=True,
        env=cpu_only_env(num_cpu_devices=2),
    )
    assert restarts == 1
    results = [
        json.load(open(out_dir / f"node{i}.json")) for i in range(2)
    ]
    for r in results:
        assert r["resumed_from"] == 2  # restored the collective save
        assert r["process_count"] == 2
        assert all(math.isfinite(l) for l in r["losses"])
    assert results[0]["losses"] == results[1]["losses"]
