"""Every example driver script runs end-to-end at tiny scale.

The reference's examples layer is a graded component (SURVEY.md §2.4),
and example scripts are the one surface nothing else imports — they rot
silently when APIs move. Each test drives the real script through the
real launcher (`python -m tensorflowonspark_tpu.launcher`) in a
subprocess at smoke scale: synthetic data, tiny configs, 1-2 steps.
The self-driving cluster scripts (mnist_dstream, mnist_streaming) run
the same way; mnist_data_setup and serve_continuous (which starts its
own server thread and fires its own requests — no cluster) are plain
scripts run without the launcher.

Subprocesses inherit this process's environ, which conftest.py pinned to
CPU BEFORE any of this imports — safe to spawn freely.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.e2e, pytest.mark.slow]

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(*argv: str, timeout: int = 420) -> subprocess.CompletedProcess:
    r = subprocess.run(
        [sys.executable, *argv],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert r.returncode == 0, (
        f"{argv} failed rc={r.returncode}\n"
        f"stdout tail: {r.stdout[-2000:]}\nstderr tail: {r.stderr[-2000:]}"
    )
    return r


def _launch(script: str, *args: str, executors: int = 1) -> None:
    _run(
        "-m",
        "tensorflowonspark_tpu.launcher",
        "--num-executors",
        str(executors),
        script,
        *args,
    )


@pytest.fixture(scope="module")
def mnist_tfrecords(tmp_path_factory):
    """Fake-MNIST TFRecord shards, generated once for the module (both
    the tf-mode and manifest tests consume the identical input)."""
    records = str(tmp_path_factory.mktemp("mnist") / "tfr")
    _run(
        "examples/mnist/mnist_data_setup.py",
        "--output",
        records,
        "--num-examples",
        "512",
    )
    return records


def test_mnist_spark_then_inference(tmp_path):
    model_dir = str(tmp_path / "model")
    _launch(
        "examples/mnist/mnist_spark.py",
        "--model-dir",
        model_dir,
        "--num-records",
        "512",
        "--batch-size",
        "128",
        "--cpu",
        executors=2,
    )
    _launch(
        "examples/mnist/mnist_inference.py",
        "--model-dir",
        model_dir,
        "--num-records",
        "256",
        "--batch-size",
        "128",
        "--cpu",
    )


def test_mnist_data_setup_then_tf_mode(mnist_tfrecords):
    _launch(
        "examples/mnist/mnist_tf.py",
        "--tfrecords",
        mnist_tfrecords,
        "--batch-size",
        "128",
        "--cpu",
    )


def test_llama_fsdp_tiny():
    _launch(
        "examples/llama/llama_fsdp.py",
        "--model",
        "tiny",
        "--steps",
        "2",
        "--seq",
        "128",
        "--batch-size",
        "8",
        "--cpu",
    )


def test_unet_segmentation_tiny(tmp_path):
    _launch(
        "examples/segmentation/unet_segmentation.py",
        "--tiny",
        "--steps",
        "2",
        "--batch-size",
        "8",  # must divide the suite's 8 virtual devices (data-sharded)
        "--size",
        "32",
        "--model-dir",
        str(tmp_path / "m"),
        "--cpu",
    )


def test_inception_imagenet_tiny():
    _launch(
        "examples/imagenet/inception_imagenet.py",
        "--tiny",
        "--steps",
        "2",
        "--batch-size",
        "8",  # must divide the suite's 8 virtual devices (data-sharded)
        "--cpu",
    )


def test_resnet_imagenet_tiny():
    _launch(
        "examples/resnet/resnet_imagenet.py",
        "--tiny",
        "--steps",
        "2",
        "--batch-size",
        "8",  # must divide the suite's 8 virtual devices (data-sharded)
        "--cpu",
    )


def test_mnist_estimator_tiny(tmp_path):
    _launch(
        "examples/mnist/mnist_estimator.py",
        "--export-dir",
        str(tmp_path / "export"),
        "--num-records",
        "256",
        "--cpu",
    )


def test_mnist_manifest(mnist_tfrecords):
    _launch(
        "examples/mnist/mnist_manifest.py",
        "--tfrecords",
        mnist_tfrecords,
        "--batch-size",
        "128",
        "--cpu",
    )


def test_mnist_dstream_tiny():
    _launch(
        "examples/mnist/mnist_dstream.py",
        "--files",
        "2",
        "--rows-per-file",
        "128",
        "--target-steps",
        "2",
        "--batch-size",
        "64",
        "--interval",
        "0.2",
        "--cpu",
    )


def test_mnist_streaming_tiny():
    _launch(
        "examples/mnist/mnist_streaming.py",
        "--micro-batches",
        "3",
        "--records-per-batch",
        "128",
        "--target-steps",
        "3",
        "--batch-size",
        "64",
        "--cpu",
    )


def test_cifar10_train_tiny(tmp_path):
    _launch(
        "examples/cifar10/cifar10_train.py",
        "--model",
        "resnet18",
        "--steps",
        "2",
        "--batch-size",
        "64",
        "--model-dir",
        str(tmp_path / "m"),
        "--cpu",
    )


def test_serve_continuous_self_drive(tmp_path):
    # Self-driving: builds a tiny checkpoint, starts the HTTP server on
    # an ephemeral port, fires concurrent mixed greedy/sampled requests,
    # checks stats, and exits nonzero on any mismatch.
    _run(
        "examples/serving/serve_continuous.py",
        "--checkpoint",
        str(tmp_path / "ckpt"),
        timeout=600,
    )


def test_bert_estimator_tiny(tmp_path):
    _launch(
        "examples/bert/bert_estimator.py",
        "--tiny",
        "--records",
        "64",
        "--batch-size",
        "16",
        "--epochs",
        "1",
        "--export-dir",
        str(tmp_path / "export"),
        "--cpu",
    )
