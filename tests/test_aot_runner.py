"""native/aot_runner.cc — the no-Python SavedModel runner.

The reference's Scala L7 API consumed SavedModels through the TF JVM
runtime with no Python in the serving path (SURVEY.md §2.2). This is
that property for the rebuild: a C++ binary (TF C API) loads the
``export_tf_saved_model`` artifact and serves batches from .npy files;
the only Python below is test staging (the binary subprocess does every
inference step).

Why not the PJRT C API with a CPU plugin: this image ships no CPU PJRT
plugin .so (the only ``GetPjrtApi`` exporter is libtpu.so, which CI must
not load — one process at a time may hold it), so the C++ entry consumes
the SavedModel artifact instead, which is also the closer parity match.
"""

import os

import numpy as np
import pytest

pytestmark = pytest.mark.e2e

jnp = pytest.importorskip("jax.numpy")


def _runner_or_skip():
    from tensorflowonspark_tpu.native.aot_runner import build_runner

    binary = build_runner()
    if binary is None:
        pytest.skip("tensorflow or C++ toolchain unavailable")
    return binary


def test_cpp_runner_matches_python(tmp_path):
    pytest.importorskip("tensorflow")
    from tensorflowonspark_tpu.api.export import export_tf_saved_model
    from tensorflowonspark_tpu.native.aot_runner import run_saved_model

    _runner_or_skip()
    state = {"w": jnp.asarray([[2.0], [1.0]], jnp.float32),
             "b": jnp.float32(0.5)}
    d = str(tmp_path / "svm")
    export_tf_saved_model(
        lambda s, b: b @ s["w"] + s["b"],
        state,
        np.zeros((4, 2), np.float32),
        d,
    )
    assert os.path.exists(os.path.join(d, "cpp_runner_manifest.txt"))
    # polymorphic batch: a size the example batch never had
    x = np.arange(14, dtype=np.float32).reshape(7, 2)
    out = run_saved_model(d, [x], str(tmp_path / "io"))
    (got,) = out.values()
    np.testing.assert_allclose(
        got, x @ np.array([[2.0], [1.0]], np.float32) + 0.5, rtol=1e-6
    )


def test_cpp_runner_mnist_artifact(tmp_path):
    """Execute an exported MNIST
    model through the C++ runner and match the in-process JAX forward."""
    pytest.importorskip("tensorflow")
    import jax

    from tensorflowonspark_tpu.api.export import export_tf_saved_model
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.native.aot_runner import run_saved_model

    _runner_or_skip()
    model = mnist.CNN()
    example = np.zeros((2, 28, 28, 1), np.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(example))["params"]

    def apply_fn(p, batch):
        return model.apply({"params": p}, batch)

    d = str(tmp_path / "mnist_svm")
    export_tf_saved_model(apply_fn, params, example, d)

    rng = np.random.default_rng(0)
    batch = rng.normal(size=(5, 28, 28, 1)).astype(np.float32)
    out = run_saved_model(d, [batch], str(tmp_path / "io"))
    (logits_cpp,) = out.values()
    logits_jax = np.asarray(apply_fn(params, jnp.asarray(batch)))
    assert logits_cpp.shape == logits_jax.shape == (5, 10)
    np.testing.assert_allclose(logits_cpp, logits_jax, rtol=1e-4, atol=1e-5)
    # classification agreement, the serving-level contract
    np.testing.assert_array_equal(
        logits_cpp.argmax(-1), logits_jax.argmax(-1)
    )
