"""Coverage for the small parity modules: compat, device_info, util path
resolution, and the tpu-submit CLI front door."""

import os

import numpy as np
import pytest

from tensorflowonspark_tpu.utils import compat, device_info, util


def test_compat_export_and_noop_shims(tmp_path):
    path = compat.export_saved_model({"w": np.float32(2.0)}, str(tmp_path / "m"))
    from tensorflowonspark_tpu.compute.checkpoint import restore_checkpoint

    state = restore_checkpoint(path)
    assert float(np.asarray(state["w"])) == 2.0
    assert compat.disable_auto_shard() is None
    assert compat.disable_auto_shard(object()) is None  # accepts tf options
    assert isinstance(compat.is_gpu_available(), bool)


def test_device_info_shims():
    csv = device_info.get_gpus(num_gpu=2)
    assert csv == "0,1"  # conftest: 8 virtual CPU devices
    assert len(device_info.get_local_devices()) == 8
    assert device_info.is_tpu_available() is False  # CPU test mesh


def test_resolve_path_matrix(tmp_path):
    # scheme-qualified passes through
    assert util.resolve_path("hdfs://nn/a") == "hdfs://nn/a"
    # absolute + scheme default_fs -> prefixed
    assert (
        util.resolve_path("/data", default_fs="hdfs://nn") == "hdfs://nn/data"
    )
    # absolute + no scheme fs -> untouched
    assert util.resolve_path("/data", default_fs="") == "/data"
    # relative resolves against working dir, or cwd when unset
    assert (
        util.resolve_path("logs", working_dir=str(tmp_path))
        == f"{tmp_path}/logs"
    )
    assert util.resolve_path("logs") == f"{os.getcwd()}/logs"


def test_executor_id_pinning(tmp_path):
    assert util.read_executor_id(str(tmp_path)) is None
    util.write_executor_id(3, str(tmp_path))
    assert util.read_executor_id(str(tmp_path)) == 3


def test_launcher_main_runs_script_with_env(tmp_path, monkeypatch):
    """tpu-submit parses flags, exports TFOS_TPU_*/--conf env, runs the
    script as __main__ with its own argv."""
    from tensorflowonspark_tpu import launcher

    out = tmp_path / "out.txt"
    script = tmp_path / "driver.py"
    script.write_text(
        "import os, sys, json\n"
        "from tensorflowonspark_tpu.launcher import cluster_args_from_env\n"
        "payload = {'argv': sys.argv[1:],\n"
        "           'num': cluster_args_from_env()['num_executors'],\n"
        "           'conf': os.environ.get('MY_CONF')}\n"
        f"open({str(out)!r}, 'w').write(json.dumps(payload))\n"
    )
    monkeypatch.setattr("sys.argv", ["tpu-submit"])
    rc = launcher.main(
        [
            "--num-executors", "3",
            "--conf", "MY_CONF=hello",
            str(script),
            "--user-flag", "7",
        ]
    )
    assert rc == 0
    import json

    payload = json.loads(out.read_text())
    assert payload == {
        "argv": ["--user-flag", "7"],
        "num": 3,
        "conf": "hello",
    }


def test_launcher_rejects_bad_conf(tmp_path):
    from tensorflowonspark_tpu import launcher

    script = tmp_path / "s.py"
    script.write_text("pass\n")
    with pytest.raises(SystemExit):
        launcher.main(["--conf", "novalue", str(script)])


def test_export_tf_saved_model_roundtrip(tmp_path):
    """jax2tf SavedModel export loads and serves in TF (TF-serving interop,
    the artifact family the reference's Scala API consumed)."""
    tf = pytest.importorskip("tensorflow")
    import jax.numpy as jnp

    from tensorflowonspark_tpu.api.export import export_tf_saved_model

    state = {"w": jnp.asarray([[2.0], [1.0]]), "b": jnp.asarray([0.5])}

    def apply_fn(s, batch):
        return batch @ s["w"] + s["b"]

    d = str(tmp_path / "saved_model")
    export_tf_saved_model(apply_fn, state, np.zeros((4, 2), np.float32), d)
    loaded = tf.saved_model.load(d)
    for n in (2, 5):  # polymorphic batch dim
        x = np.arange(2 * n, dtype=np.float32).reshape(n, 2)
        got = np.asarray(loaded.f(tf.constant(x)))
        np.testing.assert_allclose(
            got, x @ np.array([[2.0], [1.0]], np.float32) + 0.5, rtol=1e-6
        )


def test_local_launcher_refuses_several_accelerator_nodes_on_one_host():
    """A host's chips belong to one process at a time: several node
    processes that would all ask for the TPU are refused before any
    starts (CPU-only nodes, and a single accelerator node, are fine)."""
    import pytest

    from tensorflowonspark_tpu.cluster.launchers import LocalLauncher

    launcher = LocalLauncher()
    with pytest.raises(ValueError, match="ONE node process per host"):
        launcher.launch(
            2, print, lambda i: (i,), env={"JAX_PLATFORMS": "tpu,cpu"}
        )
    assert launcher.exitcodes() == []  # nothing was started
