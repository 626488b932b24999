"""Pipeline (fit/transform) and dfutil (TFRecord) tests.

Reference parity: test/test_pipeline.py and test/test_dfutil.py.
"""

import numpy as np
import pytest

from tensorflowonspark_tpu.api.pipeline import Namespace, TFEstimator, TFModel
from tensorflowonspark_tpu.utils.util import cpu_only_env

from tests import cluster_fns


def test_namespace_argv_roundtrip():
    ns = Namespace(["--batch_size", "64", "--verbose", "--name=x"])
    assert ns.batch_size == "64"
    assert ns.verbose is True
    assert ns.name == "x"
    ns2 = Namespace({"a": 1}, b=2)
    assert ns2.a == 1 and ns2.b == 2
    assert "--a" in ns2.argv()
    with pytest.raises(AttributeError):
        _ = ns.missing


def test_estimator_fit_transform(tmp_path):
    """Tiny linear model: estimator trains via the cluster, model transforms."""
    export_dir = str(tmp_path / "export")

    est = TFEstimator(
        cluster_fns.estimator_train_fn,
        cluster_size=2,
        epochs=4,
        export_dir=export_dir,
        batch_size=32,
    )
    rng = np.random.default_rng(0)
    x = rng.normal(size=256).astype(np.float32)
    records = list(zip(x.tolist(), (3.0 * x - 1.0).tolist()))
    model = est.fit([records[i::4] for i in range(4)], env=cpu_only_env())
    assert isinstance(model, TFModel)

    model.export_fn = cluster_fns.estimator_export_fn
    # cluster_size=2 inherited from fit: transform scales out over a
    # 2-node cluster and MUST inherit fit's env (cpu_only_env) — no
    # env kwarg here, yet no node may dial the TPU
    preds = model.transform([(v,) for v in [0.0, 1.0, 2.0]])
    preds = [float(p) for p in preds]
    assert abs(preds[0] - (-1.0)) < 0.3
    assert abs(preds[1] - 2.0) < 0.3
    assert abs(preds[2] - 5.0) < 0.3


def test_estimator_tensorflow_mode_stages_tfrecords(tmp_path):
    """InputMode.TENSORFLOW + tfrecord_dir: fit stages the data as
    TFRecords and nodes read the files (reference _fit staging path)."""
    import glob

    from tensorflowonspark_tpu.cluster.tfcluster import InputMode

    tfrecord_dir = str(tmp_path / "staged")
    export_dir = str(tmp_path / "export")
    rng = np.random.default_rng(1)
    x = rng.normal(size=128).astype(np.float32)
    records = list(zip(x.tolist(), (2.0 * x + 0.5).tolist()))

    est = TFEstimator(
        cluster_fns.tfrecord_train_fn,
        {"export_dir": export_dir, "tfrecord_dir": tfrecord_dir},
        cluster_size=1,
        input_mode=InputMode.TENSORFLOW,
        tfrecord_dir=tfrecord_dir,
        export_dir=export_dir,
        input_mapping={"x": "x", "y": "y"},  # names the tuple fields
    )
    model = est.fit(records, env=cpu_only_env())
    assert glob.glob(f"{tfrecord_dir}/part-*")  # staging really happened
    model.export_fn = cluster_fns.estimator_export_fn
    model.args.input_mapping = None  # transform takes bare (x,) records
    preds = model.transform([(v,) for v in [0.0, 1.0]])
    assert abs(float(preds[0]) - 0.5) < 0.1
    assert abs(float(preds[1]) - 2.5) < 0.1


def test_dfutil_roundtrip(tmp_path):
    from tensorflowonspark_tpu.data import dfutil

    rows = [
        {
            "idx": i,
            "vec": np.arange(4, dtype=np.float32) * i,
            "name": f"row{i}",
            "blob": b"\x00\x01" + bytes([i]),
        }
        for i in range(25)
    ]
    schema = dfutil.infer_schema(rows[0])
    assert schema == {
        "idx": "int64",
        "vec": "float",
        "name": "bytes",
        "blob": "bytes",
    }
    paths = dfutil.saveAsTFRecords(rows, str(tmp_path), records_per_file=10)
    assert len(paths) == 3  # 25 rows / 10 per file

    back = list(dfutil.loadTFRecords(str(tmp_path), binary_features=["blob"]))
    assert len(back) == 25
    r = back[3]
    assert int(r["idx"]) == 3
    np.testing.assert_allclose(r["vec"], np.arange(4, dtype=np.float32) * 3)
    assert r["name"] == "row3"
    assert r["blob"] == b"\x00\x01\x03"


def test_dfutil_example_conversion():
    from tensorflowonspark_tpu.data import dfutil

    row = {"a": 7, "b": [1.5, 2.5], "s": "hi"}
    ex = dfutil.toTFExample(row)
    back = dfutil.fromTFExample(ex.SerializeToString())
    assert int(back["a"]) == 7
    np.testing.assert_allclose(back["b"], [1.5, 2.5])
    assert back["s"] == "hi"


def test_estimator_has_param_accessors():
    """Reference Has* mixin surface: chainable setXxx / getXxx per param
    (setBatchSize, setNumPS, setTFRecordDir, ...)."""
    from tensorflowonspark_tpu.api.pipeline import TFEstimator

    est = TFEstimator(train_fn=lambda a, c: None, tf_args={})
    est.setBatchSize(128).setNumPS(0).setModelDir("/tmp/m").setTFRecordDir(
        "/tmp/r"
    ).setGraceSecs(5.0)
    assert est.getBatchSize() == 128
    assert est.getNumPS() == 0
    assert est.getModelDir() == "/tmp/m"
    assert est.getTFRecordDir() == "/tmp/r"
    assert est.getGraceSecs() == 5.0
    with pytest.raises(AttributeError):
        est.setNoSuchParam(1)


def test_has_param_accessor_arity():
    """Accessors have exact arity — a stray argument must raise, not
    silently redirect to another param."""
    from tensorflowonspark_tpu.api.pipeline import TFEstimator

    est = TFEstimator(train_fn=lambda a, c: None, tf_args={})
    with pytest.raises(TypeError):
        est.setBatchSize(128, "steps")
    with pytest.raises(TypeError):
        est.getBatchSize("epochs")


def test_transform_distributed_matches_local(tmp_path):
    """cluster_size=2 routes transform over cluster nodes (per-node model
    singletons + order-preserving inference plumbing); outputs must match
    the local path's exactly, in input order."""
    from tensorflowonspark_tpu.compute.checkpoint import save_checkpoint

    export_dir = str(tmp_path / "export")
    save_checkpoint(export_dir, {"w": np.float32(3.0), "b": np.float32(-1.0)})

    xs = [[float(v)] for v in np.linspace(-2, 2, 37)]  # odd count; LIST
    # records: the distributed path must not reinterpret them as partitions

    local = TFModel(
        export_dir=export_dir,
        batch_size=8,
        export_fn=cluster_fns.estimator_export_fn,
    ).transform(xs)

    dist = TFModel(
        export_dir=export_dir,
        batch_size=8,
        cluster_size=2,
        export_fn=cluster_fns.estimator_export_fn,
    ).transform(xs, env=cpu_only_env())

    assert len(dist) == len(local) == 37
    np.testing.assert_allclose(
        [float(p) for p in dist], [float(p) for p in local], rtol=1e-6
    )


class _CountingIter:
    """Iterator that records how many records have been pulled —
    observes whether transform consumes incrementally or materializes."""

    def __init__(self, records):
        self._it = iter(records)
        self.pulled = 0

    def __iter__(self):
        return self

    def __next__(self):
        rec = next(self._it)
        self.pulled += 1
        return rec


def test_transform_streams_local(tmp_path):
    """transform_iter must pull input incrementally, interleaved with
    model calls — never list(data). Verified
    with a counting iterator: when the first result comes out, at most
    the prefetch window (depth-2 DevicePrefetcher: queue + in-flight +
    staging ≈ 4 batches), not the dataset, has been consumed."""
    from tensorflowonspark_tpu.compute.checkpoint import save_checkpoint

    export_dir = str(tmp_path / "export")
    save_checkpoint(export_dir, {"w": np.float32(2.0), "b": np.float32(1.0)})

    xs = [[float(v)] for v in range(64)]
    src = _CountingIter(xs)
    model = TFModel(
        export_dir=export_dir,
        batch_size=8,
        export_fn=cluster_fns.estimator_export_fn,
    )
    stream = model.transform_iter(src)
    first = next(stream)
    assert src.pulled <= 8 * 4, f"materialized {src.pulled} records up front"
    rest = list(stream)
    assert src.pulled == 64
    preds = [float(p) for p in [first, *rest]]
    np.testing.assert_allclose(preds, [2.0 * v + 1.0 for v in range(64)],
                               rtol=1e-6)


def test_transform_streams_distributed(tmp_path):
    """The distributed path must also consume incrementally: at most the
    cluster_size-chunk head buffer plus in-flight partitions are pulled
    before the first result appears, and results stream back in input
    order."""
    from tensorflowonspark_tpu.compute.checkpoint import save_checkpoint

    export_dir = str(tmp_path / "export")
    save_checkpoint(export_dir, {"w": np.float32(3.0), "b": np.float32(0.0)})

    xs = [[float(v)] for v in range(60)]
    src = _CountingIter(xs)
    model = TFModel(
        export_dir=export_dir,
        batch_size=5,
        cluster_size=2,
        export_fn=cluster_fns.estimator_export_fn,
    )
    stream = model.transform_iter(src, env=cpu_only_env())
    first = next(stream)
    # head peek (2 chunks = 10) + one in-flight chunk per worker (10)
    # + single-chunk lookahead inside the shared source (5): anything
    # near 60 means the input was materialized
    assert src.pulled <= 30, f"pulled {src.pulled} records before first result"
    rest = list(stream)
    assert src.pulled == 60
    preds = [float(p) for p in [first, *rest]]
    np.testing.assert_allclose(preds, [3.0 * v for v in range(60)], rtol=1e-6)


def test_transform_distributed_over_aot_artifact(tmp_path):
    """Distributed transform with NO export_fn: each node loads the
    self-describing AOT artifact (the Scala-API-parity path) as its own
    singleton. The composition the reference ran at scale — per-executor
    SavedModel sessions over partitions — here as per-node AOT replays."""
    from tensorflowonspark_tpu.api import export as aot_export

    w, b = np.array([[2.0], [1.0]], np.float32), 0.5

    art = str(tmp_path / "aot_model")
    aot_export.export_model(
        lambda state, batch: {
            "y": batch["x0"] * state["w"][0, 0]
            + batch["x1"] * state["w"][1, 0]
            + state["b"][0]
        },
        {"w": w, "b": np.array([b], np.float32)},
        {"x0": np.zeros((4,), np.float32), "x1": np.zeros((4,), np.float32)},
        art,
        input_mapping={"x0": "x0", "x1": "x1"},
        output_mapping={"y": "pred"},
    )

    rows = [
        {"x0": float(i), "x1": float(2 * i)} for i in range(11)
    ]  # odd count: exercises the ragged tail
    local = TFModel(export_dir=art, batch_size=4).transform(rows)
    dist = TFModel(export_dir=art, batch_size=4, cluster_size=2).transform(
        rows, env=cpu_only_env()
    )
    assert len(dist) == len(local) == 11
    for i, (d, l) in enumerate(zip(dist, local)):
        assert float(d["pred"]) == float(l["pred"])
        np.testing.assert_allclose(
            float(d["pred"]), 2.0 * i + 1.0 * 2 * i + 0.5, rtol=1e-6
        )
