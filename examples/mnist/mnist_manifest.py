"""MNIST training with MANIFEST feeding — node-side feeders in SPARK mode.

The push plane routes every byte through the driver, one host's
ceiling; the reference never hit this because
its feed tasks ran on the executors with HDFS locality. This example
restores that property: the driver feeds ``FileManifest`` records (one
per TFRecord shard — O(files) driver bytes) and every node expands its
manifests locally through ``ManifestFeed``. Same cluster API, same
training loop shape as ``mnist_spark.py``.

Usage::

    python examples/mnist/mnist_data_setup.py --output /tmp/mnist_tfr
    tpu-submit --num-executors 2 examples/mnist/mnist_manifest.py \
        --tfrecords /tmp/mnist_tfr [--batch-size 256] [--cpu]
"""

from __future__ import annotations

import os as _os, sys as _sys

# examples are runnable without installing the package
_sys.path.insert(0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), "..", "..")))


import argparse


def main_fun(args, ctx):
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.feed.manifest import ManifestFeed
    from tensorflowonspark_tpu.feed.prefetch import DevicePrefetcher
    from tensorflowonspark_tpu.models import mnist

    model = mnist.CNN()
    mesh = make_mesh()
    # the driver ships paths; this node reads its shard files locally
    feed = ManifestFeed(ctx.get_data_feed(train_mode=True))

    params = model.init(
        jax.random.PRNGKey(0), np.zeros((2, 28, 28, 1), np.float32)
    )["params"]
    tx = optax.adam(1e-3)
    state = TrainState.create(params, tx)
    step = build_train_step(mnist.loss_fn(model.apply), tx, mesh)

    def prepare(cols):
        n = len(cols["label"])
        return {
            "image": np.asarray(cols["image"], np.float32).reshape(
                n, 28, 28, 1
            )
            / 255.0,
            "label": np.asarray(cols["label"], np.int32),
        }

    steps = 0
    with DevicePrefetcher.from_feed(
        feed,
        args.batch_size,
        mesh,
        multiple_of=jax.device_count(),
        prepare=prepare,
        input_mapping={"image": "image", "label": "label"},
    ) as pf:
        for batch in pf:
            state, loss = step(state, batch)
            steps += 1
            if steps % 20 == 0:
                print(
                    f"node{ctx.executor_id} step {steps} loss {float(loss):.4f}"
                )
    print(f"node{ctx.executor_id} finished after {steps} steps")

    if args.model_dir and ctx.is_chief:
        ctx.export_saved_model(jax.device_get(state.params), args.model_dir)
        print(f"chief (node{ctx.executor_id}) exported to {args.model_dir}")


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--tfrecords", required=True, help="TFRecord dir (mnist_data_setup.py output)")
    p.add_argument("--model-dir", default=None)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--cpu", action="store_true", help="force CPU-only nodes")
    return p.parse_args(argv)


if __name__ == "__main__":
    from tensorflowonspark_tpu.cluster import tfcluster
    from tensorflowonspark_tpu.cluster.tfcluster import InputMode
    from tensorflowonspark_tpu.data import dfutil
    from tensorflowonspark_tpu.feed.manifest import FileManifest
    from tensorflowonspark_tpu.launcher import cluster_args_from_env
    from tensorflowonspark_tpu.utils.util import cpu_only_env

    args = parse_args()
    largs = cluster_args_from_env()

    # one manifest per TFRecord shard — the driver never touches the bytes
    manifests = [
        FileManifest(path) for path in dfutil.tfrecord_files(args.tfrecords)
    ]
    if not manifests:
        raise SystemExit(f"no TFRecord shards under {args.tfrecords}")
    n_exec = largs["num_executors"]
    partitions = [manifests[i::n_exec] for i in range(min(n_exec, len(manifests)))]

    cluster = tfcluster.run(
        main_fun,
        args,
        num_executors=n_exec,
        input_mode=InputMode.SPARK,
        env=cpu_only_env() if args.cpu else None,
        launcher=largs.get("launcher"),
        distributed=largs.get("distributed", False),
    )
    cluster.train(partitions, num_epochs=args.epochs, close_feed=True)
    cluster.shutdown()
    print("mnist_manifest done")
