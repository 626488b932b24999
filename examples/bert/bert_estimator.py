"""BERT fine-tune via the TFEstimator / TFModel pipeline, plus AOT export.

Reference parity: the estimator-path examples
(``examples/mnist/estimator/mnist_spark.py`` + ``pipeline.TFEstimator``,
SURVEY.md §2.4/§3.4) applied to the BASELINE.json "BERT-base fine-tune via
the Estimator pipeline" config. Synthetic task: sequence classification
where the label is derivable from token statistics, so loss actually drops.

The fitted model is exported twice: orbax (for TFModel.transform via
``export_fn``) and, on request, an AOT artifact
(:mod:`tensorflowonspark_tpu.api.export`) runnable with zero user code::

    python -m tensorflowonspark_tpu.tools.run_model --export-dir ... --input ...

Usage::

    tpu-submit --num-executors 1 examples/bert/bert_estimator.py \
        --export-dir /tmp/bert_est [--aot-dir /tmp/bert_aot] [--tiny] [--cpu]
"""

from __future__ import annotations

import os as _os, sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), "..", "..")))

import argparse

VOCAB = 64
SEQ = 32
NUM_CLASSES = 2


def _config(tiny: bool):
    from tensorflowonspark_tpu.models.bert import BertConfig

    if tiny:
        return BertConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)
    return BertConfig.bert_base(vocab_size=VOCAB, max_seq_len=SEQ)


def make_records(n, seed=0):
    """Token sequences whose label = 1 iff mean(token) > VOCAB/2."""
    import numpy as np

    rng = np.random.default_rng(seed)
    records = []
    for _ in range(n):
        tokens = rng.integers(1, VOCAB, size=SEQ)
        label = int(tokens.mean() > VOCAB / 2)
        records.append((tokens.astype(np.int64), label))
    return records


def train_fn(args, ctx):
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.feed.prefetch import DevicePrefetcher
    from tensorflowonspark_tpu.models.bert import (
        BertForClassification,
        bert_param_shardings,
        classification_loss_fn,
    )

    cfg = _config(bool(args.get("tiny")))
    model = BertForClassification(config=cfg, num_classes=NUM_CLASSES)
    mesh = make_mesh()
    feed = ctx.get_data_feed(
        train_mode=True, input_mapping={"tokens": "tokens", "label": "label"}
    )
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((2, SEQ), np.int32)
    )["params"]
    psh = bert_param_shardings(params, mesh)
    params = jax.tree.map(jax.device_put, params, psh)
    tx = optax.adamw(float(args.get("lr", 1e-3)))
    state = TrainState.create(params, tx)
    step = build_train_step(
        classification_loss_fn(model), tx, mesh, param_shardings=psh
    )

    def prepare(cols):
        return {
            "tokens": np.asarray(cols["tokens"], np.int32),
            "label": np.asarray(cols["label"], np.int32),
        }

    loss = None
    with DevicePrefetcher.from_feed(
        feed,
        int(args["batch_size"]),
        mesh,
        multiple_of=jax.device_count(),
        prepare=prepare,
    ) as pf:
        for batch in pf:
            state, loss = step(state, batch)
    print(f"node{ctx.executor_id} final loss {float(loss):.4f}")
    ctx.export_saved_model(jax.device_get(state.params), args["export_dir"])

    if ctx.is_chief and args.get("aot_dir"):
        from tensorflowonspark_tpu.api.export import export_model

        def apply_fn(params, batch):
            logits = model.apply({"params": params}, batch["tokens"])
            return {"label": jax.numpy.argmax(logits, -1)}

        export_model(
            apply_fn,
            jax.device_get(state.params),
            {"tokens": np.zeros((2, SEQ), np.int32)},
            ctx.absolute_path(args["aot_dir"]),
            input_mapping={"tokens": "tokens"},
            output_mapping={"label": "prediction"},
        )
        print(f"AOT artifact exported to {args['aot_dir']}")


def export_fn(args):
    """(apply_fn, target_state) for TFModel.transform."""
    import jax
    import numpy as np

    from tensorflowonspark_tpu.models.bert import BertForClassification

    cfg = _config(bool(args.get("tiny")))
    model = BertForClassification(config=cfg, num_classes=NUM_CLASSES)
    target = model.init(
        jax.random.PRNGKey(0), np.zeros((2, SEQ), np.int32)
    )["params"]

    def apply_fn(params, batch):
        logits = model.apply({"params": params}, batch["tokens"].astype("int32"))
        return {"prediction": jax.numpy.argmax(logits, -1)}

    return apply_fn, target


if __name__ == "__main__":
    import numpy as np

    from tensorflowonspark_tpu.api.pipeline import TFEstimator
    from tensorflowonspark_tpu.launcher import cluster_args_from_env
    from tensorflowonspark_tpu.utils.util import cpu_only_env

    p = argparse.ArgumentParser()
    p.add_argument("--export-dir", required=True)
    p.add_argument("--aot-dir", default=None)
    p.add_argument("--records", type=int, default=2048)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()
    largs = cluster_args_from_env()

    records = make_records(args.records)
    est = TFEstimator(
        train_fn,
        {
            "export_dir": args.export_dir,
            "aot_dir": args.aot_dir,
            "batch_size": args.batch_size,
            "tiny": args.tiny,
        },
        export_fn=export_fn,
        cluster_size=largs["num_executors"],
        epochs=args.epochs,
        batch_size=args.batch_size,
        export_dir=args.export_dir,
        input_mapping={"tokens": "tokens", "label": "label"},
    )
    model = est.fit(
        records, env=cpu_only_env() if args.cpu else None
    )

    test = make_records(256, seed=1)
    model.args.input_mapping = {"tokens": "tokens", "label": "label"}
    model.args.output_mapping = {"prediction": "prediction"}
    preds = model.transform(test)
    correct = sum(
        int(np.asarray(p["prediction"]).reshape(())) == label
        for p, (_, label) in zip(preds, test)
    )
    print(f"bert_estimator accuracy: {correct}/{len(test)}")
