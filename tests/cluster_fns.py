"""Top-level map_fun functions for cluster e2e tests.

Node processes are spawned (not forked), so these must live in an importable
module — the analog of the reference's pattern of defining ``map_fun`` at
module scope so Spark can pickle it to executors.
"""

from __future__ import annotations

import os


def sum_fn(args, ctx):
    """Trivial SPARK-mode map_fun: sums fed numbers, writes result to a file.

    Mirrors the reference's test_TFCluster 'sum numbers' map_fun.
    """
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    count = 0
    while not feed.should_stop():
        batch = feed.next_batch(16)
        total += sum(r[0] for r in batch)
        count += len(batch)
    out = os.path.join(args["out_dir"], f"node{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(f"{total} {count}")


def square_inference_fn(args, ctx):
    """SPARK-mode inference map_fun: one squared result per input record."""
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
        batch = feed.next_batch(8)
        if batch:
            feed.batch_results([r[0] ** 2 for r in batch])


def failing_fn(args, ctx):
    raise ValueError("intentional failure for error-ferry test")


def poison_inference_fn(args, ctx):
    """Inference map_fun that dies when it sees the poison record —
    mid-stream node-failure tests."""
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
        batch = feed.next_batch(8)
        if any(r[0] == -1 for r in batch):
            raise RuntimeError("poison record consumed")
        if batch:
            feed.batch_results([r[0] ** 2 for r in batch])


def file_reader_fn(args, ctx):
    """TENSORFLOW-mode map_fun: nodes read their own data (no feed)."""
    path = ctx.absolute_path(args["data_file"])
    with open(path) as f:
        values = [int(line) for line in f]
    # shard by executor like a real per-host reader would
    mine = values[ctx.executor_id :: ctx.num_workers]
    out = os.path.join(args["out_dir"], f"node{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(str(sum(mine)))


def manifest_drain_fn(args, ctx):
    """SPARK-mode map_fun consuming FileManifest records: the driver
    ships paths, this node reads the files locally (the node-side
    feeder pattern — past the push plane's ceiling)."""
    from tensorflowonspark_tpu.feed.manifest import ManifestFeed

    feed = ManifestFeed(ctx.get_data_feed())
    rows = []
    while not feed.should_stop():
        rows.extend(feed.next_batch(4))
    out = os.path.join(args["out_dir"], f"node{ctx.executor_id}.txt")
    with open(out, "w") as f:
        for r in rows:
            f.write(f"{r}\n")


def _fit_linear(ctx, batch_size: int):
    """Shared feed-loop fitting y = w*x + b with a jitted SGD step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    feed = ctx.get_data_feed(train_mode=True)

    @jax.jit
    def step(params, x, y):
        def loss_fn(p):
            return jnp.mean((p["w"] * x + p["b"] - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(params)
        return {k: params[k] - 0.1 * g[k] for k in params}, loss

    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    loss = None
    while not feed.should_stop():
        batch = feed.next_batch(batch_size)
        if not batch:
            continue
        x = jnp.asarray(np.array([r[0] for r in batch], dtype=np.float32))
        y = jnp.asarray(np.array([r[1] for r in batch], dtype=np.float32))
        params, loss = step(params, x, y)
    return params, loss


def estimator_train_fn(args, ctx):
    """TFEstimator map_fun: fit y = w*x + b on fed records, chief exports."""
    params, _ = _fit_linear(ctx, int(args["batch_size"]))
    ctx.export_saved_model(params, args["export_dir"])


def tfrecord_train_fn(args, ctx):
    """TENSORFLOW-mode estimator train_fn: read the staged TFRecords and
    fit y = w*x + b, chief exports (reference: nodes read files directly
    after _fit staged them)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.data import dfutil

    rows = list(dfutil.loadTFRecords(args["tfrecord_dir"]))
    rows = rows[ctx.executor_id :: ctx.num_workers]
    x = jnp.asarray(np.array([r["x"] for r in rows], np.float32))
    y = jnp.asarray(np.array([r["y"] for r in rows], np.float32))

    @jax.jit
    def step(params):
        def loss_fn(p):
            return jnp.mean((p["w"] * x + p["b"] - y) ** 2)

        g = jax.grad(loss_fn)(params)
        return {k: params[k] - 0.1 * g[k] for k in params}

    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    for _ in range(200):
        params = step(params)
    ctx.export_saved_model(params, args["export_dir"])


def estimator_export_fn(args):
    """Rebuild (apply_fn, target_state) for TFModel.transform."""
    import jax.numpy as jnp

    def apply_fn(state, batch):
        # jit-traced: batch is already an array (N, 1)
        x = batch.reshape(-1).astype(jnp.float32)
        return state["w"] * x + state["b"]

    target = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    return apply_fn, target


def train_linear_fn(args, ctx):
    """A real (tiny) JAX training loop fed through the data plane.

    Fits y = w*x + b on fed (x, y) records with a jitted SGD step, then
    writes the result — the minimum end-to-end slice of SURVEY.md §7
    (queue → DataFeed → jit step → export).
    """
    params, loss = _fit_linear(ctx, 32)

    out = os.path.join(args["out_dir"], f"node{ctx.executor_id}.json")
    with open(out, "w") as f:
        import json

        json.dump(
            {"w": float(params["w"]), "b": float(params["b"]),
             "loss": float(loss) if loss is not None else None},
            f,
        )


def terminate_after_fn(args, ctx):
    """Consume until ``limit`` records, then DataFeed.terminate (early stop)."""
    feed = ctx.get_data_feed(train_mode=True)
    seen = 0
    while not feed.should_stop() and seen < int(args["limit"]):
        seen += len(feed.next_batch(8))
    feed.terminate()
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.txt"), "w"
    ) as f:
        f.write(str(seen))


def stalling_consumer_fn(args, ctx):
    """Reads one batch then stops pulling forever (feed-timeout injection)."""
    import time

    feed = ctx.get_data_feed(train_mode=True)
    feed.next_batch(4)
    time.sleep(600)


def crashing_consumer_fn(args, ctx):
    """Reads one batch then hard-crashes the node process (no error ferry)."""
    feed = ctx.get_data_feed(train_mode=True)
    feed.next_batch(4)
    os._exit(3)


def distributed_allgather_fn(args, ctx):
    """Join jax.distributed (done by run_node), allgather across processes.

    The CPU analog of multi-host pod wiring: N spawned processes, one
    coordinator address from the roster, a real cross-process collective.
    """
    import json

    import jax
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray([ctx.executor_id], np.int32)
    )
    out = {
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "global_devices": len(jax.devices()),
        "gathered": np.asarray(gathered).reshape(-1).tolist(),
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)


def distributed_train_fn(args, ctx):
    """Multi-controller DP training: every process runs the same jit over
    the global mesh, feeding its local half of the global batch."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh, shard_batch

    mesh = make_mesh()  # all GLOBAL devices, data-parallel

    def loss_fn(params, batch):
        pred = batch["x"] * params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    tx = optax.sgd(0.1)
    state = TrainState.create(params, tx)
    step = build_train_step(loss_fn, tx, mesh)

    # Deterministic global data; each process feeds its own slice.
    rng = np.random.default_rng(0)
    x = rng.normal(size=64).astype(np.float32)
    y = 3.0 * x + 1.5
    n_local = len(x) // ctx.num_workers
    lo = ctx.executor_id * n_local
    local = {"x": x[lo : lo + n_local], "y": y[lo : lo + n_local]}

    loss = None
    for _ in range(60):
        state, loss = step(state, shard_batch(mesh, local))
    out = {
        "w": float(state.params["w"]),
        "b": float(state.params["b"]),
        "loss": float(loss),
        "global_devices": len(jax.devices()),
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)


def role_aware_fn(args, ctx):
    """Branches on role: data-plane nodes consume the feed; the evaluator
    sidecar never touches it (reference eval_node semantics)."""
    out = os.path.join(args["out_dir"], f"node{ctx.executor_id}.txt")
    if ctx.job_name == "evaluator":
        with open(out, "w") as f:
            f.write("evaluator 0")
        return
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    while not feed.should_stop():
        total += sum(r[0] for r in feed.next_batch(16))
    with open(out, "w") as f:
        f.write(f"{ctx.job_name} {total}")


def sum_sizes_fn(args, ctx):
    """Sum len() of byte records; writes 'total count' like sum_fn."""
    import os

    feed = ctx.get_data_feed()
    total = count = 0
    while not feed.should_stop():
        for rec in feed.next_batch(8):
            total += len(rec)
            count += 1
    with open(os.path.join(args["out_dir"], f"node{ctx.executor_id}.txt"), "w") as f:
        f.write(f"{total} {count}")


def distributed_spark_train_fn(args, ctx):
    """Multi-controller DP over the PUSH feed: each process consumes its
    own queue via synchronized_batch_stream, so unequal feeds stop every
    process together instead of deadlocking the psum."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    mesh = make_mesh()  # all GLOBAL devices, data-parallel
    feed = ctx.get_data_feed(
        train_mode=True, input_mapping={"x": "x", "y": "y"}
    )

    def loss_fn(params, batch):
        pred = batch["x"] * params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    tx = optax.sgd(0.1)
    state = TrainState.create(params, tx)
    step = build_train_step(loss_fn, tx, mesh)

    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(mesh.axis_names))
    steps = 0
    loss = None
    for cols in feed.synchronized_batch_stream(8):
        batch = {
            name: jax.make_array_from_process_local_data(
                sharding, np.asarray(cols[name], np.float32)
            )
            for name in ("x", "y")
        }
        state, loss = step(state, batch)
        steps += 1
    # Drain whatever this process's queue still holds (the agreement may
    # stop all processes while the longer feeds have records left) so the
    # driver's feeders aren't stuck on a full queue.
    feed.terminate()
    out = {
        "w": float(state.params["w"]),
        "b": float(state.params["b"]),
        "steps": steps,
        "global_devices": len(jax.devices()),
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)


def flaky_checkpoint_fn(args, ctx):
    """TENSORFLOW-mode map_fun for the supervised-restart test: node 0
    crashes hard on its first attempt (before 'checkpointing' progress),
    then every node completes on the retry — the whole-cluster restart +
    resume-from-checkpoint convention (SURVEY.md §5.3)."""
    d = args["dir"]
    attempt_file = os.path.join(d, f"attempts{ctx.executor_id}")
    n = int(open(attempt_file).read()) if os.path.exists(attempt_file) else 0
    with open(attempt_file, "w") as f:
        f.write(str(n + 1))
    if ctx.executor_id == 0 and n == 0:
        os._exit(5)  # simulated node crash; no cleanup, like a real one
    with open(os.path.join(d, f"done{ctx.executor_id}"), "w") as f:
        f.write("ok")


def always_crash_fn(args, ctx):
    os._exit(7)


def obs_train_fn(args, ctx):
    """Mapped fed train loop for the cluster-observability e2e: runs a
    tiny jitted step over sliced column batches (recording train.step /
    feed.queue_get spans + registry counters), then writes this node's
    Chrome trace — with its trace_context metadata — so the driver can
    merge it against its own timeline (tools/trace_merge.py)."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.obs import spans as obs_spans

    feed = ctx.get_data_feed(
        train_mode=True, input_mapping={"x": "x", "y": "y"}
    )

    @jax.jit
    def step(params, x, y):
        def loss_fn(p):
            return jnp.mean((p["w"] * x + p["b"] - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(params)
        return {k: params[k] - 0.1 * g[k] for k in params}, loss

    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    steps = 0
    for cols in feed.batch_stream(8):
        with obs_spans.step_span("train.step", steps):
            params, loss = step(
                params,
                jnp.asarray(np.asarray(cols["x"], np.float32)),
                jnp.asarray(np.asarray(cols["y"], np.float32)),
            )
        steps += 1
    out_dir = args["out_dir"]
    obs_spans.get_tracer().write_chrome_trace(
        os.path.join(out_dir, f"node{ctx.executor_id}.trace.json"),
        process_name=f"node{ctx.executor_id} host",
    )
    with open(
        os.path.join(out_dir, f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump({"steps": steps, "loss": float(loss)}, f)


def sleepy_fn(args, ctx):
    """TENSORFLOW-mode map_fun that just sleeps — the SIGKILL target for
    the liveness-plane chaos tests (a killed node must be detected by
    missed heartbeats, not by a feed/shutdown timeout)."""
    import time

    time.sleep(float(args.get("sleep", 120)))


def busy_span_fn(args, ctx):
    """TENSORFLOW-mode map_fun recording work spans forever — the
    SIGKILL target for the flight-recorder e2e: the node's rolling
    flightrec snapshot must carry these final spans to disk even
    though the process never gets to say goodbye."""
    import time

    from tensorflowonspark_tpu.obs import spans as obs_spans

    deadline = time.monotonic() + float(args.get("sleep", 120))
    i = 0
    while time.monotonic() < deadline:
        with obs_spans.span("work.tick", i=i):
            time.sleep(0.05)
        i += 1


def _tiny_llama_fsdp_setup(logit_chunk=None):
    """Shared recipe for the multi-controller FSDP Llama tests: a tiny
    fp32 Llama with params + bf16-moment Adam state sharded over ALL
    processes' devices (the fsdp axis spans the process boundary, where
    a pod's DCN/ICI would sit). Returns (cfg, mesh, psh, state, step);
    seq length is 16 (batches are ``(b, 17)`` token arrays)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.compute import (
        TrainState,
        build_train_step,
        optim,
        shard_state,
    )
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.models.llama import (
        Llama,
        LlamaConfig,
        llama_loss_fn,
        llama_param_shardings,
    )
    from tensorflowonspark_tpu.parallel import use_mesh

    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False, attention_impl="xla")
    model = Llama(cfg)
    mesh = make_mesh({"fsdp": len(jax.devices())})  # spans both processes
    tokens0 = np.zeros((2, 17), np.int32)
    with use_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0), tokens0[:, :-1])["params"]
    psh = llama_param_shardings(params, mesh)
    params = jax.tree.map(jax.device_put, params, psh)
    tx = optim.adamw(1e-2, moment_dtype=jnp.bfloat16)
    # commit ALL state leaves (incl. bf16 moments + step scalar) to their
    # mesh shardings: the restore target's committed placements are what
    # orbax restores to
    state = shard_state(TrainState.create(params, tx), mesh, psh)
    token_loss = llama_loss_fn(model, logit_chunk=logit_chunk)
    step = build_train_step(
        lambda p, b: token_loss(p, b["tokens"]), tx, mesh, param_shardings=psh
    )
    return cfg, mesh, psh, state, step



def _llama_local_batch(mesh, cfg, ctx, seed_base, i):
    """Deterministic GLOBAL batch for step ``i``; each process feeds its
    slice. Pairs with _tiny_llama_fsdp_setup (seq 16 -> (8, 17) tokens)."""
    import numpy as np

    from tensorflowonspark_tpu.compute.mesh import shard_batch

    rng = np.random.default_rng(seed_base + i)
    toks = rng.integers(0, cfg.vocab_size, size=(8, 17)).astype(np.int32)
    n_local = 8 // ctx.num_workers
    lo = ctx.executor_id * n_local
    return shard_batch(mesh, {"tokens": toks[lo : lo + n_local]})

def distributed_llama_fsdp_fn(args, ctx):
    """Multi-controller FSDP: a tiny Llama's params and optimizer state
    sharded over ALL processes' devices (the fsdp axis spans the process
    boundary, where a pod's DCN/ICI would sit), gradients synced by the
    jit-inserted collectives. Every process must observe identical losses."""
    import json

    import jax
    import numpy as np

    from tensorflowonspark_tpu.compute.mesh import shard_batch
    from tensorflowonspark_tpu.parallel import use_mesh

    cfg, mesh, psh, state, step = _tiny_llama_fsdp_setup(logit_chunk=8)
    seq, global_batch = 16, 8

    # deterministic GLOBAL batch; each process feeds its local slice
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(global_batch, seq + 1)).astype(
        np.int32
    )
    n_local = global_batch // ctx.num_workers
    lo = ctx.executor_id * n_local
    local = {"tokens": toks[lo : lo + n_local]}

    losses = []
    with use_mesh(mesh):
        for _ in range(4):
            state, loss = step(state, shard_batch(mesh, local))
            losses.append(float(loss))
    out = {
        "losses": losses,
        "global_devices": len(jax.devices()),
        "process_count": jax.process_count(),
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)


def distributed_llama_ckpt_fn(args, ctx):
    """Multi-controller FSDP checkpoint/resume: the state is sharded over
    BOTH processes' devices, so orbax save/restore is a collective — every
    process calls save (writes its addressable shards; process 0 commits).
    Phase "train": 2 steps -> all-process save -> 2 more steps, recording
    the post-save losses. Phase "resume": restore (collective), assert the
    resumed step, replay the same 2 batches -> losses must be bit-identical
    to phase train's (the checkpoint captured params AND optimizer state
    exactly). Reference parity: SURVEY.md §5.4 multi-host done right."""
    import json

    import jax

    from tensorflowonspark_tpu.compute.checkpoint import (
        CheckpointManager,
        chief_final_save,
        restore_latest,
        saves_on_this_process,
    )
    from tensorflowonspark_tpu.parallel import use_mesh

    cfg, mesh, psh, state, step = _tiny_llama_fsdp_setup()

    def local_batch(i):
        return _llama_local_batch(mesh, cfg, ctx, 1000, i)

    assert saves_on_this_process(is_chief=ctx.is_chief), (
        "multi-controller mode must make EVERY process a save participant"
    )
    ckpt = CheckpointManager(args["model_dir"], async_save=False)
    losses = []
    with use_mesh(mesh):
        if args["phase"] == "train":
            for i in range(2):
                state, loss = step(state, local_batch(i))
            ckpt.save(2, state, force=True)  # collective in-loop save
            for i in range(2, 4):
                state, loss = step(state, local_batch(i))
            chief_final_save(ckpt, state, 4, ctx.is_chief)  # collective
            # post-checkpoint steps: the resume phase must reproduce
            # these losses bit-identically from the step-4 checkpoint
            for i in range(4, 6):
                state, loss = step(state, local_batch(i))
                losses.append(float(loss))
        else:  # resume
            latest, state = restore_latest(ckpt, state)  # collective
            assert latest == args["expect_step"], (latest, args["expect_step"])
            for i in range(latest, latest + 2):
                state, loss = step(state, local_batch(i))
                losses.append(float(loss))
            ckpt.close()

    with CheckpointManager(args["model_dir"]) as reader:
        latest_after = reader.latest_step()
    out = {
        "losses": losses,
        "latest_after": latest_after,
        "process_count": jax.process_count(),
        "global_devices": len(jax.devices()),
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)


def ingest_drain_fn(args, ctx):
    """Pull-plane map_fun: drain this node's driver-published shard
    (ctx.get_ingest_feed) into mapped column batches; write the
    consumed values + the final replay cursor so the e2e can assert
    exact coverage with no driver in the data loop."""
    import json

    import numpy as np

    feed = ctx.get_ingest_feed(
        input_mapping={"x": "x"}, timeout=float(args.get("timeout", 120))
    )
    values = []
    for cols in feed.batch_stream(int(args.get("batch", 8))):
        values.extend(np.ravel(cols["x"]).tolist())
    out = {
        "values": values,
        "cursor": feed.cursor(),
        "plan_epoch": feed.plan_epoch,
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)


def ingest_restart_fn(args, ctx):
    """Pull-plane restart map_fun (run_with_restarts): consumes the
    shard in args['manifests'] batch by batch, persisting the replay
    cursor + consumed values after every batch; attempt 1 crashes hard
    mid-shard, the relaunched attempt seeds the persisted cursor and
    finishes — the consumed union must be exactly-once."""
    import json

    import numpy as np

    from tensorflowonspark_tpu.feed.ingest import IngestFeed

    d = args["dir"]
    state_path = os.path.join(d, f"state{ctx.executor_id}.json")
    state = {"values": [], "cursor": {}, "attempts": 0}
    if os.path.exists(state_path):
        with open(state_path) as f:
            state = json.load(f)
    state["attempts"] += 1
    feed = IngestFeed(args["manifests"], input_mapping={"x": "x"})
    feed.seed_cursor(state["cursor"])
    n_batches = 0
    for cols in feed.batch_stream(int(args.get("batch", 4))):
        state["values"].extend(np.ravel(cols["x"]).tolist())
        state["cursor"] = feed.cursor()
        # persist atomically: the crash below must never half-write
        tmp = state_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f)
        os.replace(tmp, state_path)
        n_batches += 1
        if (
            state["attempts"] == 1
            and ctx.executor_id == 0
            and n_batches >= int(args.get("crash_after", 3))
        ):
            os._exit(5)  # mid-shard crash; no cleanup, like a real one
    with open(os.path.join(d, f"done{ctx.executor_id}"), "w") as f:
        f.write("ok")


def ingest_handover_fn(args, ctx):
    """Live-shard-redistribution map_fun (handover e2e): drains this
    node's driver-published shard through a handover-armed IngestFeed,
    persisting the consumed values + the plan epoch after EVERY batch
    (atomic replace) — so even a SIGKILLed node leaves an exact record
    of what it trained on, which is what the exactly-once accounting
    (zero-gap, duplicates <= one publication interval) is computed
    from. Optional planned leave: after ``leave_after`` batches,
    publish an exact cursor and exit(3) — the cooperative shrink; a
    replacement with the same executor id skips the leave (marker
    file) and consumes its re-split share."""
    import json
    import time

    import numpy as np

    d = args["dir"]
    state_path = os.path.join(d, f"consumed{ctx.executor_id}.json")
    state = {"values": [], "epochs": []}
    if os.path.exists(state_path):
        with open(state_path) as f:
            state = json.load(f)
    feed = ctx.get_ingest_feed(
        input_mapping={"x": "x"},
        timeout=float(args.get("timeout", 120)),
        publish_blocks=int(args.get("publish_blocks", 2)),
    )
    left_marker = os.path.join(d, "left")
    n_batches = 0
    for cols in feed.batch_stream(int(args.get("batch", 4))):
        state["values"].extend(np.ravel(cols["x"]).tolist())
        state["epochs"].append(feed.plan_epoch)
        tmp = state_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f)
        os.replace(tmp, state_path)
        n_batches += 1
        if args.get("step_sleep"):
            time.sleep(float(args["step_sleep"]))
        if (
            args.get("leave_after")
            and ctx.executor_id == int(args.get("leave_id", 1))
            and n_batches >= int(args["leave_after"])
            and not os.path.exists(left_marker)
        ):
            with open(left_marker, "w") as f:
                f.write("1")
            # planned leave: an EXACT cursor first, so the re-split
            # starts precisely where training stopped (zero-dup)
            feed.publish_cursor()
            os._exit(3)
    with open(os.path.join(d, f"done{ctx.executor_id}"), "w") as f:
        f.write("ok")


def online_consumer_fn(args, ctx):
    """Online continual-loop map_fun (chaos e2e): drains a GROWING
    traffic-log dataset through a handover-armed IngestFeed, recording
    every consumed ``trace_id`` after EVERY batch (atomic replace) —
    the exactly-once ledger even across SIGKILL — and, on the chief,
    publishes a real orbax checkpoint to the rollout channel every
    ``ckpt_batches`` batches so the driver-side online loop observes
    trainer progress the same way a serving fleet's watcher would."""
    import json
    import time

    import numpy as np

    d = args["dir"]
    state_path = os.path.join(d, f"consumed{ctx.executor_id}.json")
    state = {"traces": [], "epochs": []}
    if os.path.exists(state_path):
        with open(state_path) as f:
            state = json.load(f)
    feed = ctx.get_ingest_feed(
        input_mapping={"trace_id": "trace_id"},
        timeout=float(args.get("timeout", 120)),
        publish_blocks=int(args.get("publish_blocks", 2)),
    )
    channel = args.get("channel")
    ckpt_every = int(args.get("ckpt_batches", 4))
    n_batches = 0
    for cols in feed.batch_stream(int(args.get("batch", 4))):
        state["traces"].extend(
            str(t).rstrip() for t in np.ravel(cols["trace_id"]).tolist()
        )
        state["epochs"].append(feed.plan_epoch)
        tmp = state_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f)
        os.replace(tmp, state_path)
        n_batches += 1
        if channel and ctx.executor_id == 0 and n_batches % ckpt_every == 0:
            from tensorflowonspark_tpu.serving.rollout import (
                publish_params,
            )

            publish_params(
                channel,
                {"step": np.asarray(n_batches, np.int32)},
                version=f"step-{n_batches:06d}",
                step=n_batches,
            )
        if args.get("step_sleep"):
            time.sleep(float(args["step_sleep"]))
    with open(os.path.join(d, f"done{ctx.executor_id}"), "w") as f:
        f.write("ok")


def _elastic_recipe():
    """Shared pieces of the elastic chaos tests: a tiny linear model
    whose data order is a pure function of the step index (the replay
    cursor contract — any process at step i computes the same batch),
    trained with momentum-SGD so the optimizer state is a real pytree
    that must survive resharding. Returns (loss_fn, tx, make_batch)."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    def loss_fn(params, batch):
        pred = batch["x"] * params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def make_batch(i):
        rng = np.random.default_rng(1000 + i)
        x = rng.normal(size=8).astype(np.float32)
        return {"x": x, "y": 3.0 * x + 1.5}

    return loss_fn, optax.sgd(0.1, momentum=0.9), make_batch


def elastic_reference_params(steps: int) -> dict[str, str]:
    """The uninterrupted run at the same data order: the byte-identity
    oracle the elastic chaos test compares final params against."""
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh, shard_batch

    loss_fn, tx, make_batch = _elastic_recipe()
    mesh = make_mesh({"data": -1})
    state = TrainState.create({"w": jnp.zeros(()), "b": jnp.zeros(())}, tx)
    step_fn = build_train_step(loss_fn, tx, mesh)
    for i in range(steps):
        state, _ = step_fn(state, shard_batch(mesh, make_batch(i)))
    return {
        k: np.asarray(v).tobytes().hex() for k, v in state.params.items()
    }


def elastic_train_fn(args, ctx):
    """TENSORFLOW-mode elastic training loop (compute/elastic.py).

    Deterministic per-step batches, an ElasticTrainer reconfigure
    whenever the membership epoch moves, per-step peer-hydration
    snapshots, and — with ``rejoin=True`` — hydration from a surviving
    peer's in-memory state before training. Writes losses / epochs /
    wall times plus the final params as hex bytes, so the chaos tests
    can assert the loss curve continued across a SIGKILL and the final
    params are byte-identical to an uninterrupted run at the same data
    order."""
    import json
    import time

    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.compute import (
        ElasticTrainer,
        TrainState,
        build_train_step,
    )
    from tensorflowonspark_tpu.compute.mesh import shard_batch

    loss_fn, tx, make_batch = _elastic_recipe()
    trainer = ElasticTrainer(
        ctx,
        axis_shapes={"data": -1},
        checkpoint_dir=args.get("model_dir"),
    )
    mesh = trainer.mesh()

    start, hydrated_via = 0, "fresh"
    state = None
    if args.get("rejoin"):
        step0, state = trainer.hydrate()
        if state is not None:
            start, hydrated_via = int(step0), "peer_or_checkpoint"
    if state is None:
        state = TrainState.create(
            {"w": jnp.zeros(()), "b": jnp.zeros(())}, tx
        )
    step_fn = build_train_step(loss_fn, tx, mesh)

    total = int(args["steps"])
    losses, epochs, times = [], [], []
    i = start
    while i < total:
        if trainer.changed():
            state, mesh = trainer.reconfigure(state)
            step_fn = build_train_step(loss_fn, tx, mesh)
            if trainer.resume_step is not None:
                # checkpoint fallback: rewind and replay the same data
                # order from the restored step
                i = trainer.resume_step
        state, loss = step_fn(state, shard_batch(mesh, make_batch(i)))
        losses.append(float(loss))
        epochs.append(trainer.epoch)
        times.append(time.time())
        trainer.publish(state, i + 1)
        if args.get("step_sleep"):
            time.sleep(float(args["step_sleep"]))
        i += 1

    out = {
        "start": start,
        "hydrated_via": hydrated_via,
        "losses": losses,
        "epochs": epochs,
        "t": times,
        "final_epoch": trainer.epoch,
        "roster_size": len(trainer.roster),
        "mesh_devices": int(trainer.mesh().devices.size),
        "params_hex": {
            k: np.asarray(v).tobytes().hex()
            for k, v in state.params.items()
        },
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)


def distributed_flaky_llama_fn(args, ctx):
    """Multi-controller FSDP under the restart supervisor: attempt 1
    trains 2 steps, saves COLLECTIVELY (every process writes its shards),
    then both processes crash; attempt 2 restores collectively and
    finishes. Composes the three hard pieces: fresh jax.distributed
    coordinator per attempt, cross-process-sharded orbax save/restore,
    and run_with_restarts supervision."""
    import json

    import jax

    from tensorflowonspark_tpu.compute.checkpoint import (
        CheckpointManager,
        restore_latest,
    )
    from tensorflowonspark_tpu.parallel import use_mesh

    cfg, mesh, psh, state, step = _tiny_llama_fsdp_setup()

    def local_batch(i):
        return _llama_local_batch(mesh, cfg, ctx, 2000, i)

    ckpt = CheckpointManager(args["model_dir"], async_save=False)
    latest, state = restore_latest(ckpt, state)  # collective
    start = latest or 0
    losses = []
    with use_mesh(mesh):
        if start == 0:  # first attempt: train, save collectively, die
            for i in range(2):
                state, loss = step(state, local_batch(i))
            ckpt.save(2, state, force=True)
            os._exit(3)
        for i in range(start, start + 2):  # resumed attempt
            state, loss = step(state, local_batch(i))
            losses.append(float(loss))
    ckpt.close()
    out = {
        "resumed_from": start,
        "losses": losses,
        "process_count": jax.process_count(),
    }
    with open(
        os.path.join(args["out_dir"], f"node{ctx.executor_id}.json"), "w"
    ) as f:
        json.dump(out, f)
