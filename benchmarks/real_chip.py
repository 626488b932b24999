"""Single-chip benchmark runner: one config per invocation.

Runs entirely in the main process — the process that touches JAX holds
the chip, so nothing here starts a child that needs it — and prints one
JSON line: step time, examples/sec(/chip), and MFU, with the ``backend``,
``device_kind`` and ``chips`` it ran on.

MFU accounting: transformers use the standard 6*P*T model-flops rule
(fwd+bwd, no attention or remat term); ResNet uses 3x its 4.1 GFLOP
forward. The per-chip peak comes from ``benchmarks/peaks.py`` by
``device_kind``; a kind that is not in that table is an error.

A benchmark number comes only from the chip: unless the platform JAX
picked is ``tpu`` the run exits non-zero and says why
(``BENCH_ALLOW_CPU=1`` rehearses the flow on the CPU; no utilisation is
computed then).

Usage::

    python benchmarks/real_chip.py --config resnet50 [--steps 30] ...

Configs: mnist, resnet50, inception_v3, bert_base, llama1b,
llama1b_decode (KV-cache decode; --new-tokens sets the decode length,
step_time_ms is one single-token step, examples_per_sec is tokens/sec),
llama1b_engine, llama1b_prefix.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(
    0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), ".."))
)

import argparse
import json
import time

# Set by --profile: after each config's timed loop, a few extra steps run
# under jax.profiler.trace, so the run yields a trace to read the MFU gap
# from without polluting the timed numbers.
_PROFILE_DIR = None


def _maybe_trace(run_steps) -> None:
    """Trace a short post-timing window; ``run_steps(n)`` must execute n
    steps and end with a host-fetch barrier."""
    if not _PROFILE_DIR:
        return
    import jax

    with jax.profiler.trace(_PROFILE_DIR):
        run_steps(5)
    # stderr: stdout is the machine-readable JSONL stream
    print(f"profile trace written to {_PROFILE_DIR}", file=_sys.stderr, flush=True)


def _bench_step(step, state, make_batch, steps: int, warmup: int = 3):
    """Time `steps` executions of step(state, batch); return (state, dt).

    The barrier is a host fetch of the loss scalar, which the result
    needs anyway. On the attached v5e it times the same as
    ``block_until_ready`` (PR 21 chip run: a 100-matmul chain read
    0.5894 s under one and 0.5899 s under the other), so either is
    sound. The batch is put on device once and reused so the timing
    measures the train step, not the host->device transfer.
    """
    batch = make_batch()  # device-resident, reused every step
    for _ in range(warmup):
        state, loss = step(state, batch)
    float(loss)  # host fetch = real barrier
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step(state, batch)
    loss = float(loss)
    dt = time.perf_counter() - t0

    def run_steps(n):
        # thread the live state (step may donate its input buffers);
        # the returned float loss stays untouched
        nonlocal state
        for _ in range(n):
            state, l = step(state, batch)
        float(l)

    _maybe_trace(run_steps)
    return state, dt, loss


def bench_mnist(args):
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh, shard_batch
    from tensorflowonspark_tpu.models import mnist

    mesh = make_mesh({"data": len(jax.devices())})
    b = args.batch_size or 1024
    model = mnist.CNN()
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.random((b, 28, 28, 1), dtype=np.float32),
        "label": rng.integers(0, 10, size=b).astype(np.int32),
    }
    params = model.init(jax.random.PRNGKey(0), batch["image"][:2])["params"]
    tx = optax.adam(1e-3)
    state = TrainState.create(params, tx)
    step = build_train_step(mnist.loss_fn(model.apply), tx, mesh)
    make_batch = lambda: shard_batch(mesh, batch)
    state, dt, loss = _bench_step(step, state, make_batch, args.steps)
    return dict(examples=b, dt=dt, loss=loss, flops_fallback=None)


def _bench_bn_model(model, loss_fn, tx, batch, steps, flops_of=None):
    """Shared warm/time loop for BatchNorm models (carried batch_stats).

    Same sync rules as _bench_step: device-resident batch, host-fetch
    barrier. ``flops_of(step_fn, state, stats, dev_batch)`` may supply a
    FLOP count (e.g. XLA cost analysis); None means caller's fallback.
    """
    import jax
    import optax

    from tensorflowonspark_tpu.compute import TrainState
    from tensorflowonspark_tpu.compute.mesh import make_mesh, shard_batch

    mesh = make_mesh({"data": len(jax.devices())})
    variables = model.init(jax.random.PRNGKey(0), batch["image"][:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    state = TrainState.create(params, tx)

    @jax.jit
    def step(state, stats, batch):
        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, stats, batch
        )
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            ),
            new_stats,
            loss,
        )

    dev_batch = shard_batch(mesh, batch)
    flops = flops_of(step, state, batch_stats, dev_batch) if flops_of else None
    for _ in range(3):
        state, batch_stats, loss = step(state, batch_stats, dev_batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, batch_stats, loss = step(state, batch_stats, dev_batch)
    loss = float(loss)  # host fetch = timing barrier
    dt = time.perf_counter() - t0

    def run_steps(n):
        nonlocal state, batch_stats
        for _ in range(n):
            state, batch_stats, l = step(state, batch_stats, dev_batch)
        float(l)

    _maybe_trace(run_steps)
    return dt, loss, flops


def bench_resnet50(args):
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models import resnet

    b = args.batch_size or 256
    model = resnet.ResNet(resnet.ResNetConfig.resnet50())
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.random((b, 224, 224, 3), dtype=np.float32),
        "label": rng.integers(0, 1000, size=b).astype(np.int32),
    }
    dt, loss, _ = _bench_bn_model(
        model, resnet.loss_fn(model), optax.sgd(0.1, momentum=0.9),
        batch, args.steps,
    )
    # ResNet-50 training ≈ 3x forward (4.1 GFLOPs) per image
    return dict(
        examples=b, dt=dt, loss=loss, flops_fallback=3 * 4.1e9 * b
    )


def bench_inception_v3(args):
    """Inception-v3 (the reference's headline scaling-chart model)."""
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models import inception

    b = args.batch_size or 128
    model = inception.InceptionV3(inception.InceptionConfig.v3())
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.random((b, 299, 299, 3), dtype=np.float32),
        "label": rng.integers(0, 1000, size=b).astype(np.int32),
    }

    def flops_of(step, state, stats, dev_batch):
        # honest FLOP count from XLA's own cost analysis (covers the
        # SAME-padding grid variant exactly). cost_analysis reports the
        # per-device SPMD module, so scale by chip count to match the
        # global-batch flops convention of the other configs (main()
        # divides by n_chips for the per-chip MFU).
        import jax

        try:
            cost = step.lower(state, stats, dev_batch).compile().cost_analysis()
            return float(cost.get("flops", 0.0)) * len(jax.devices()) or None
        except Exception:
            return None

    dt, loss, flops = _bench_bn_model(
        model, inception.loss_fn(model), optax.sgd(0.045, momentum=0.9),
        batch, args.steps, flops_of=flops_of,
    )
    # fallback: the classic 3x5.7 GF/img training estimate
    return dict(
        examples=b, dt=dt, loss=loss, flops_fallback=flops or 3 * 5.7e9 * b
    )


def bench_bert_base(args):
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh, shard_batch
    from tensorflowonspark_tpu.models import bert

    mesh = make_mesh({"data": len(jax.devices())})
    b = args.batch_size or 64
    seq = args.seq or 128
    cfg = bert.BertConfig(vocab_size=30522, max_seq_len=seq)
    model = bert.BertForMLM(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, size=(b, seq)).astype(
            np.int32
        ),
        "targets": rng.integers(0, cfg.vocab_size, size=(b, seq)).astype(
            np.int32
        ),
    }
    params = model.init(jax.random.PRNGKey(0), batch["tokens"][:2])["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    tx = optax.adamw(1e-4)
    state = TrainState.create(params, tx)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]
        ).mean()

    step = build_train_step(loss_fn, tx, mesh)
    make_batch = lambda: shard_batch(mesh, batch)
    state, dt, loss = _bench_step(step, state, make_batch, args.steps)
    return dict(
        examples=b,
        dt=dt,
        loss=loss,
        flops_fallback=6 * n_params * b * seq,
    )


def bench_llama1b(args):
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh, shard_batch
    from tensorflowonspark_tpu.models.llama import (
        Llama,
        LlamaConfig,
        llama_loss_fn,
        llama_param_shardings,
    )
    from tensorflowonspark_tpu.parallel import use_mesh
    import jax.numpy as jnp

    # mesh_axis="data" puts the bench in the pure data-parallel regime
    # (replicated params, replicated optimizer pre-ZeRO) — the
    # bench.py --zero A/B leg's configuration, where the cross-replica
    # sharded weight update (zero_sharding, arXiv 2004.13336) is the
    # variable under test. The default stays the FSDP headline config.
    mesh_axis = getattr(args, "mesh_axis", "fsdp")
    mesh = make_mesh({mesh_axis: len(jax.devices())})
    zero_sharding = getattr(args, "zero_sharding", True)
    b = args.batch_size or 8
    seq = args.seq or 1024
    # model_scale="tiny" swaps in the smoke-test decoder so the WHOLE
    # bench flow (state build, sharded step, timing, JSON assembly) can
    # run on CPU in seconds — bench.py's BENCH_SMOKE de-risk path
    scale = getattr(args, "model_scale", "1b")
    make_cfg = LlamaConfig.tiny if scale == "tiny" else LlamaConfig.llama_1b
    cfg = make_cfg(
        max_seq_len=seq,
        remat=getattr(args, "remat", "full") != "none",
        remat_policy=getattr(args, "remat", "full"),
        attention_impl=args.attention,
        sliding_window=getattr(args, "window", None),
    )
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    tokens0 = np.zeros((2, seq + 1), np.int32)
    with use_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0), tokens0[:, :-1])["params"]
    # HBM-footprint knobs (see compute/optim.py): on a 16 GB chip the
    # fp32-everything state is what caps MFU, not the matmuls.
    precision = getattr(args, "precision", "fp32")
    moments = getattr(args, "moments", "fp32")
    moment_dtype = jnp.bfloat16 if moments == "bf16" else None
    if precision == "mixed":
        from tensorflowonspark_tpu.compute import mixed_precision_adamw

        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        tx = mixed_precision_adamw(1e-4, moment_dtype=moment_dtype)
    elif moment_dtype is not None:
        from tensorflowonspark_tpu.compute import optim

        tx = optim.adamw(1e-4, moment_dtype=moment_dtype)
    else:
        tx = optax.adamw(1e-4)
    psh = llama_param_shardings(params, mesh)
    params = jax.tree.map(jax.device_put, params, psh)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    # shard_state (not bare create): commits the optimizer tree to the
    # layout-table shardings — with zero_sharding on, the Adam moments
    # land data-partitioned at init instead of being resharded by the
    # first jitted step
    from tensorflowonspark_tpu.compute import shard_state

    state = shard_state(
        TrainState.create(params, tx), mesh, psh, zero_sharding=zero_sharding
    )
    token_loss = llama_loss_fn(
        model, logit_chunk=getattr(args, "logit_chunk", None)
    )
    step = build_train_step(
        lambda p, bt: token_loss(p, bt["tokens"]), tx, mesh,
        param_shardings=psh, zero_sharding=zero_sharding,
    )
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, size=(b, seq + 1)).astype(
            np.int32
        )
    }
    make_batch = lambda: shard_batch(mesh, batch)
    with use_mesh(mesh):
        state, dt, loss = _bench_step(step, state, make_batch, args.steps)
    res = dict(
        examples=b,
        dt=dt,
        loss=loss,
        flops_fallback=6 * n_params * b * seq,
        n_params=n_params,
        tokens=b * seq,
    )
    if getattr(args, "params_digest", False):
        res["params_digest"] = _params_digest(state.params)
    if getattr(args, "measure_update", False):
        # LAST: the update-only timing loop donates `state`
        res["weight_update_ms"] = _time_weight_update(
            tx, mesh, psh, state, zero_sharding, args.steps
        )
    return res


def _params_digest(params) -> str:
    """sha256 over the host bytes of every param leaf, in tree-leaf
    order — the byte-identity currency of the --zero A/B gates."""
    import hashlib

    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get(params)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def _time_weight_update(tx, mesh, psh, state, zero_sharding, steps):
    """Isolated optimizer-update time (ms/step): the weight update alone
    against fixed pre-placed gradients (each step consumes the previous
    step's donated state, so the chain serializes; one host fetch at the
    end is the timing barrier) — the 'optimizer-span ms' column of the
    bench.py --zero A/B artifact.
    Also feeds the train_weight_update_seconds histogram +
    train.weight_update span via build_update_step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.compute import (
        build_update_step,
        zero_update_shardings,
    )

    upd = build_update_step(
        tx, mesh, param_shardings=psh, zero_sharding=zero_sharding
    )
    gsh = zero_update_shardings(state.params, mesh, psh) if zero_sharding else psh
    grads = jax.tree.map(
        lambda p, s: jax.device_put(
            jnp.full(p.shape, 1e-4, jnp.float32), s
        ),
        state.params,
        gsh,
    )
    state = upd(state, grads)  # compile + warm
    state = upd(state, grads)
    np.asarray(state.step)  # barrier
    n = max(2, int(steps))
    t0 = time.perf_counter()
    for _ in range(n):
        state = upd(state, grads)
    np.asarray(state.step)  # host fetch: the honest end-of-work barrier
    return round((time.perf_counter() - t0) / n * 1e3, 3)


def update_ab_digests(ns, k: int = 4):
    """Byte-identity probe for the bench.py --zero smoke gate: K
    IDENTICAL-gradient weight updates through the ZeRO-sharded and the
    replicated update step, from the same initial state; returns the
    two final-param sha256 digests. The sharded Adam/decay/lr
    arithmetic is elementwise per leaf, so the cross-replica
    decomposition must be byte-exact here — unlike the full train legs,
    whose gradient REDUCTION order legitimately differs
    (reduce-scatter vs all-reduce summation grouping, ~1 ulp on the
    embedding grad after a few steps)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.compute import (
        TrainState,
        build_update_step,
        shard_state,
        zero_update_shardings,
    )
    from tensorflowonspark_tpu.compute import optim
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.models.llama import (
        Llama,
        LlamaConfig,
        llama_param_shardings,
    )
    from tensorflowonspark_tpu.parallel import use_mesh

    mesh = make_mesh({getattr(ns, "mesh_axis", "data"): len(jax.devices())})
    scale = getattr(ns, "model_scale", "tiny")
    make_cfg = LlamaConfig.tiny if scale == "tiny" else LlamaConfig.llama_1b
    cfg = make_cfg(max_seq_len=ns.seq, remat=False)
    model = Llama(cfg)
    with use_mesh(mesh):
        params = model.init(
            jax.random.PRNGKey(0), np.zeros((2, ns.seq), np.int32)
        )["params"]
    tx = optim.adamw(1e-4, moment_dtype=jnp.bfloat16)
    psh = llama_param_shardings(params, mesh)
    rng = np.random.default_rng(7)
    grads_host = jax.tree.map(
        lambda p: (rng.standard_normal(p.shape) * 1e-2).astype(np.float32),
        params,
    )
    digests = {}
    for zero in (True, False):
        state = shard_state(
            TrainState.create(jax.tree.map(jnp.array, params), tx),
            mesh, psh, zero_sharding=zero,
        )
        gsh = zero_update_shardings(params, mesh, psh) if zero else psh
        grads = jax.tree.map(jax.device_put, grads_host, gsh)
        upd = build_update_step(
            tx, mesh, param_shardings=psh, zero_sharding=zero
        )
        for _ in range(k):
            state = upd(state, grads)
        digests["on" if zero else "off"] = _params_digest(state.params)
    return digests


def _llama1b_decode_setup(args, prompt_len: int | None = None):
    """Shared config/model/prompt build for the decode-side llama1b
    benches — ``llama1b_decode`` and ``llama1b_engine`` are read as a
    same-configuration pair (their delta is the engine's scheduling
    tax), so they must not drift. ``--seq`` overrides the prompt length
    (the KV-traffic knob: at long prompts the per-step cache read
    rivals the weight read, which is what ``--kv-quantize`` halves)."""
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig

    b = args.batch_size or 8
    if prompt_len is None:
        prompt_len = args.seq or 128
    new_tokens = args.new_tokens
    # speculative verification scratches up to spec_k slots past the
    # emitted text
    max_seq = prompt_len + new_tokens + (getattr(args, "spec_k", 0) or 0)
    if getattr(args, "model_scale", "1b") == "tiny":
        # CPU smoke path (--model-scale tiny): the full bench flow in
        # seconds, same shape logic — mirrors bench_llama1b's scale knob
        cfg = LlamaConfig.tiny(
            max_seq_len=max_seq,
            remat=False,
            attention_impl="xla",
            kv_cache_dtype=(
                "int8" if getattr(args, "kv_quantize", False) else "model"
            ),
        )
    else:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_layers=16,
            num_heads=16,
            num_kv_heads=16,
            max_seq_len=max_seq,
            dtype=jnp.bfloat16,
            remat=False,
            attention_impl="xla",  # decode is single-token; flash n/a
            kv_cache_dtype=(
                "int8" if getattr(args, "kv_quantize", False) else "model"
            ),
        )
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    prompt_np = rng.integers(
        0, cfg.vocab_size, size=(b, prompt_len)
    ).astype(np.int32)
    return b, new_tokens, cfg, model, prompt_np


def bench_llama1b_decode(args):
    """KV-cache autoregressive decode: tokens/sec at batch 8."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models.llama import generate

    b, new_tokens, cfg, model, prompt_np = _llama1b_decode_setup(args)
    prompt = jnp.asarray(prompt_np)
    from tensorflowonspark_tpu.ops.quant import quantize_tree

    spec_k = getattr(args, "spec_k", 0) or 0
    if spec_k and getattr(args, "quantize", False):
        # int8 target + int8 draft would be the SAME tree: acceptance
        # trivially 100%, the number would measure nothing
        raise SystemExit("--spec-k measures a bf16 target with an int8 "
                         "draft; drop --quantize")
    raw_params = model.init(jax.random.PRNGKey(0), prompt[:2])["params"]
    params = raw_params
    if getattr(args, "quantize", False):
        # int8 weight-only decode: weights consumed as int8 by the model
        params = quantize_tree(params)
        # the bf16 tree must actually free — this benchmark is HBM-bound
        # by construction (spec_k needs it for the draft; the combo with
        # --quantize is rejected above)
        raw_params = None
    params = jax.tree.map(jax.device_put, params)
    if spec_k:
        # SELF-speculation: the draft is the SAME weights quantized to
        # int8 — it mostly agrees with the bf16 target's argmax (high
        # acceptance) at roughly half the weight-read cost, so this
        # measures speculative decoding with a REAL acceptance profile
        # (a random independent draft would accept ~never).
        from tensorflowonspark_tpu.models.speculative import (
            speculative_generate,
        )

        draft_params = jax.tree.map(
            jax.device_put, quantize_tree(raw_params)
        )

        def decode():
            return speculative_generate(
                model, params, model, draft_params, prompt, new_tokens,
                k=spec_k,
            )

    else:

        def decode():
            return generate(model, params, prompt, new_tokens)

    out = decode()  # compile + warm
    np.asarray(out[0, :1])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = decode()
        np.asarray(out[0, :1])  # host fetch = real barrier
    dt = time.perf_counter() - t0

    def run_steps(n):
        for _ in range(n):
            np.asarray(decode()[0, :1])

    _maybe_trace(run_steps)
    # Reported so that step_time_ms is ONE single-token decode step and
    # examples_per_sec is new tokens/sec: examples = batch rows, dt
    # rescaled by tokens-per-generate.
    return dict(examples=b, dt=dt / new_tokens, loss=0.0)


def bench_llama1b_engine(args):
    """Continuous-batching engine throughput at full occupancy: the same
    1B decode as ``llama1b_decode`` but scheduled by
    ``serving.ContinuousBatcher`` (per-token host sync + slot
    scheduling). The delta vs ``llama1b_decode`` at the same batch IS
    the scheduling tax of token-granular admission; the win it buys —
    no convoying, immediate slot reuse — doesn't show in a
    full-occupancy steady-state number, so read the pair together."""
    import threading

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.serving import ContinuousBatcher

    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(args)
    prompt_len = prompts.shape[1]
    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(prompts[:2])
    )["params"]
    if getattr(args, "quantize", False):
        from tensorflowonspark_tpu.ops.quant import quantize_tree

        params = quantize_tree(params)
    params = jax.tree.map(jax.device_put, params)
    engine = ContinuousBatcher(
        model, params, slots=b, prompt_widths=(prompt_len,)
    )

    def fire_all(n_tokens):
        # Ferry worker-thread failures: a dead engine answers every
        # submit instantly with an error, and a swallowed exception
        # would let a microseconds-long round masquerade as a
        # measurement in the teed artifact.
        errors = [None] * b

        def one(i):
            try:
                engine.submit(prompts[i].tolist(), n_tokens)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errors[i] = e

        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(b)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for e in errors:
            if e is not None:
                raise e

    fire_all(4)  # compile prefill + admit + step, warm the loop
    t0 = time.perf_counter()
    for _ in range(args.steps):
        fire_all(new_tokens)
    dt = time.perf_counter() - t0
    engine.close()
    # Same reporting convention as llama1b_decode (dt rescaled by
    # tokens-per-round): step_time_ms is one single-token engine step at
    # full occupancy, examples_per_sec is tokens/sec across the batch.
    return dict(examples=b, dt=dt / new_tokens, loss=0.0)


def bench_llama1b_prefix(args):
    """Prefix-caching TTFT: requests share a long system prefix (7/8 of
    the prompt) with unique tails. Headline step_time_ms is the WARM
    per-request prefill latency (prefix resumed from the LRU);
    ttft_cold_ms in the same line is the first, miss-path request —
    their ratio is what `--gen-prefix-cache` buys a shared-system-prompt
    workload. Budget is 1 token, isolating prefill + admission."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.serving import ContinuousBatcher

    import dataclasses

    from tensorflowonspark_tpu.models.llama import Llama

    prompt_len = args.seq or 512
    shared_len = prompt_len * 7 // 8
    _, _, cfg, model, _ = _llama1b_decode_setup(args, prompt_len)
    # Every request here decodes 1 token, so the decode setup's
    # prompt+new_tokens KV sizing would inflate every slot AND every
    # prefix-store entry (each a full-max_seq_len single-row cache) by
    # ~50% at defaults — size the cache to this workload instead.
    cfg = dataclasses.replace(cfg, max_seq_len=prompt_len + 8)
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((2, 8), jnp.int32),
    )["params"]
    params = jax.tree.map(jax.device_put, params)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, size=shared_len).tolist()
    tails = rng.integers(
        0, cfg.vocab_size, size=(args.steps + 2, prompt_len - shared_len)
    ).tolist()
    engine = ContinuousBatcher(
        model,
        params,
        slots=4,
        prompt_widths=(prompt_len,),
        prefill_chunk=min(128, cfg.max_seq_len),
        prefix_cache=8,
    )
    try:
        # warm the compiled programs on an unrelated prompt (chunk,
        # sample, admit, step) so cold-vs-warm isolates the PREFIX
        # reuse, not XLA compilation
        engine.submit(
            rng.integers(0, cfg.vocab_size, size=prompt_len).tolist(), 1
        )
        t0 = time.perf_counter()
        engine.submit(shared + tails[0], 1)  # miss: full prefill
        cold = time.perf_counter() - t0
        # Prime the store with the system prefix ITSELF (the documented
        # server-startup pattern): its full-prompt entry lets every
        # warm request resume at shared_len exactly, rather than at the
        # nearest exponential chunk boundary.
        engine.submit(shared, 1)
        hits_before = engine.stats()["prefix_hits"]
        t0 = time.perf_counter()
        for i in range(args.steps):
            engine.submit(shared + tails[i + 1], 1)  # hits: resume
        dt = time.perf_counter() - t0
        stats = engine.stats()
        # Delta, not total: the prime request can itself hit a
        # chunk-boundary entry from the cold request, which would mask
        # a warm-loop miss in a >= total check.
        if stats["prefix_hits"] - hits_before != args.steps:
            raise RuntimeError(
                f"prefix bench expected {args.steps} warm hits, got "
                f"{stats['prefix_hits'] - hits_before} — a warm request "
                f"missed; the headline would include a cold prefill"
            )
    finally:
        engine.close()
    return dict(
        examples=1,
        dt=dt,
        loss=0.0,
        extra={
            "ttft_cold_ms": round(cold * 1e3, 2),
            "prompt_len": prompt_len,
            "shared_len": shared_len,
            "prefix_hits": stats["prefix_hits"],
            "prefix_tokens_saved": stats["prefix_tokens_saved"],
        },
    )


CONFIGS = {
    "mnist": bench_mnist,
    "resnet50": bench_resnet50,
    "inception_v3": bench_inception_v3,
    "bert_base": bench_bert_base,
    "llama1b": bench_llama1b,
    "llama1b_decode": bench_llama1b_decode,
    "llama1b_engine": bench_llama1b_engine,
    "llama1b_prefix": bench_llama1b_prefix,
}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", choices=sorted(CONFIGS), required=True)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--attention", default="auto")
    p.add_argument(
        "--remat", choices=("full", "dots", "none"), default="full"
    )
    p.add_argument(
        "--precision",
        choices=("fp32", "mixed"),
        default="fp32",
        help="llama1b: param storage (mixed = bf16 params + fp32 master)",
    )
    p.add_argument(
        "--moments",
        choices=("fp32", "bf16"),
        default="fp32",
        help="llama1b: Adam moment storage dtype",
    )
    p.add_argument(
        "--logit-chunk",
        type=int,
        default=None,
        help="llama1b: chunked-CE chunk length (skips (B,S,V) logits)",
    )
    p.add_argument(
        "--new-tokens",
        type=int,
        default=256,
        help="decode length for llama1b_decode/llama1b_engine",
    )
    p.add_argument(
        "--quantize",
        action="store_true",
        help="llama1b_decode/llama1b_engine: int8 weight-only decode "
        "(ops/quant.py)",
    )
    p.add_argument(
        "--window",
        type=int,
        default=None,
        help="llama1b: sliding-window attention width (the flash "
        "kernel's window-restricted grids make the step O(S*W) — A/B "
        "against full attention at --seq 4096)",
    )
    p.add_argument(
        "--kv-quantize",
        action="store_true",
        help="llama decode configs: int8 KV cache "
        "(kv_cache_dtype='int8' — halves cache HBM footprint and "
        "per-step cache reads; composes with --quantize)",
    )
    p.add_argument(
        "--spec-k",
        type=int,
        default=0,
        help="llama1b_decode: self-speculative decoding with an int8 "
        "draft of the same model proposing K tokens per verification "
        "(0 = off); output identical to plain greedy",
    )
    p.add_argument(
        "--model-scale",
        choices=("1b", "tiny"),
        default="1b",
        help="llama configs: 'tiny' swaps in the smoke-test decoder so "
        "the full bench flow runs on CPU in seconds",
    )
    p.add_argument(
        "--profile",
        default=None,
        metavar="DIR",
        help="after the timed loop, trace 5 extra steps with "
        "jax.profiler into DIR (TensorBoard-readable; does not touch "
        "the timed numbers)",
    )
    args = p.parse_args(argv)
    global _PROFILE_DIR
    _PROFILE_DIR = args.profile

    import jax

    from benchmarks import peaks

    dev = peaks.bench_device("real_chip")

    res = CONFIGS[args.config](args)
    n_chips = len(jax.devices())
    step_time = res["dt"] / args.steps
    eps = res["examples"] / step_time
    out = {
        "config": args.config,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "chips": n_chips,
        "step_time_ms": round(step_time * 1e3, 2),
        "examples_per_sec": round(eps, 1),
        "examples_per_sec_per_chip": round(eps / n_chips, 1),
        "final_loss": round(res["loss"], 4),
    }
    if res.get("tokens"):
        out["tokens_per_sec_per_chip"] = round(
            res["tokens"] / step_time / n_chips
        )
    if res.get("flops_fallback") and dev.platform == "tpu":
        # a TPU kind without a published peak is an error, not a default
        peak = peaks.peak_for(dev).bf16_tflops
        mfu = res["flops_fallback"] / step_time / n_chips / (peak * 1e12)
        out["mfu_pct"] = round(mfu * 100, 1)
    if res.get("n_params"):
        out["n_params_m"] = round(res["n_params"] / 1e6)
    out.update(res.get("extra", {}))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
