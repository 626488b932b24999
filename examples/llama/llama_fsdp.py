"""Llama fine-tune with FSDP over the ICI mesh — the headline config.

Reference parity: there is no reference equivalent (TFoS topped out at
data-parallel, SURVEY.md §2.3); this is the config BASELINE.json adds:
"Llama-2-7B fine-tune, FSDP over ICI, v4-32, ≥40% MFU". The same script
scales from a tiny CPU smoke run to the real thing by flags: mesh axes,
model size, remat, and checkpoint/resume are all config.

MFU accounting: 6*P*T model flops per token (fwd+bwd) over the measured
step time, against the per-chip peak ``perfbench/peaks.py`` publishes for
the device's ``device_kind`` (a TPU kind missing there is an error; on the
CPU no utilisation is computed).

Usage::

    tpu-submit --num-executors 1 examples/llama/llama_fsdp.py \
        [--model tiny|7b] [--fsdp -1] [--tp 1] [--steps 20] \
        [--seq 512] [--batch-size 8] [--model-dir DIR] [--cpu]
"""

from __future__ import annotations

import os as _os, sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), "..", "..")))

import argparse
import time


def _config(name: str, seq: int):
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.llama import LlamaConfig

    if name == "7b":
        return LlamaConfig(
            hidden_size=4096,
            intermediate_size=11008,
            num_layers=32,
            num_heads=32,
            num_kv_heads=32,
            vocab_size=32000,
            max_seq_len=seq,
            dtype=jnp.bfloat16,
            remat=True,
        )
    return LlamaConfig.tiny(
        hidden_size=256,
        intermediate_size=512,
        num_layers=4,
        num_heads=8,
        num_kv_heads=4,
        vocab_size=1024,
        max_seq_len=seq,
        dtype=jnp.bfloat16,
        remat=True,
    )


def main_fun(args, ctx):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.compute import (
        TrainState,
        build_train_step,
        shard_state,
    )
    from tensorflowonspark_tpu.compute.checkpoint import (
        CheckpointManager,
        chief_final_save,
        restore_latest,
        saves_on_this_process,
    )
    from tensorflowonspark_tpu.compute.mesh import make_mesh, shard_batch
    from tensorflowonspark_tpu.models.llama import (
        Llama,
        llama_loss_fn,
        llama_param_shardings,
    )
    from tensorflowonspark_tpu.parallel import use_mesh

    cfg = _config(args.model, args.seq)
    if args.remat != "full":
        cfg = dataclasses.replace(
            cfg, remat=args.remat != "none", remat_policy=args.remat
        )
    if args.attention != "auto":
        cfg = dataclasses.replace(cfg, attention_impl=args.attention)
    if args.sp > 1:
        # Sequence parallelism: 'ring' rotates KV blocks around the ring
        # (memory-optimal for long S_local); 'ulysses' does two
        # all-to-alls and runs full-sequence attention per head subset
        # (fewer collectives; needs heads divisible by sp).
        cfg = dataclasses.replace(cfg, attention_impl=args.sp_impl)
    model = Llama(cfg)
    mesh = make_mesh(
        {"data": args.dp, "fsdp": args.fsdp, "model": args.tp, "seq": args.sp}
    )
    if ctx.executor_id == 0:
        print(f"mesh: {dict(mesh.shape)}")

    rng = np.random.default_rng(ctx.executor_id)
    # The SP shard_maps need the init batch to divide over (data, fsdp);
    # other impls keep the cheap batch-2 init.
    dp_size = mesh.shape["data"] * mesh.shape["fsdp"]
    init_b = dp_size if cfg.attention_impl in ("ring", "ulysses") else 2
    tokens0 = np.zeros((init_b, args.seq + 1), np.int32)
    with use_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0), tokens0[:, :-1])["params"]
    if args.lora_rank:
        from tensorflowonspark_tpu.ops.lora import add_lora

        # parameter-efficient fine-tune: only rank-r adapters train;
        # the frozen base carries no gradients and no optimizer moments
        params = add_lora(
            params, rank=int(args.lora_rank), rng=jax.random.PRNGKey(1)
        )
    psh = llama_param_shardings(params, mesh)
    params = jax.tree.map(jax.device_put, params, psh)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    moment_dtype = jnp.bfloat16 if args.moments == "bf16" else None
    # standard large-model LR recipe: linear warmup -> cosine decay to
    # 10% of peak; --warmup 0 keeps the constant LR (every optimizer
    # here accepts a schedule callable)
    if args.warmup > 0:
        # The schedule indexes the RESTORED optimizer count on resume, so
        # its horizon must be the TOTAL run length across all legs —
        # --total-steps (kept identical on every resume invocation), not
        # this leg's --steps; otherwise a resumed leg would start past
        # the decay clamp and train entirely at end_value.
        total = args.total_steps or args.steps
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=float(args.lr),
            warmup_steps=args.warmup,
            decay_steps=max(total, args.warmup + 1),
            end_value=0.1 * float(args.lr),
        )
    else:
        lr = float(args.lr)
    if args.precision == "mixed":
        from tensorflowonspark_tpu.compute import mixed_precision_adamw

        # bf16 stored params + fp32 master in the optimizer state
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        tx = mixed_precision_adamw(lr, moment_dtype=moment_dtype)
    elif args.moments == "bf16":
        from tensorflowonspark_tpu.compute import optim

        tx = optim.adamw(lr, moment_dtype=jnp.bfloat16)
    else:
        tx = optax.adamw(lr)
    if args.clip > 0:
        # global-norm clip BEFORE the optimizer (the usual transformer
        # training guard against loss spikes)
        tx = optax.chain(optax.clip_by_global_norm(float(args.clip)), tx)
    if args.lora_rank:
        from tensorflowonspark_tpu.ops.lora import lora_optimizer

        # masks moments down to the adapters — the HBM win
        tx = lora_optimizer(tx, params)
    # commit ALL state leaves (moments, masters, step scalar) to their
    # mesh shardings — required for checkpoint restore to reproduce
    # placements exactly under multi-controller FSDP
    state = shard_state(TrainState.create(params, tx), mesh, psh)
    token_loss = llama_loss_fn(model, logit_chunk=args.logit_chunk)
    weight_fn = None
    if args.packed:
        from tensorflowonspark_tpu.models.llama import packed_valid_count

        loss_fn = lambda p, b: token_loss(  # noqa: E731
            p, b["tokens"], segment_ids=b["segment_ids"]
        )
        # exact token weighting under accumulation: packed microbatches
        # have data-dependent valid counts, so weight each by its count
        weight_fn = lambda b: packed_valid_count(b["segment_ids"])  # noqa: E731
    else:
        loss_fn = lambda p, b: token_loss(p, b["tokens"])  # noqa: E731
    step = build_train_step(
        loss_fn, tx, mesh, param_shardings=psh, accum_steps=args.accum,
        batch_weight_fn=weight_fn,
    )

    ckpt = None
    if args.model_dir:
        ckpt = CheckpointManager(
            ctx.absolute_path(args.model_dir),
            save_interval_steps=args.save_every or 1,
        )
        latest, restored = restore_latest(ckpt, state)
        if latest is not None:
            if ctx.is_chief:
                print(f"resuming from step {latest}")
            state = restored

    if args.packed:
        from tensorflowonspark_tpu.data.packing import pack_batches

        def synthetic_docs():
            # variable-length documents, the shape real corpora have
            lo = min(8, max(1, args.seq // 2))
            hi = max(lo + 1, args.seq)
            while True:
                n = int(rng.integers(lo, hi))
                yield rng.integers(1, cfg.vocab_size, size=n).tolist()

        packed_iter = pack_batches(
            synthetic_docs(), args.batch_size, args.seq
        )

        def batch():
            return next(packed_iter)

    else:

        def batch():
            return {
                "tokens": rng.integers(
                    0, cfg.vocab_size, size=(args.batch_size, args.seq + 1)
                ).astype(np.int32)
            }

    with use_mesh(mesh):
        # compile + warmup excluded from timing
        state, loss = step(state, shard_batch(mesh, batch()))
        jax.block_until_ready(loss)
        # host-side step counter: int(state.step) inside the loop would
        # force a device sync every iteration and kill async dispatch
        step_base = int(state.step)
        t0 = time.time()
        for i in range(args.steps):
            state, loss = step(state, shard_batch(mesh, batch()))
            if (i + 1) % 10 == 0:
                print(
                    f"node{ctx.executor_id} step {i + 1} "
                    f"loss {float(loss):.4f}"
                )
            if (
                ckpt is not None
                and args.save_every
                and saves_on_this_process(ctx.is_chief)
            ):
                # async save overlapped with the next steps; the manager's
                # save_interval policy decides which steps actually land.
                # Under multi-controller FSDP the state is sharded across
                # processes, so EVERY process participates in the save.
                ckpt.save(step_base + 1 + i, state)
        jax.block_until_ready(loss)
    dt = time.time() - t0

    step_time = dt / args.steps
    tokens_per_step = args.batch_size * args.seq
    device = jax.devices()[0]
    line = (
        f"node{ctx.executor_id}: {n_params / 1e6:.1f}M params on "
        f"{jax.device_count()} x {device.device_kind}, "
        f"step {step_time * 1e3:.1f}ms, "
        f"{tokens_per_step / step_time:.0f} tokens/sec "
        f"({tokens_per_step / step_time / jax.device_count():.0f} /chip)"
    )
    if device.platform == "tpu":
        from perfbench.peaks import peak_for

        model_flops = 6 * n_params * tokens_per_step  # fwd+bwd, no attn term
        mfu = model_flops / step_time / jax.device_count() / (
            peak_for(device.device_kind)["flops"]
        )
        line += f", MFU {mfu * 100:.1f}%"
    print(line)
    if ckpt is not None:
        # Single-controller: chief-only (independent replicas would race
        # on the directory). Multi-controller: collective all-process save
        # of the cross-process-sharded state. chief_final_save picks.
        chief_final_save(ckpt, state, int(state.step), ctx.is_chief)
        if ctx.is_chief:
            print(f"checkpointed step {int(state.step)} to {args.model_dir}")

    if args.generate:
        from tensorflowonspark_tpu.models.llama import generate

        # SPMD: every process runs the same decode over the (possibly
        # globally sharded) params; only the chief prints. A device_get of
        # FSDP-sharded params would fail multi-host — keep them on-mesh.
        gen_params = state.params
        if args.lora_rank:
            from tensorflowonspark_tpu.ops.lora import merge_lora

            # fold adapters into plain kernels: zero decode overhead,
            # and quantize_tree below would otherwise descend INTO the
            # LoraTensor and quantize its base out from under lora_apply
            with use_mesh(mesh):
                gen_params = jax.jit(merge_lora)(gen_params)
        if args.quantize_decode:
            from tensorflowonspark_tpu.ops.quant import (
                QuantTensor,
                quantize_tree,
            )

            # int8 weight-only decode (ops/quant.py): the model consumes
            # the quantized tree natively (QDense/quantized_dot), so
            # weights stay int8 through the decode. jit so quantization
            # runs as SPMD on FSDP-sharded (non-fully-addressable) params
            # instead of eagerly; drop the bf16 state so its buffers can
            # actually be freed.
            with use_mesh(mesh):
                gen_params = jax.jit(quantize_tree)(gen_params)
            state = None
            n_q = sum(
                isinstance(leaf, QuantTensor)
                for leaf in jax.tree.leaves(
                    gen_params, is_leaf=lambda x: isinstance(x, QuantTensor)
                )
            )
            if ctx.is_chief:
                print(
                    f"quantized {n_q} weight tensors for decode"
                    + (
                        " (NONE met quantize_tree's size threshold — "
                        "tiny configs decode unquantized)"
                        if n_q == 0
                        else ""
                    )
                )
        gen_rng = np.random.default_rng(0)  # same prompt on every process
        prompt = gen_rng.integers(
            0, cfg.vocab_size, size=(2, 8)
        ).astype(np.int32)
        t0 = time.time()
        with use_mesh(mesh):
            out = generate(
                model,
                gen_params,
                jax.numpy.asarray(prompt),
                max_new_tokens=args.generate,
                temperature=args.temperature,
                top_k=args.top_k,
                top_p=args.top_p,
                eos_id=args.eos_id,
            )
        jax.block_until_ready(out)
        dt = time.time() - t0
        if ctx.is_chief:
            out_np = np.asarray(out)
            if args.eos_id is None:
                n_generated = float(args.generate)
            else:
                # count tokens up to and including each row's first EOS;
                # the eos-padded tail was never decoded (early stop)
                hit = out_np == args.eos_id
                first = np.where(
                    hit.any(axis=1), hit.argmax(axis=1) + 1, out_np.shape[1]
                )
                n_generated = float(first.mean())
            print(
                f"generated {n_generated:.1f} tokens/seq (KV-cache "
                f"decode) in {dt:.1f}s: {out_np[0][:10].tolist()}"
            )


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=("tiny", "7b"), default="tiny")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=-1, help="-1: all devices")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel axis size",
    )
    p.add_argument(
        "--sp-impl", choices=("ring", "ulysses"), default="ring",
        help="sequence-parallel strategy",
    )
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument(
        "--warmup",
        type=int,
        default=0,
        help="linear-warmup steps into a cosine decay (0: constant LR)",
    )
    p.add_argument(
        "--total-steps",
        type=int,
        default=0,
        help="cosine-decay horizon across ALL resume legs (0: this "
        "invocation's --steps); keep identical when resuming so the "
        "restored optimizer count lands on a coherent schedule",
    )
    p.add_argument(
        "--clip",
        type=float,
        default=0.0,
        help="global-norm gradient clip (0: off)",
    )
    p.add_argument(
        "--precision",
        choices=("fp32", "mixed"),
        default="fp32",
        help="mixed: bf16 stored params + fp32 master (compute/optim.py)",
    )
    p.add_argument(
        "--moments",
        choices=("fp32", "bf16"),
        default="bf16",
        help="Adam moment storage dtype (bf16 frees 4 bytes/param of HBM)",
    )
    p.add_argument(
        "--accum",
        type=int,
        default=1,
        help="gradient-accumulation microbatches per optimizer step "
        "(batch-size must divide evenly); the HBM lever when the target "
        "global batch's activations exceed memory even after remat",
    )
    p.add_argument(
        "--packed",
        action="store_true",
        help="pack variable-length synthetic documents into each row "
        "(data/packing.py); trains with per-document attention "
        "isolation + boundary/padding loss masking",
    )
    p.add_argument(
        "--logit-chunk",
        type=int,
        default=None,
        help="chunked-CE chunk length; skips the (B,S,V) fp32 logits",
    )
    p.add_argument(
        "--lora-rank",
        type=int,
        default=0,
        help="parameter-efficient fine-tune: wrap attention/MLP kernels "
        "in rank-R LoRA adapters (ops/lora.py) — only adapters train, "
        "the frozen base carries no grads and no optimizer moments "
        "(0 = full fine-tune)",
    )
    p.add_argument("--model-dir", default=None)
    p.add_argument(
        "--save-every",
        type=int,
        default=0,
        help="mid-training checkpoint interval in steps (0: only at end)",
    )
    p.add_argument(
        "--generate",
        type=int,
        default=0,
        help="after training, decode N tokens via the KV cache (chief)",
    )
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument(
        "--eos-id",
        type=int,
        default=None,
        help="stop each row at this token (decode exits early once all "
        "rows finish)",
    )
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument(
        "--quantize-decode",
        action="store_true",
        help="int8 weight-only storage for the --generate decode pass",
    )
    p.add_argument(
        "--remat", choices=("full", "dots", "none"), default="full",
        help="rematerialization policy (none = keep activations)",
    )
    p.add_argument(
        "--attention", choices=("auto", "xla", "flash"), default="auto",
        help="attention impl when not sequence-parallel",
    )
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)
    if (args.top_k is not None or args.top_p is not None) and (
        args.temperature == 0.0
    ):
        # fail at parse time, not after the whole training run
        p.error("--top-k/--top-p require --temperature > 0")
    return args


if __name__ == "__main__":
    from tensorflowonspark_tpu.cluster import tfcluster
    from tensorflowonspark_tpu.cluster.tfcluster import InputMode
    from tensorflowonspark_tpu.launcher import cluster_args_from_env
    from tensorflowonspark_tpu.utils.util import cpu_only_env

    args = parse_args()
    largs = cluster_args_from_env()
    cluster = tfcluster.run(
        main_fun,
        args,
        num_executors=largs["num_executors"],
        input_mode=InputMode.TENSORFLOW,
        env=cpu_only_env() if args.cpu else None,
        launcher=largs.get("launcher"),
        distributed=largs.get("distributed", False),
    )
    cluster.shutdown()
    print("llama_fsdp done")
