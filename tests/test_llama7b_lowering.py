"""The Llama-2-7B FSDP train step traces and lowers on the 8-way mesh.

Shape-level guard for the BASELINE.json headline config ("Llama-2-7B
fine-tune, FSDP over ICI, v4-32"): no 7B-capable hardware exists in CI,
but tracing + StableHLO lowering catches sharding-rule mismatches,
remat/flash-attention composition breaks, and param-count drift without
allocating a single real buffer (everything is ShapeDtypeStructs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflowonspark_tpu.compute import TrainState

pytestmark = pytest.mark.slow  # tracing/lowering the full 7B config
from tensorflowonspark_tpu.compute.mesh import batch_sharding, make_mesh
from tensorflowonspark_tpu.compute.train import state_shardings
from tensorflowonspark_tpu.models.llama import (
    Llama,
    LlamaConfig,
    llama_loss_fn,
    llama_param_shardings,
)
from tensorflowonspark_tpu.parallel import use_mesh


def test_llama2_7b_fsdp_step_lowers():
    mesh = make_mesh({"fsdp": 8})
    cfg = LlamaConfig.llama2_7b()
    model = Llama(cfg)
    seq, b = 4096, 8
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    params_shape = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), tokens
    )["params"]
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree.leaves(params_shape)
    )
    # Llama-2-7B is 6.74B params; drift means the architecture changed.
    assert abs(n_params - 6.74e9) < 0.05e9, n_params

    psh = llama_param_shardings(params_shape, mesh)
    # the big 2D weights must actually shard over fsdp (not replicate)
    sharded = [
        s
        for s, p in zip(jax.tree.leaves(psh), jax.tree.leaves(params_shape))
        if np.prod(p.shape) > 1e6 and "fsdp" in str(s.spec)
    ]
    assert len(sharded) >= cfg.num_layers * 4

    tx = optax.adamw(1e-4)
    state_shape = jax.eval_shape(
        lambda p: TrainState.create(p, tx), params_shape
    )
    token_loss = llama_loss_fn(model)

    def step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: token_loss(p, batch["tokens"])
        )(state.params)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        return (
            TrainState(
                step=state.step + 1,
                params=optax.apply_updates(state.params, updates),
                opt_state=new_opt,
            ),
            loss,
        )

    ssh = state_shardings(state_shape, mesh, psh)
    batch_shape = {"tokens": jax.ShapeDtypeStruct((b, seq + 1), jnp.int32)}
    with use_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(ssh, batch_sharding(mesh)),
            out_shardings=(ssh, None),
        ).lower(state_shape, batch_shape)
    hlo = lowered.as_text()
    # the lowered module carries the mesh sharding annotations XLA will
    # turn into ICI collectives
    assert "sharding" in hlo


def test_llama2_7b_fsdp_hbm_budget():
    """Pre-hardware HBM gate for the v4-32 north-star config: compile
    the PRODUCTION 7B train step (donated state,
    bf16 Adam moments, chunked CE, full remat) on the 8-way virtual mesh
    and bound its per-device memory three ways:

    1. exact, from XLA's per-device memory analysis: the state is
       donated (params+moments alias the output) and its per-device
       bytes match fp32 params + bf16 mu/nu fsdp-sharded 8 ways —
       catches widened moments and broken sharding rules;
    2. analytic, against the v4 chip's 32 GiB HBM: state + fp32 grads +
       the full-remat activation floor (saved layer inputs + one
       layer's recompute live set + chunked-CE buffers) — the
       backend-independent "does the north star fit" estimate;
    3. pinned, on XLA's temp estimate: the CPU scheduler's buffer
       assignment inflates temps ~3.2x vs the chip (calibrated on the
       llama1b config measured on real v5e: 44.6 GiB estimated for a
       step that fits 15.75 GiB), so its absolute value is NOT an HBM
       proxy — but remat silently disabled or (B,S,V) logits
       materialized each add >100 GiB to it, so a pinned bound still
       catches order-of-magnitude regressions.
    """
    import optax

    from tensorflowonspark_tpu.compute import optim

    mesh = make_mesh({"fsdp": 8})
    n_dev = 8
    cfg = LlamaConfig.llama2_7b()
    model = Llama(cfg)
    assert cfg.remat and cfg.remat_policy == "full"
    seq, b = 4096, 8
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    params_shape = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), tokens
    )["params"]
    psh = llama_param_shardings(params_shape, mesh)
    tx = optim.adamw(1e-4, moment_dtype=jnp.bfloat16)
    state_shape = jax.eval_shape(
        lambda p: TrainState.create(p, tx), params_shape
    )
    token_loss = llama_loss_fn(model, logit_chunk=512)

    def step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: token_loss(p, batch["tokens"])
        )(state.params)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        return (
            TrainState(
                step=state.step + 1,
                params=optax.apply_updates(state.params, updates),
                opt_state=new_opt,
            ),
            loss,
        )

    ssh = state_shardings(state_shape, mesh, psh)
    batch_shape = {"tokens": jax.ShapeDtypeStruct((b, seq + 1), jnp.int32)}
    with use_mesh(mesh):
        compiled = (
            jax.jit(
                step,
                in_shardings=(ssh, batch_sharding(mesh)),
                out_shardings=(ssh, None),
                donate_argnums=(0,),
            )
            .lower(state_shape, batch_shape)
            .compile()
        )
    ma = compiled.memory_analysis()  # all fields are PER-DEVICE sizes
    gib = 1 << 30

    # (1a) the state must actually be donated (params+moments alias the
    # output) — without aliasing the 7B state alone would double-count
    assert ma.alias_size_in_bytes >= 0.9 * ma.argument_size_in_bytes

    # (1b) fp32 stored params (bf16 is the COMPUTE dtype) + bf16 mu +
    # bf16 nu = 8 bytes/param, fsdp-sharded 8 ways — the llama1b
    # headline recipe (bf16 moments free 4 bytes/param)
    n_params = 6.74e9
    state_bytes_per_dev = ma.argument_size_in_bytes
    assert state_bytes_per_dev < n_params * 8 / n_dev * 1.15, (
        f"sharded state {state_bytes_per_dev / gib:.2f} GiB/device — "
        "moments widened or params not fsdp-sharded?"
    )

    # (2) analytic per-device peak vs the v4 chip's 32 GiB HBM
    b_local = b // n_dev
    h, layers, ffn, heads = 4096, 32, 11008, 32
    bytes_state = state_bytes_per_dev
    bytes_grads = n_params * 4 / n_dev  # fp32 grad tree, fsdp-sharded
    # full remat saves each layer's input; the backward recompute of ONE
    # layer holds its attention scores (xla impl: (b, heads, S, S) bf16)
    # plus SwiGLU intermediates; chunked CE holds (b, chunk, V) fp32
    # logits twice (fwd + grad)
    bytes_saved = layers * b_local * seq * h * 2
    bytes_recompute = (
        b_local * heads * seq * seq * 2 + 3 * b_local * seq * ffn * 2
    )
    bytes_ce = 2 * b_local * 512 * 32000 * 4
    analytic = (
        bytes_state + bytes_grads + bytes_saved + bytes_recompute + bytes_ce
    )
    assert analytic < 32 * gib, (
        f"analytic estimate {analytic / gib:.2f} GiB/device exceeds the "
        "v4 chip's 32 GiB HBM — the north-star config no longer fits"
    )

    # (3) pinned regression bound on XLA's (CPU-inflated) temp estimate:
    # currently ~197 GiB/device; remat-off or (B,S,V) logits add >100
    assert ma.temp_size_in_bytes < 250 * gib, (
        f"XLA temp estimate {ma.temp_size_in_bytes / gib:.2f} GiB/device "
        "jumped past the pinned bound — remat/chunked-CE regression?"
    )
