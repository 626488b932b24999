#!/usr/bin/env python3
"""Compile a cell's programs at its real size for a described v5e, with no
chip: ``JAX_PLATFORMS=cpu python3 perfbench/tools/compile_rehearsal.py
<config> [rows] [remat]``. Prints ``memory_analysis()`` of each program.
Nothing runs; no number here is a chip number."""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import harness, weights  # noqa: E402
from perfbench.drivers import _llama  # noqa: E402

# the kernels are picked by asking JAX for its backend, which is the CPU
# here: steer the dispatch from this script, as the program's own compile
# tests do
from tensorflowonspark_tpu.ops import attention as _attention  # noqa: E402

_attention.TREAT_AS_TPU = True

GB = 1e9


def report(name, compiled):
    ma = compiled.memory_analysis()
    arg, out, alias, temp = (getattr(ma, k + "_size_in_bytes") for k in
                             ("argument", "output", "alias", "temp"))
    print(f"{name}: args {arg / GB:.2f} out {out / GB:.2f} alias {alias / GB:.2f} "
          f"temp {temp / GB:.2f} -> peak {(arg + out - alias + temp) / GB:.2f} GB; "
          f"tpu_custom_call {compiled.as_text().count('tpu_custom_call')}", flush=True)


def main():
    config = harness.load_json("configs", sys.argv[1] + ".json")
    cfg, run = _llama.model_keys(config), dict(config["run"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    if "rows_per_step" in run:
        if len(sys.argv) > 2:
            run["rows_per_step"] = int(sys.argv[2])
        if len(sys.argv) > 3:
            run["remat"] = sys.argv[3]
        from tensorflowonspark_tpu.compute import TrainState, build_train_step
        from tensorflowonspark_tpu.compute.mesh import MESH_AXES, batch_sharding
        from tensorflowonspark_tpu.compute.train import state_shardings
        from tensorflowonspark_tpu.models.llama import llama_loss_fn, llama_param_shardings

        from perfbench.drivers import train

        mesh = Mesh(np.asarray([dev]).reshape([1] * len(MESH_AXES)), MESH_AXES)
        remat = run["remat"]
        model, tx = train.make_model({**config, "run": run}), train.make_tx(run)
        params = jax.eval_shape(
            lambda k: weights.make_params(cfg, k, jnp.dtype(run["param_dtype"])),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        psh = llama_param_shardings(params, mesh)
        state = jax.eval_shape(lambda p: TrainState.create(p, tx), params)
        ssh = state_shardings(state, mesh, psh, True)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), state, ssh)
        shape = (run["rows_per_step"], run["max_seq_len"] + 1)
        batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=batch_sharding(mesh, 2))
                 for k in ("tokens", "segment_ids")}
        token_loss = llama_loss_fn(model)
        step = build_train_step(
            lambda p, bt: token_loss(p, bt["tokens"], bt["segment_ids"]), tx, mesh,
            param_shardings=psh)
        report(f"train step rows={shape[0]} remat={remat}", step.lower(state, batch).compile())
        return

    from tensorflowonspark_tpu.models.llama import Llama
    from tensorflowonspark_tpu.serving.engine import ContinuousBatcher

    model = Llama(_llama.llama_config(config))
    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: weights.make_params(cfg, k, jnp.dtype(run["param_dtype"])),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    eng = ContinuousBatcher.__new__(ContinuousBatcher)  # programs only: no thread, no state
    eng._model, eng._mesh, eng._slots, eng._params = model, None, run["slots"], params
    eng._block_cache, eng._prefill_cache = {}, {}
    b = run["slots"]
    cache = jax.tree.map(sds, eng._cache_shapes(b))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)  # noqa: E731
    u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one)  # noqa: E731
    v = cfg["vocab_size"]
    from tensorflowonspark_tpu.serving import engine as E

    nb = getattr(E, "_BIAS_SLOTS", 8)
    for k in (8, 1):
        args = (params, cache, i32(b), i32(b), f32(b), i32(b), f32(b, 3), u32(b), f32(b, 2),
                f32(b, v), i32(b, nb), f32(b, nb),
                jax.ShapeDtypeStruct((4,), jnp.bool_, sharding=one))
        report(f"decode block k={k}", eng._block_fn(k).lower(*args).compile())
    for w in run["prompt_widths"]:
        args = (params, i32(1, w), i32(1), f32(1), i32(1), f32(1, 3), u32(1), i32(1, nb),
                f32(1, nb))
        report(f"prefill width={w}", eng._prefill_fn(w).lower(*args).compile())


if __name__ == "__main__":
    main()
