#!/usr/bin/env python3
"""Compile the SambaY serve cell's programs at its real size for a
described v5e, with no chip: ``JAX_PLATFORMS=cpu python3
perfbench/tools/compile_rehearsal_phi4_flash.py <config> [hlo-dir]``.
Prints ``memory_analysis()`` of ``jit_block`` (k = 8, 1), ``jit_prefill``
at every width and ``jit_admit``, and for each how many operations hold a
whole batch leaf of the scan state as a ``copy`` or a ``transpose`` and
how many ``selective_scan`` and ``decode_attention`` kernels it calls;
with ``hlo-dir``, writes each program's HLO there. Nothing runs; no number
here is a chip number."""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perfbench import counts_phi4_flash, harness, weights_phi4_flash  # noqa: E402
from perfbench.drivers import _llama, serve_phi4_flash  # noqa: E402
from perfbench.tools.compile_rehearsal import report  # noqa: E402  (also steers the kernels' dispatch)


def main():
    config = harness.load_json("configs", sys.argv[1] + ".json")
    hlo_dir = sys.argv[2] if len(sys.argv) > 2 else None
    cfg, run = _llama.model_keys(config), config["run"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    from tensorflowonspark_tpu.serving import engine as E

    model = serve_phi4_flash.build_model(config, cfg)
    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: weights_phi4_flash.make_params(cfg, k, jnp.dtype(run["param_dtype"])),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    eng = E.ContinuousBatcher.__new__(E.ContinuousBatcher)  # programs only: no thread, no state
    eng._model, eng._mesh, eng._slots, eng._params = model, None, run["slots"], params
    eng._block_cache, eng._prefill_cache = {}, {}
    b, v, nb = run["slots"], cfg["vocab_size"], E._BIAS_SLOTS
    eng._batch_cache_shapes = eng._cache_shapes(b)
    cache = jax.tree.map(sds, eng._batch_cache_shapes)
    cache_1 = jax.tree.map(sds, eng._single_row_cache_shapes)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)  # noqa: E731
    u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one)  # noqa: E731
    c, N, _, _ = counts_phi4_flash.mamba(cfg)
    state = f"f32\\[{b},{N},{c}\\]"

    def show(name, lowered):
        compiled = lowered.compile()
        report(name, compiled)
        text = compiled.as_text()
        copies = len(re.findall(rf"= {state}\S* (copy|transpose)\(", text))
        kernels = {k: len(re.findall(rf"%{k}[.0-9]* = ", text))
                   for k in ("selective_scan", "decode_attention")}
        print(f"  scan state: {copies} whole copies or transposes; kernels {kernels}",
              flush=True)
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, name.replace(" ", "_") + ".hlo.txt"), "w") as f:
                f.write(text)

    for k in (8, 1):
        args = (params, cache, i32(b), i32(b), f32(b), i32(b), f32(b, 3), u32(b), f32(b, 2),
                f32(b, v), i32(b, nb), f32(b, nb),
                jax.ShapeDtypeStruct((4,), jnp.bool_, sharding=one))
        show(f"decode block k={k}", eng._block_fn(k).lower(*args))
    for w in run["prompt_widths"]:
        args = (params, i32(1, w), i32(1), f32(1), i32(1), f32(1, 3), u32(1), i32(1, nb),
                f32(1, nb))
        show(f"prefill width={w}", eng._prefill_fn(w).lower(*args))
    args = (cache, cache_1, i32(), i32(b), i32(1), i32(b), i32(1), f32(b), f32(1), i32(b),
            i32(1), f32(b, 3), f32(1, 3), u32(b), u32(1), f32(b, 2), f32(1, 2), f32(b, v),
            i32(b, nb), i32(1, nb), f32(b, nb), f32(1, nb))
    show("admit", eng._admit_fn.lower(*args))


if __name__ == "__main__":
    main()
