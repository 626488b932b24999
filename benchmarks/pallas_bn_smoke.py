"""Chip smoke + rate A/B for the Pallas BN stats kernels.

Two jobs, in ~a minute of chip time on a deliberately SMALL program:

1. De-risk: compile the Pallas stats kernels (`ops/bn_kernels.py`)
   standalone — if the chip's compiler refuses one, we learn it on a
   30 s program, not inside a 15-minute ResNet-50 compile.

2. Evidence: the kernels' premise is that XLA's `convert_reduce_fusion`
   runs well below streaming bandwidth (not measured on this
   installation). This prints the per-pass effective GB/s of the XLA
   reduce pair vs the Pallas kernel on the same ResNet-shaped
   activations, so the premise is measured directly, not inferred from
   a full-model trace.

Output: one JSON line per shape on stdout (machine-readable, tee-able
into benchmarks/results/), human notes on stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def _bench(fn, *args, iters: int = 20):
    import jax

    # Timing barrier = host fetch of one element per output leaf (on
    # the attached v5e it times the same as block_until_ready).
    def fetch(o):
        return [float(x.ravel()[0]) for x in jax.tree.leaves(o)]

    out = fn(*args)
    jax.block_until_ready(out)
    fetch(out)
    t0 = time.perf_counter()
    for _i in range(iters):
        out = fn(*args)
    fetch(out)
    return (time.perf_counter() - t0) / iters


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.ops import bn_kernels
    from tensorflowonspark_tpu.ops.batch_norm import fused_batch_norm

    backend = jax.default_backend()
    if backend != "tpu":
        # no interpret-mode stand-in: a rate comes only from the chip, and
        # whether the chip's compiler accepts these kernels is what
        # tests/test_tpu_compile.py checks without one
        raise SystemExit(
            f"pallas_bn_smoke: no TPU — JAX picked the {backend!r} backend"
        )
    rng = np.random.default_rng(0)

    # ResNet-50 b=256 layer shapes: early (big spatial, narrow C), late
    # (small spatial, wide C) — the two extremes the reduce must handle —
    # PLUS the narrow/non-128-aligned channel counts the adopting models
    # actually have (Inception-v3 BN at C=32/48/80, ResNet stem C=64):
    # sub-128-lane column blocks are where Mosaic tiling constraints
    # bite, so de-risk them here on a 30 s program, not in the conv-net
    # compile.
    shapes = [
        (256 * 56 * 56, 256),
        (256 * 14 * 14, 1024),
        (256 * 112 * 112, 32),  # Inception stem
        (256 * 56 * 56, 48),  # Inception narrow branch
        (256 * 28 * 28, 80),  # Inception 5b input
        (256 * 56 * 56, 64),  # ResNet stem
    ]
    for rows, cols in shapes:
        x = jnp.asarray(rng.standard_normal((rows, cols), np.float32), jnp.bfloat16)
        dy = jnp.asarray(rng.standard_normal((rows, cols), np.float32), jnp.bfloat16)
        stream_gb = rows * cols * 2 / 1e9

        xla_pair = jax.jit(
            lambda a: (
                jnp.sum(a.astype(jnp.float32), 0),
                jnp.sum(a.astype(jnp.float32) ** 2, 0),
            )
        )
        pallas_pair = jax.jit(bn_kernels.pair_stats)
        pallas_cross = jax.jit(bn_kernels.cross_stats)

        t_xla = _bench(xla_pair, x)
        t_pl = _bench(pallas_pair, x)
        t_cr = _bench(pallas_cross, dy, x)
        print(
            json.dumps(
                {
                    "config": "pallas_bn_smoke",
                    "backend": backend,
                    "rows": rows,
                    "cols": cols,
                    "xla_pair_ms": round(t_xla * 1e3, 3),
                    "pallas_pair_ms": round(t_pl * 1e3, 3),
                    "pallas_cross_ms": round(t_cr * 1e3, 3),
                    "xla_pair_gbps": round(stream_gb / t_xla, 1),
                    "pallas_pair_gbps": round(stream_gb / t_pl, 1),
                    "pallas_cross_gbps": round(2 * stream_gb / t_cr, 1),
                }
            ),
            flush=True,
        )

    # Full fwd+bwd through the custom VJP (the program ResNet will run).
    # impl="pallas" explicitly: "auto" always takes the XLA branch.
    fb_shape = (64, 28, 28, 256)
    x4 = jnp.asarray(rng.standard_normal(fb_shape, np.float32), jnp.bfloat16)
    g = jnp.ones((fb_shape[-1],), jnp.float32)
    b = jnp.zeros((fb_shape[-1],), jnp.float32)

    @jax.jit
    def fwd_bwd(x, g, b):
        def loss(x, g, b):
            y = fused_batch_norm(x, g, b, 1e-5, impl="pallas")
            return jnp.sum(y.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(x, g, b)

    t_fb = _bench(fwd_bwd, x4, g, b, iters=10)
    print(
        json.dumps(
            {
                "config": "pallas_bn_smoke_fwdbwd",
                "backend": backend,
                "shape": list(x4.shape),
                "fwd_bwd_ms": round(t_fb * 1e3, 3),
            }
        ),
        flush=True,
    )
    print("pallas BN smoke complete", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
