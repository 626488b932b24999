#!/usr/bin/env python3
"""Plant one fault underneath the timed path of a latent-attention expert
serve cell and run the cell as ``perfbench/run.py`` does: ``correct`` has
to come out false.

    python3 perfbench/tools/faults_pangu_moe.py --fault <name> \
        --workload <cell> --seed <n> --seconds <s> [--rehearse]

- ``scaling_left_out``: the model is built with ``routed_scaling_factor`` 1;
- ``renorm_left_out``: with ``norm_topk_prob`` false;
- ``shared_left_out``: without its shared expert;
- ``expert_tokens_dropped``: the pairs routed to the fourth held expert
  are dropped before the dispatch, as a capacity limit would drop them;
- ``rope_wrong_dims``: the queries' rotary part is rotated sixteen
  dimensions off, so a query dimension meets its key's under another
  frequency;
- ``post_norm_left_out``: ``post_mlp_norm`` hands its input on;
- ``value_projection_transposed``: the absorbed path reads the value half
  of ``kv_b_proj`` as (rank, width, heads), not (rank, heads, width).

``--sensitivity`` instead reads, in the reference alone on one row of
random tokens, how far zeroing a part moves the logit of each position's
best token (median and 5th percentile over positions): the attention, the
shared expert, the held experts, the rotary part of the keys.

The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@contextlib.contextmanager
def _config_override(**over):
    from tensorflowonspark_tpu.models import pangu_moe

    orig = pangu_moe.from_hf_config
    pangu_moe.from_hf_config = lambda hf, **kw: orig(hf, **{**kw, **over})
    try:
        yield
    finally:
        pangu_moe.from_hf_config = orig


def scaling_left_out():
    return _config_override(routed_scaling_factor=1.0)


def renorm_left_out():
    return _config_override(norm_topk_prob=False)


def shared_left_out():
    return _config_override(n_shared_experts=0)


@contextlib.contextmanager
def expert_tokens_dropped():
    import jax.numpy as jnp

    from tensorflowonspark_tpu.parallel import moe

    orig = moe.dropless_experts

    def dropping(x, weights, experts, w_gate, w_up, w_down, first_held=0):
        experts = jnp.where(experts == first_held + 3, -1, experts)
        return orig(x, weights, experts, w_gate, w_up, w_down, first_held)

    moe.dropless_experts = dropping
    try:
        yield
    finally:
        moe.dropless_experts = orig


@contextlib.contextmanager
def rope_wrong_dims():
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import pangu_moe

    orig = pangu_moe.rope

    def off(x, positions, theta):
        if x.shape[2] == 1:  # the shared key: rotated as it should be
            return orig(x, positions, theta)
        shift = x.shape[-1] // 4
        return jnp.roll(orig(jnp.roll(x, shift, -1), positions, theta), -shift, -1)

    pangu_moe.rope = off
    try:
        yield
    finally:
        pangu_moe.rope = orig


@contextlib.contextmanager
def post_norm_left_out():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import pangu_moe

    orig = pangu_moe.RMSNorm

    class Leaky(nn.Module):
        eps: float
        dtype: jnp.dtype

        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
            if self.name == "post_mlp_norm":
                return x.astype(self.dtype)
            x32 = x.astype(jnp.float32)
            norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
            return (norm * scale).astype(self.dtype)

    pangu_moe.RMSNorm = Leaky
    try:
        yield
    finally:
        pangu_moe.RMSNorm = orig


@contextlib.contextmanager
def value_projection_transposed():
    import jax.numpy as real

    from tensorflowonspark_tpu.models import pangu_moe

    class Jnp:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def einsum(spec, *ops, **kw):
            if spec == "bshc,chd->bshd":
                ctx, w = ops
                rank, heads, vd = w.shape
                ops = (ctx, w.reshape(rank, vd, heads).transpose(0, 2, 1))
            return real.einsum(spec, *ops, **kw)

    pangu_moe.jnp = Jnp()
    try:
        yield
    finally:
        pangu_moe.jnp = real


FAULTS = {f.__name__: f for f in (
    scaling_left_out, renorm_left_out, shared_left_out, expert_tokens_dropped,
    rope_wrong_dims, post_norm_left_out, value_projection_transposed)}

ZEROED = {
    "attention": lambda n: n.endswith("attn/o_proj/kernel"),
    "shared_expert": lambda n: n.endswith("moe/shared_down/kernel"),
    "held_experts": lambda n: n.endswith("moe/w_down"),
    "rope_part": lambda n: n.endswith("attn/kv_a_proj/kernel"),
}


def sensitivity(cell: str, seed: int, tokens: int) -> dict:
    import numpy as np

    from perfbench import harness, reference_pangu_moe, weights_pangu_moe
    from perfbench.drivers import serve_pangu_moe

    import jax.numpy as jnp

    _, config, _ = harness.load_cell(cell)
    harness.enable_compile_cache()
    cfg = serve_pangu_moe.model_keys(config)
    key = weights_pangu_moe.seed_key(seed)
    dtype = jnp.dtype(config["run"]["param_dtype"])
    rng = np.random.default_rng(seed)
    seqs = rng.integers(0, cfg["vocab_size"], size=(1, tokens), dtype=np.int32)
    at = np.arange(tokens, dtype=np.int32)[None, :]
    rank = cfg["kv_lora_rank"]

    def readings(part, toks):
        def edit(name, leaf):
            if part is None or not ZEROED[part](name):
                return leaf
            if part == "rope_part":
                return leaf.at[:, rank:].set(0.0)
            return jnp.zeros_like(leaf)

        get_leaf = serve_pangu_moe.reference_leaves(cfg, key, dtype, edit)
        return [np.asarray(x) for x in reference_pangu_moe.serve_readings(
            cfg, get_leaf, seqs, at, toks, blocks=2, vocab_blocks=8)]

    best, top, _, _ = readings(None, np.zeros((1, tokens, 1), np.int32))
    out = {"tokens": tokens, "seed": seed}
    for part in ZEROED:
        _, _, _, got = readings(part, top[..., None])
        moved = np.abs(best - got[..., 0])[0, tokens // 8:]  # past the first few positions
        out[part] = {"median": float(np.median(moved)), "p5": float(np.percentile(moved, 5))}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--sensitivity", action="store_true")
    ap.add_argument("--tokens", type=int, default=1024)
    a, rest = ap.parse_known_args()
    if a.sensitivity:
        sp = argparse.ArgumentParser()
        sp.add_argument("--workload", required=True)
        sp.add_argument("--seed", type=int, default=0)
        s, _ = sp.parse_known_args(rest)
        print(json.dumps({"sensitivity": sensitivity(s.workload, s.seed, a.tokens)}), flush=True)
        return 0
    if not a.fault:
        ap.error("--fault or --sensitivity")
    from perfbench import run

    with FAULTS[a.fault]():
        return run.main(rest)


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
