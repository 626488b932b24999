"""Seeded weights, made on the device, leaf by leaf from one key.

The benchmark makes the weights (the program never does), so that the
plain reference can make the same ones again from the seed once the
program's state is freed. A leaf is a function of (key, its index in
``leaf_specs``, its shape): matrices are N(0, 0.02^2), norm scales
1 + 0.1 N(0, 1) so that a dropped scale shows.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from perfbench import counts


def seed_key(seed: int):
    """A raw threefry key for any whole number (the driver's seeds pass
    2**31): both 32-bit halves, and a traced argument wherever it is
    used, so that a new seed never compiles anything."""
    seed = int(seed) % (1 << 64)
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32))


def leaf_specs(cfg: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], str]]:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = counts.head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    out = [(("embed",), (v, h), "matrix")]
    for n in range(cfg["num_hidden_layers"]):
        L = f"layer{n}"
        out += [
            ((L, "attn_norm", "scale"), (h,), "scale"),
            ((L, "attn", "q_proj", "kernel"), (h, nq), "matrix"),
            ((L, "attn", "k_proj", "kernel"), (h, nkv), "matrix"),
            ((L, "attn", "v_proj", "kernel"), (h, nkv), "matrix"),
            ((L, "attn", "o_proj", "kernel"), (nq, h), "matrix"),
            ((L, "mlp_norm", "scale"), (h,), "scale"),
            ((L, "mlp", "gate_proj", "kernel"), (h, i), "matrix"),
            ((L, "mlp", "up_proj", "kernel"), (h, i), "matrix"),
            ((L, "mlp", "down_proj", "kernel"), (i, h), "matrix"),
        ]
    out += [(("final_norm", "scale"), (h,), "scale"), (("lm_head",), (h, v), "matrix")]
    return out


def make_leaf(key, index, shape, kind: str, dtype):
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    x = x * 0.02 if kind == "matrix" else 1.0 + 0.1 * x
    return x.astype(dtype)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x
    return tree


def make_params(cfg: dict, key, dtype) -> dict:
    """The whole tree, in the layout of a checkpoint of the model (nested
    dicts named as ``leaf_specs`` names them). Call under ``jax.jit`` with
    ``key`` traced: one program, every leaf made on the device."""
    return nest({
        path: make_leaf(key, n, shape, kind, dtype)
        for n, (path, shape, kind) in enumerate(leaf_specs(cfg))
    })
