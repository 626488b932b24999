"""What both drivers need of the program's Llama family: its config from
a configuration file, and the seed's weights in its checkpoint layout."""

from __future__ import annotations

import numpy as np


def llama_config(cfg: dict, **over):
    """``LlamaConfig`` at the file's sizes; every option the file does not
    name stays at the program's default."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.llama import LlamaConfig

    run = cfg["run"]
    kw = dict(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        max_seq_len=run["max_seq_len"],
        rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        sliding_window=cfg.get("sliding_window"),
        dtype=jnp.dtype(run["compute_dtype"]),
    )
    kw.update(over)
    return LlamaConfig(**kw)


def model_keys(cfg: dict) -> dict:
    """The configuration without the benchmark's own notes: what the
    reference and the counts read."""
    skip = ("run", "reduced", "published", "assumed", "deployment", "name", "source", "notes")
    return {k: v for k, v in cfg.items() if k not in skip}


def device_params(cfg: dict, key, dtype):
    """The seed's weights as the program's param tree, made on the device
    in one jitted call."""
    import jax

    from perfbench import weights

    return jax.jit(lambda k: weights.make_params(cfg, k, dtype))(key)


def reference_leaves(cfg: dict, key, store_dtype):
    """``get_leaf(name)`` for the reference: the same leaf from the same
    key, rounded to the dtype it is stored in, as float32. One small
    program per (shape, kind); the index is traced."""
    import functools

    import jax
    import jax.numpy as jnp

    from perfbench import weights

    specs = {"/".join(p): (n, s, k) for n, (p, s, k) in enumerate(weights.leaf_specs(cfg))}

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def make(k, index, shape, kind):
        return weights.make_leaf(k, index, shape, kind, store_dtype).astype(jnp.float32)

    def get_leaf(name: str):
        n, shape, kind = specs[name]
        return make(key, np.int32(n), shape, kind)

    get_leaf.names = list(specs)
    return get_leaf


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, name))
        else:
            out[name] = v
    return out
