"""Seeded Falcon-H1 weights, made on the device leaf by leaf from one key,
in the layout of a checkpoint of ``models/falcon_h1.py``: what
``perfbench/weights.py`` is to the Mistral cells.

The published multipliers are muP factors for TRAINED weights whose scale
the config does not give. With every matrix at N(0, 0.02^2) the embedding
(x5.66) would be some twenty times what any branch adds to it, attention
(keys x0.011) and the logits (x0.0078) would be flat, and a fault in any
branch would hide under the limits. So the multipliers stay as published
and each kind of matrix gets the standard deviation that answers to its
own: ``STD`` below, repeated in the configuration's ``assumed``. Reckoned
at the published widths so that the residual stream starts at unit RMS,
each branch of each block adds about 0.4 RMS to it (in the reference at
layer 0: mixer 0.398, attention 0.405, MLP 0.399), attention scores have
a standard deviation of about 2.5, the mixer's x, z, B, C and dt arrive at
1, 1, 2, 2 and 0.5, and the logits' is about 1.7. The mixer's own leaves
are Mamba-2's initialisation, so that the state remembers hundreds of
tokens and a broken carry shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench import counts, counts_falcon_h1
from perfbench.weights import nest, seed_key  # noqa: F401  (seed_key: the drivers' entry)

STD = {
    "embed": 0.177,  # x embedding_multiplier 5.66: unit RMS
    "q_proj": 0.1, "k_proj": 0.45,  # scores: 71.6*0.1 x 71.6*0.011*0.45 = 2.5
    "v_proj": 0.02, "o_proj": 0.4,  # x attention_out_multiplier 0.0375: 0.4
    "gate_proj": 0.08, "up_proj": 0.02, "down_proj": 0.28,  # x 0.177, x 0.0112: 0.4
    # the zones of in_proj, after ssm_in_multiplier 0.25 and ssm_multipliers
    "in_proj.z": 0.158, "in_proj.x": 0.223, "in_proj.B": 0.632,
    "in_proj.C": 0.223, "in_proj.dt": 0.079,
    "out_proj": 0.07,  # after the gated norm, x ssm_out_multiplier 0.088: 0.4
    "lm_head": 3.0,  # x lm_head_multiplier 0.0078: 1.7
}


def leaf_specs(cfg: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], object]]:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = counts.head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    d, nh = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    c, k = counts_falcon_h1.conv_dim(cfg), cfg["mamba_d_conv"]
    out = [(("embed",), (v, h), "embed")]
    for n in range(cfg["num_hidden_layers"]):
        L = f"layer{n}"
        out += [
            ((L, "in_norm", "scale"), (h,), "scale"),
            ((L, "mixer", "in_proj", "kernel"), (h, d + c + nh), ("in_proj", (d, d, gn, gn, nh))),
            ((L, "mixer", "conv_weight"), (k, c), ("conv", k)),
            ((L, "mixer", "conv_bias"), (c,), ("conv", k)),
            ((L, "mixer", "dt_bias"), (nh,), "dt_bias"),
            ((L, "mixer", "A_log"), (nh,), "A_log"),
            ((L, "mixer", "D"), (nh,), "scale"),
            ((L, "mixer", "norm_scale"), (d,), "scale"),
            ((L, "mixer", "out_proj", "kernel"), (d, h), "out_proj"),
            ((L, "attn", "q_proj", "kernel"), (h, nq), "q_proj"),
            ((L, "attn", "k_proj", "kernel"), (h, nkv), "k_proj"),
            ((L, "attn", "v_proj", "kernel"), (h, nkv), "v_proj"),
            ((L, "attn", "o_proj", "kernel"), (nq, h), "o_proj"),
            ((L, "ff_norm", "scale"), (h,), "scale"),
            ((L, "mlp", "gate_proj", "kernel"), (h, i), "gate_proj"),
            ((L, "mlp", "up_proj", "kernel"), (h, i), "up_proj"),
            ((L, "mlp", "down_proj", "kernel"), (i, h), "down_proj"),
        ]
    out += [(("final_norm", "scale"), (h,), "scale"), (("lm_head",), (h, v), "lm_head")]
    return out


def make_leaf(key, index, shape, kind, dtype):
    """``kind``: a key of ``STD`` (a matrix N(0, std^2)); ``scale`` (1 +
    0.1 N(0, 1): norm scales and D); ``("in_proj", zone widths)`` (each
    zone's columns at its own std); ``("conv", k)`` (U(+-k^-0.5), weight
    and bias); ``A_log`` (log U[1, 16]); ``dt_bias`` (softplus^-1 of a
    step log-uniform in [1e-3, 1e-1])."""
    k = jax.random.fold_in(key, index)
    if kind == "A_log":
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif isinstance(kind, tuple) and kind[0] == "conv":
        x = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) * kind[1] ** -0.5
    else:
        x = jax.random.normal(k, shape, jnp.float32)
        if kind == "scale":
            x = 1.0 + 0.1 * x
        elif isinstance(kind, tuple):  # in_proj: one std a zone of columns
            stds = [STD["in_proj." + z] for z in ("z", "x", "B", "C", "dt")]
            x = x * jnp.concatenate(
                [jnp.full((w,), s, jnp.float32) for w, s in zip(kind[1], stds)])
        else:
            x = x * STD[kind]
    return x.astype(dtype)


def make_params(cfg: dict, key, dtype) -> dict:
    """The whole tree (nested dicts named as ``leaf_specs`` names them).
    Call under ``jax.jit`` with ``key`` traced."""
    return nest({
        path: make_leaf(key, n, shape, kind, dtype)
        for n, (path, shape, kind) in enumerate(leaf_specs(cfg))
    })
