"""Seeded Laguna weights, made on the device leaf by leaf from one key, in
the layout of a checkpoint of ``models/laguna.py``: what
``perfbench/weights.py`` is to the Mistral cells.

The published config gives no scale of the trained weights. The layers
are pre-norm, so a matrix's standard deviation decides both what is
inside a branch and how much the branch adds to the residual stream.
``STD`` is reckoned at the published widths (hidden 2,048) from inputs of
unit RMS (``embed`` 1.0: the stream starts there) and repeated in the
configuration's ``assumed``:

- every projection of the normed input into an activation or a gate
  (``v``, the per-head gate, the dense MLP's and the experts' ``gate`` /
  ``up``, the router) 0.0221 = 1 / sqrt(2048): pre-activations of unit
  standard deviation, router logits of standard deviation 1;
- ``q`` / ``k`` 0.025: scores of standard deviation about 1.3 in the
  windowed layers; in the full ones the rotated half is scaled twice by
  YaRN's attention factor (1.416^2), about 1.8;
- ``o`` 0.0164: the branch adds about 0.4 RMS;
- the dense MLP's ``down`` 0.008 (8,192 inputs): about 0.35 RMS;
- the experts' and the shared expert's ``down`` 0.03: the shared expert
  adds about 0.35 RMS, a chosen expert at its weight of 2.5 / 8 about a
  third of that, the eight of them together about as much as the shared
  one;
- ``lm_head`` 0.0387: logits of standard deviation about 1.75.

Norm scales are 1 + 0.1 N(0, 1), so that a dropped scale shows. The
router's kernel is float32 whatever the dtype of the rest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.weights import nest, seed_key  # noqa: F401  (seed_key: the drivers' entry)

STD = {
    "embed": 1.0, "lm_head": 0.0387,
    "attn_qk": 0.025, "attn_v": 0.0221, "attn_g": 0.0221, "attn_o": 0.0164,
    "gate": 0.0221, "up": 0.0221, "mlp_down": 0.008,
    "router": 0.0221, "w_down": 0.03, "shared_down": 0.03,
}


def leaf_specs(cfg: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], str]]:
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * d
    f, sf, e = (cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"],
                cfg["num_experts"])
    out = [(("embed",), (v, h), "embed")]
    for n in range(cfg["num_hidden_layers"]):
        L = f"layer{n}"
        heads = cfg["num_attention_heads_per_layer"][n]
        nq = heads * d
        out += [
            ((L, "in_norm", "scale"), (h,), "scale"),
            ((L, "attn", "q_proj", "kernel"), (h, nq), "attn_qk"),
            ((L, "attn", "k_proj", "kernel"), (h, nkv), "attn_qk"),
            ((L, "attn", "v_proj", "kernel"), (h, nkv), "attn_v"),
            ((L, "attn", "g_proj", "kernel"), (h, heads), "attn_g"),
            ((L, "attn", "o_proj", "kernel"), (nq, h), "attn_o"),
            ((L, "ff_norm", "scale"), (h,), "scale"),
        ]
        if cfg["mlp_layer_types"][n] == "dense":
            i = cfg["intermediate_size"]
            out += [
                ((L, "mlp", "gate_proj", "kernel"), (h, i), "gate"),
                ((L, "mlp", "up_proj", "kernel"), (h, i), "up"),
                ((L, "mlp", "down_proj", "kernel"), (i, h), "mlp_down"),
            ]
        else:
            out += [
                ((L, "moe", "router"), (h, e), "router"),
                ((L, "moe", "w_gate"), (e, h, f), "gate"),
                ((L, "moe", "w_up"), (e, h, f), "up"),
                ((L, "moe", "w_down"), (e, f, h), "w_down"),
                ((L, "moe", "shared_gate", "kernel"), (h, sf), "gate"),
                ((L, "moe", "shared_up", "kernel"), (h, sf), "up"),
                ((L, "moe", "shared_down", "kernel"), (sf, h), "shared_down"),
            ]
    out += [(("final_norm", "scale"), (h,), "scale"), (("lm_head",), (h, v), "lm_head")]
    return out


def make_leaf(key, index, shape, kind: str, dtype):
    """``kind``: a key of ``STD`` (a matrix N(0, std^2); the router stays
    float32) or ``scale`` (1 + 0.1 N(0, 1))."""
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    if kind == "scale":
        return (1.0 + 0.1 * x).astype(dtype)
    return (x * STD[kind]).astype(jnp.float32 if kind == "router" else dtype)


def make_params(cfg: dict, key, dtype) -> dict:
    """The whole tree (nested dicts named as ``leaf_specs`` names them).
    Call under ``jax.jit`` with ``key`` traced."""
    return nest({
        path: make_leaf(key, n, shape, kind, dtype)
        for n, (path, shape, kind) in enumerate(leaf_specs(cfg))
    })
