"""Seeded openPangu-Ultra-MoE weights, made on the device leaf by leaf from
one key, in the layout of a checkpoint of ``models/pangu_moe.py``: what
``perfbench/weights.py`` is to the Mistral cells.

The published config gives no scale of the trained weights. Under the
sandwich norms every branch's output is normalised before it is added, so
a matrix's standard deviation decides nothing of a branch's size; it
decides what is *inside* a branch: how peaked attention is and how much of
a score the rotary part carries, how far apart the router's scores lie,
and how much the routed experts weigh beside the shared one. ``STD``
below is chosen for those, reckoned at the published widths from inputs of
unit RMS, and repeated in the configuration's ``assumed``:

- ``q_b_proj`` 0.038, ``kv_b_proj`` 0.066, ``kv_a_proj`` 0.017: queries
  and both kinds of keys arrive at a standard deviation of 1.5, so a
  score's is about 2.25 and the 64 rotary dimensions carry a third of
  its variance;
- ``router`` 0.0114: logits of standard deviation 1, the eight best of
  256 scoring 0.87 to 0.94 under the sigmoid, so that leaving the
  renormalisation out multiplies the routed part by seven;
- expert ``w_down`` and ``shared_down`` both 0.01: one held expert at its
  weight of about 0.31 adds a third of what the shared expert does. Not
  more, because at bfloat16 some hundredth of the tokens a layer choose
  another 8th expert than the float32 reference, and where one of the
  two is held here its whole contribution appears or vanishes: with a
  held expert as heavy as the shared one such a token's logits moved by
  up to 4 (my chip run, PR 31), and no limit could tell a fault from it;
- ``gate`` / ``up`` of every SwiGLU 0.0114 (pre-activations of unit
  standard deviation), ``embed`` 1.0 (a residual stream that starts at
  unit RMS, each branch then adding about 1), ``lm_head`` 0.02 (logits of
  standard deviation about 1.75).
Norm scales are 1 + 0.1 N(0, 1), so that a dropped scale shows. The
router's kernel is float32 whatever the dtype of the rest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench import counts_pangu_moe as counts
from perfbench.weights import nest, seed_key  # noqa: F401  (seed_key: the drivers' entry)

STD = {
    "embed": 1.0, "lm_head": 0.02,
    "q_a_proj": 0.02, "q_b_proj": 0.038, "kv_a_proj": 0.017, "kv_b_proj": 0.066,
    "o_proj": 0.02, "gate": 0.0114, "up": 0.0114, "down": 0.01,
    "router": 0.0114, "w_down": 0.01, "shared_down": 0.01,
}



def leaf_specs(cfg: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], str]]:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, rank, rot = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd, qr = cfg["qk_nope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"]
    f, i = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    held, sf = cfg["n_routed_experts"], cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    out = [(("embed",), (v, h), "embed")]
    for n in range(cfg["num_hidden_layers"]):
        L = f"layer{n}"
        out += [
            ((L, "in_norm", "scale"), (h,), "scale"),
            ((L, "attn", "q_a_proj", "kernel"), (h, qr), "q_a_proj"),
            ((L, "attn", "q_a_norm", "scale"), (qr,), "scale"),
            ((L, "attn", "q_b_proj", "kernel"), (qr, heads * (nope + rot)), "q_b_proj"),
            ((L, "attn", "kv_a_proj", "kernel"), (h, rank + rot), "kv_a_proj"),
            ((L, "attn", "kv_a_norm", "scale"), (rank,), "scale"),
            ((L, "attn", "kv_b_proj"), (rank, heads * (nope + vd)), "kv_b_proj"),
            ((L, "attn", "o_proj", "kernel"), (heads * vd, h), "o_proj"),
            ((L, "post_attn_norm", "scale"), (h,), "scale"),
            ((L, "pre_mlp_norm", "scale"), (h,), "scale"),
        ]
        if n < cfg["first_k_dense_replace"]:
            out += [
                ((L, "mlp", "gate_proj", "kernel"), (h, i), "gate"),
                ((L, "mlp", "up_proj", "kernel"), (h, i), "up"),
                ((L, "mlp", "down_proj", "kernel"), (i, h), "down"),
            ]
        else:
            out += [
                ((L, "moe", "router"), (h, counts.router_width(cfg)), "router"),
                ((L, "moe", "w_gate"), (held, h, f), "gate"),
                ((L, "moe", "w_up"), (held, h, f), "up"),
                ((L, "moe", "w_down"), (held, f, h), "w_down"),
                ((L, "moe", "shared_gate", "kernel"), (h, sf), "gate"),
                ((L, "moe", "shared_up", "kernel"), (h, sf), "up"),
                ((L, "moe", "shared_down", "kernel"), (sf, h), "shared_down"),
            ]
        out += [((L, "post_mlp_norm", "scale"), (h,), "scale")]
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
