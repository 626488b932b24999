"""Seeded Solar-Open2 weights, made on the device leaf by leaf from one
key, in the layout of a checkpoint of ``models/solar_open2.py``: what
``perfbench/weights.py`` is to the Mistral cells.

The published config gives no scale of the trained weights. The layers
are pre-norm, so a matrix's standard deviation decides both what is
inside a branch and how much the branch adds to the residual stream.
``STD`` below is reckoned at the published widths from inputs of unit RMS
(``embed`` 1.0: the stream starts there) and repeated in the
configuration's ``assumed``:

- every projection of the normed input into an activation, a gate or a
  convolution (``q`` / ``k`` / ``v`` of the linear layer, ``g_proj``,
  ``f_a`` / ``g_a``, ``b_proj``, expert ``gate`` / ``up``, ``router``)
  0.0156 = 1 / sqrt(4096): pre-activations of unit standard deviation,
  router logits of standard deviation 1 (the eight best of 320 score
  0.88 to 0.95, each chosen expert weighs about an eighth), beta spread
  over (0.5, 1.5): above 1 at half the positions;
- the attention's ``q`` / ``k`` 0.0247: scores of standard deviation
  about 2.5; ``v`` 0.0156, ``o`` 0.0164: the branch adds about 0.4 RMS;
- the convolutions 0.125 a tap: four taps bring a unit input to a
  standard deviation of 0.25, where SiLU is nearly linear. At 0.5 a tap
  (my chip runs, PR 33) ``q~``, ``k~`` and ``v~`` carried SiLU's positive
  mean, two keys' cosine was 0.12 and a state's output nearly the same
  vector for every token: the routers then preferred the same experts
  for all tokens (``moe_expert_load_imbalance_pct.serve`` 57), the seed
  chose how many pairs the held experts got (488 to 525 a step) and six
  seeds spread 0.65 % in ``itl_p95_ms``; ``f_b`` 0.0442: the decay's pre-activation has a standard
  deviation of 0.5 around ``dt_bias``; ``g_b`` 0.0884 and its bias 0.5:
  the output gate's of about 1.1;
- ``A_log`` uniform in log(0.5)..log(2) a head and ``dt_bias`` the
  inverse softplus of a step log-uniform in 0.002..0.05 a channel: a
  position's decays ``exp(-A softplus(f + dt_bias))`` spread over
  roughly 0.85 to 0.999, so a channel remembers from six to a thousand
  positions and a state that is not carried, or decays wrongly, shows;
- the linear layer's ``o`` 0.0092: the branch adds about 0.45 RMS;
- ``shared_down`` 0.0163 (the shared expert adds about 0.35 RMS) and
  expert ``w_down`` 0.0434: one held expert at its weight of an eighth
  adds a third of what the shared expert does. Not more, for PR 31's
  reason: where bfloat16 and float32 choose another 8th expert and one
  of the two is held here, its whole contribution appears or vanishes;
- ``lm_head`` 0.0273: logits of standard deviation about 1.75.
Norm scales (the head norm's too) are 1 + 0.1 N(0, 1), so that a dropped
scale shows. The router's kernel is float32 whatever the dtype of the
rest.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import counts_solar_open2 as counts
from perfbench.weights import nest, seed_key  # noqa: F401  (seed_key: the drivers' entry)

STD = {
    "embed": 1.0, "lm_head": 0.0273,
    "attn_qk": 0.0247, "attn_v": 0.0156, "attn_g": 0.0156, "attn_o": 0.0164,
    "lin_in": 0.0156, "conv": 0.125, "f_b": 0.0442, "g_b": 0.0884, "g_bias": 0.5,
    "lin_o": 0.0092,
    "router": 0.0156, "gate": 0.0156, "up": 0.0156, "w_down": 0.0434,
    "shared_down": 0.0163,
}
A_RANGE = (0.5, 2.0)
DT_RANGE = (0.002, 0.05)


def leaf_specs(cfg: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], str]]:
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    lh, ld, k = counts.linear(cfg)
    c = lh * ld
    f = cfg["moe_intermediate_size"]
    held, sf = cfg["n_routed_experts"], cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    out = [(("embed",), (v, h), "embed")]
    for n in range(cfg["num_hidden_layers"]):
        L = f"layer{n}"
        out += [((L, "in_norm", "scale"), (h,), "scale")]
        if n in cfg["gqa_layers"]:
            out += [
                ((L, "attn", "q_proj", "kernel"), (h, nq), "attn_qk"),
                ((L, "attn", "k_proj", "kernel"), (h, nkv), "attn_qk"),
                ((L, "attn", "v_proj", "kernel"), (h, nkv), "attn_v"),
                ((L, "attn", "g_proj", "kernel"), (h, nq), "attn_g"),
                ((L, "attn", "o_proj", "kernel"), (nq, h), "attn_o"),
            ]
        else:
            M = (L, "mixer")
            out += [
                ((*M, "q_proj", "kernel"), (h, c), "lin_in"),
                ((*M, "k_proj", "kernel"), (h, c), "lin_in"),
                ((*M, "v_proj", "kernel"), (h, c), "lin_in"),
                ((*M, "f_a_proj", "kernel"), (h, ld), "lin_in"),
                ((*M, "f_b_proj", "kernel"), (ld, c), "f_b"),
                ((*M, "g_a_proj", "kernel"), (h, ld), "lin_in"),
                ((*M, "g_b_proj", "kernel"), (ld, c), "g_b"),
                ((*M, "g_b_proj", "bias"), (c,), "g_bias"),
                ((*M, "b_proj", "kernel"), (h, lh), "lin_in"),
                ((*M, "q_conv"), (k, c), "conv"),
                ((*M, "k_conv"), (k, c), "conv"),
                ((*M, "v_conv"), (k, c), "conv"),
                ((*M, "A_log"), (lh,), "A_log"),
                ((*M, "dt_bias"), (c,), "dt_bias"),
                ((*M, "o_norm"), (ld,), "scale"),
                ((*M, "o_proj", "kernel"), (c, h), "lin_o"),
            ]
        out += [
            ((L, "ff_norm", "scale"), (h,), "scale"),
            ((L, "moe", "router"), (h, counts.router_width(cfg)), "router"),
            ((L, "moe", "w_gate"), (held, h, f), "gate"),
            ((L, "moe", "w_up"), (held, h, f), "up"),
            ((L, "moe", "w_down"), (held, f, h), "w_down"),
            ((L, "moe", "shared_gate", "kernel"), (h, sf), "gate"),
            ((L, "moe", "shared_up", "kernel"), (h, sf), "up"),
            ((L, "moe", "shared_down", "kernel"), (sf, h), "shared_down"),
        ]
    out += [(("final_norm", "scale"), (h,), "scale"), (("lm_head",), (h, v), "lm_head")]
    return out


def make_leaf(key, index, shape, kind: str, dtype):
    """``kind``: a key of ``STD`` (a matrix N(0, std^2); the router stays
    float32), ``scale`` (1 + 0.1 N(0, 1)), ``A_log`` or ``dt_bias`` (the
    module's docstring)."""
    k = jax.random.fold_in(key, index)
    if kind in ("A_log", "dt_bias"):
        lo, hi = A_RANGE if kind == "A_log" else DT_RANGE
        x = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, minval=math.log(lo), maxval=math.log(hi)))
        # dt_bias: the inverse of softplus, so that softplus(dt_bias) = x
        return (jnp.log(x) if kind == "A_log" else jnp.log(jnp.expm1(x))).astype(dtype)
    x = jax.random.normal(k, shape, jnp.float32)
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
