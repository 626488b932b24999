"""Seeded Phi-4-mini-flash weights, made on the device leaf by leaf from one
key, in the layout of a checkpoint of ``models/phi4_flash.py``: what
``perfbench/weights.py`` is to the Mistral cells.

The published config gives no scale of the trained weights. The layers
are pre-norm, so a matrix's standard deviation decides both what is
inside a branch and how much the branch adds to the residual stream.
``STD`` below is reckoned at the published widths from normed inputs of
unit RMS and repeated in the configuration's ``assumed``:

- ``embed`` 0.01 and the final norm's scale 3.5 (1 + 0.1 N(0, 1)): the
  head is the embedding, whose logits then have a standard deviation of
  about 0.01 x sqrt(2560) x 3.5 = 1.8. A larger embedding (0.035 at a
  final scale of 1, the same logits) leaves so much of the current token
  in the last hidden state that the head predicts it again: on the CPU
  at 22 layers the best token was the input one at 92 % of positions,
  and a greedy decode repeats one token, whose identical keys make the
  differential heads' two maps cancel; at 0.01 it is 1.5 %. Every branch
  below adds about 0.05 to a stream that starts at 0.01;
- every projection of a normed input into an activation (Mamba ``in``,
  attention ``v``, GMU ``in``, ``fc1``) 0.0198 = 1 / sqrt(2560);
- attention ``q`` / ``k`` 0.014: each map's scores have a standard
  deviation of about 0.5; ``o`` 0.0025 (its input is normed by the subln
  and scaled by ``1 - lambda_init``, 0.2 to 0.8). At 0.028 (scores of 2)
  and convolutions of 0.25 a tap, a bfloat16 model drifted from the
  float32 reference by a median 0.13 in log-probability at 22 layers
  (CPU; the first chip runs of the 32-layer cell read 0.16–0.17): a
  perturbation grew from layer to layer. At these scales 0.029;
- the convolution 0.1 a tap, its bias 0.1: outputs of standard
  deviation about 0.2;
- ``x_proj``: 0.047 for the columns of ``dt``'s low-rank input (unit
  standard deviation), 0.094 for those of ``B`` and ``C`` (2), so that a
  scan left out shows; ``dt_proj`` 0.04: ``dt``'s pre-activation varies
  by 0.5 around ``dt_bias``, the inverse softplus of a step log-uniform
  in 0.001..0.1 a channel;
- ``A_log`` S4D-real, ``log(n)`` for state index n = 1..16, with a
  jitter of 0.05 N(0, 1); ``D`` 1 + 0.1 N(0, 1);
- Mamba and GMU ``out`` 0.0035, ``fc2`` 0.0008: each branch adds about
  0.05 RMS;
- the lambda vectors N(0, 0.1^2) (the published initialisation).

Norm scales (LayerNorms and the subln) are 1 + 0.1 N(0, 1) and the
LayerNorms' biases 0.1 N(0, 1), so that a dropped scale or bias shows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import counts_phi4_flash as counts
from perfbench.weights import nest, seed_key  # noqa: F401  (seed_key: the drivers' entry)

STD = {
    "embed": 0.01, "in": 0.0198, "attn_qk": 0.014, "attn_o": 0.0025,
    "conv": 0.1, "dt_proj": 0.04, "out": 0.0035, "fc2": 0.0008,
    "lambda": 0.1, "bias": 0.1,
}
X_PROJ = (0.047, 0.094)  # dt's columns, B's and C's
FINAL_SCALE = 3.5
DT_RANGE = (0.001, 0.1)


def leaf_specs(cfg: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], str]]:
    h, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    c, N, k, r = counts.mamba(cfg)
    d = counts.head_dim(cfg)
    kvw = cfg["num_key_value_heads"] * d
    out = [(("embed",), (v, h), "embed")]
    for n, kind in enumerate(counts.layer_kinds(cfg)):
        L = f"layer{n}"
        out += [((L, "in_norm", "scale"), (h,), "scale"),
                ((L, "in_norm", "bias"), (h,), "bias")]
        if kind == "mamba":
            M = (L, "mamba")
            out += [
                ((*M, "in_proj", "kernel"), (h, 2 * c), "in"),
                ((*M, "conv_weight"), (k, c), "conv"),
                ((*M, "conv_bias"), (c,), "bias"),
                ((*M, "x_proj", "kernel"), (c, r + 2 * N), f"x_proj/{r}"),
                ((*M, "dt_proj", "kernel"), (r, c), "dt_proj"),
                ((*M, "dt_bias"), (c,), "dt_bias"),
                ((*M, "A_log"), (c, N), "A_log"),
                ((*M, "D"), (c,), "scale"),
                ((*M, "out_proj", "kernel"), (c, h), "out"),
            ]
        elif kind == "gmu":
            out += [((L, "gmu_in", "kernel"), (h, c), "in"),
                    ((L, "gmu_out", "kernel"), (c, h), "out")]
        else:
            A = (L, "attn")
            out += [((*A, "q_proj", "kernel"), (h, h), "attn_qk")]
            if kind != "cross":
                out += [((*A, "k_proj", "kernel"), (h, kvw), "attn_qk"),
                        ((*A, "v_proj", "kernel"), (h, kvw), "in")]
            out += [((*A, "o_proj", "kernel"), (h, h), "attn_o")]
            out += [((*A, name), (d,), "lambda")
                    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
            out += [((*A, "subln"), (2 * d,), "scale")]
        out += [((L, "ff_norm", "scale"), (h,), "scale"),
                ((L, "ff_norm", "bias"), (h,), "bias"),
                ((L, "fc1", "kernel"), (h, 2 * f), "in"),
                ((L, "fc2", "kernel"), (f, h), "fc2")]
    out += [(("final_norm", "scale"), (h,), "final_scale"),
            (("final_norm", "bias"), (h,), "bias")]
    return out


def make_leaf(key, index, shape, kind: str, dtype):
    """``kind``: a key of ``STD`` (a matrix or vector N(0, std^2)),
    ``scale`` (1 + 0.1 N(0, 1)), ``final_scale`` (``FINAL_SCALE`` times
    that), ``x_proj/<dt rank>``, ``dt_bias`` or ``A_log`` (the module's
    docstring)."""
    k = jax.random.fold_in(key, index)
    if kind == "dt_bias":
        lo, hi = DT_RANGE
        x = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, minval=math.log(lo), maxval=math.log(hi)))
        return jnp.log(jnp.expm1(x)).astype(dtype)  # softplus(dt_bias) = x
    x = jax.random.normal(k, shape, jnp.float32)
    if kind == "A_log":
        n = jnp.arange(1, shape[1] + 1, dtype=jnp.float32)
        return (jnp.log(n)[None, :] + 0.05 * x).astype(dtype)
    if kind == "scale":
        return (1.0 + 0.1 * x).astype(dtype)
    if kind == "final_scale":
        return (FINAL_SCALE * (1.0 + 0.1 * x)).astype(dtype)
    if kind.startswith("x_proj/"):  # x_proj/<dt rank>: dt's columns first
        rank = int(kind.split("/")[1])
        col = jnp.where(jnp.arange(shape[1]) < rank, *X_PROJ)
        return (x * col).astype(dtype)
    return (x * STD[kind]).astype(dtype)


def make_params(cfg: dict, key, dtype) -> dict:
    """The whole tree (nested dicts named as ``leaf_specs`` names them).
    Call under ``jax.jit`` with ``key`` traced."""
    return nest({
        path: make_leaf(key, n, shape, kind, dtype)
        for n, (path, shape, kind) in enumerate(leaf_specs(cfg))
    })
