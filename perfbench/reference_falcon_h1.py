"""The plain reference for Falcon-H1: float32 ``jax.numpy``, no kernels,
no cache, no chunks, no batching.

The published description is HF ``transformers``
``modeling_falcon_h1.py``; ``cfg`` is the model's public ``config.json``.
One block, on one row ``x`` (S, H)::

    n = RMSNorm_in(x)
    x = x + ssm_out_multiplier * Mixer(n)
          + attention_out_multiplier * Attn(attention_in_multiplier * n)
    x = x + MLP(RMSNorm_ff(x))

- Attention: ``k = k_proj(.) * key_multiplier`` before the half-split
  rotation at ``rope_theta``; causal softmax at ``head_dim ** -0.5``.
- MLP: ``down(up(x) * silu(gate(x) * mlp_multipliers[0])) * mlp_multipliers[1]``.
- Mixer (Mamba-2): ``u = in_proj(n * ssm_in_multiplier)``, its five zones
  ``[z | x | B | C | dt]`` scaled by ``ssm_multipliers``; ``xBC`` through
  a causal depthwise convolution, **an explicit sum over its
  ``mamba_d_conv`` shifted copies**, plus bias, then SiLU;
  ``dt = softplus(dt + dt_bias)`` (no clamp: ``time_step_limit`` is
  (0, inf)); the recurrence **as a ``lax.scan`` over time**, head ``i``
  with the B and C of group ``i // (heads / groups)``::

      S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t,   A = -exp(A_log)
      y_t = S_t . C_t + D * x_t

  then ``y * silu(z)``, RMSNorm over each group of ``d_ssm / groups``
  channels times the norm's weight (``mamba_norm_before_gate`` false),
  ``out_proj``.
- Model: ``embed[tokens] * embedding_multiplier``, the blocks,
  ``final_norm``, ``(x @ lm_head) * lm_head_multiplier``, head untied.

Departures from the published implementation: none in the arithmetic.
The layouts are those of the program's checkpoint (``perfbench/
weights_falcon_h1.py``): the convolution's weight is (k, c), tap
``k - 1`` on the current token (published (c, 1, k)), and dense kernels
are (in, out).

It imports nothing of ``tensorflowonspark_tpu``. Every weight matmul goes
through the ``mm`` hook of ``perfbench/reference.py`` (``mm_highest``; the
control ``mm_fp8``, which here rounds the head a vocabulary block at a
time: one scale a block, not one a tensor). The head is never applied to
make a (rows, tokens, vocab) array at the published vocabulary: it is
reduced, block by block, to what a served token needs.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from perfbench import reference
from perfbench.reference import HI, MM, _blocked, rms_norm, rope


def conv_silu(xBC, w, b):
    """Causal depthwise convolution as the sum over ``k`` shifted copies,
    zeros before the first token: xBC (S, c), w (k, c), b (c,)."""
    k, s = w.shape[0], xBC.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xBC.shape[1]), xBC.dtype), xBC])
    out = b
    for j in range(k):
        out = out + padded[j : j + s] * w[j]
    return jax.nn.silu(out)


def recurrence(x, dt, A, B, C, D):
    """x (S, h, p), dt (S, h), A and D (h,), B and C (S, g, N): one
    position at a time from a zero state."""
    s, h, p = x.shape
    rep = h // B.shape[1]

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        Bh, Ch = jnp.repeat(Bt, rep, axis=0), jnp.repeat(Ct, rep, axis=0)  # (h, N)
        S = jnp.exp(dtt * A)[:, None, None] * S + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :]
        y = jnp.einsum("hpn,hn->hp", S, Ch, precision=HI) + D[:, None] * xt
        return S, y

    S0 = jnp.zeros((h, p, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, S0, (x, dt, B, C))
    return y


def mixer(cfg, w, n, mm):
    """The Mamba-2 mixer on one row's normed input n (S, H)."""
    d, h, p = cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    s = n.shape[0]
    u = mm(n * cfg["ssm_in_multiplier"], w["mixer/in_proj/kernel"])
    mz, mx, mB, mC, mdt = cfg["ssm_multipliers"]
    zones = jnp.concatenate([
        jnp.full((d,), mz), jnp.full((d,), mx), jnp.full((g * N,), mB),
        jnp.full((g * N,), mC), jnp.full((h,), mdt),
    ]).astype(jnp.float32)
    u = u * zones
    z, xBC, dt = u[:, :d], u[:, d : 2 * d + 2 * g * N], u[:, 2 * d + 2 * g * N :]
    xBC = conv_silu(xBC, w["mixer/conv_weight"], w["mixer/conv_bias"])
    x = xBC[:, :d].reshape(s, h, p)
    B = xBC[:, d : d + g * N].reshape(s, g, N)
    C = xBC[:, d + g * N :].reshape(s, g, N)
    dt = jax.nn.softplus(dt + w["mixer/dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["mixer/A_log"]), B, C, w["mixer/D"])
    y = y.reshape(s, d) * jax.nn.silu(z)
    yg = y.reshape(s, g, d // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return mm(yg.reshape(s, d) * w["mixer/norm_scale"], w["mixer/out_proj/kernel"])


def attention(cfg, w, n, pos, mm, blocks):
    hd, s = cfg["head_dim"], n.shape[0]
    q = mm(n, w["attn/q_proj/kernel"]).reshape(s, -1, hd)
    k = (mm(n, w["attn/k_proj/kernel"]) * cfg["key_multiplier"]).reshape(s, -1, hd)
    v = mm(n, w["attn/v_proj/kernel"]).reshape(s, -1, hd)
    q, k = rope(q, pos, float(cfg["rope_theta"])), rope(k, pos, float(cfg["rope_theta"]))
    doc = jnp.zeros((s,), jnp.int32)
    a = reference.attention(q, k, v, doc, pos, None, blocks)
    return mm(a, w["attn/o_proj/kernel"])


def layer(cfg, w, x, pos, mm, blocks):
    """One block on one row x (S, H); ``w`` maps a layer's leaf names
    (``mixer/in_proj/kernel`` ...) to float32 arrays."""
    n = rms_norm(x, w["in_norm/scale"], cfg["rms_norm_eps"])
    x = (
        x
        + cfg["ssm_out_multiplier"] * mixer(cfg, w, n, mm)
        + cfg["attention_out_multiplier"]
        * attention(cfg, w, n * cfg["attention_in_multiplier"], pos, mm, blocks)
    )
    m_gate, m_down = cfg["mlp_multipliers"]

    def mlp(xb):
        hb = rms_norm(xb, w["ff_norm/scale"], cfg["rms_norm_eps"])
        gate, up = mm(hb, w["mlp/gate_proj/kernel"]), mm(hb, w["mlp/up_proj/kernel"])
        return xb + mm(up * jax.nn.silu(gate * m_gate), w["mlp/down_proj/kernel"]) * m_down

    return _blocked(mlp, blocks, x)


LAYER_LEAVES = (
    "in_norm/scale", "mixer/in_proj/kernel", "mixer/conv_weight", "mixer/conv_bias",
    "mixer/dt_bias", "mixer/A_log", "mixer/D", "mixer/norm_scale",
    "mixer/out_proj/kernel", "attn/q_proj/kernel", "attn/k_proj/kernel",
    "attn/v_proj/kernel", "attn/o_proj/kernel", "ff_norm/scale",
    "mlp/gate_proj/kernel", "mlp/up_proj/kernel", "mlp/down_proj/kernel",
)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mm_name, blocks, vocab_blocks):
    cfg = json.loads(cfg_key)
    mm = MM[mm_name]

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32) * cfg["embedding_multiplier"]

    @jax.jit
    def one_layer(w, x):  # x (N, L, H): each row one sequence from position 0
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        return jax.lax.map(lambda xr: layer(cfg, w, xr, pos, mm, blocks), x)

    @jax.jit
    def final(scale, x, at):  # the hidden states to read, normed: (N, T, H)
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        return rms_norm(xs, scale, cfg["rms_norm_eps"])

    @jax.jit
    def full_head(w, xs):
        return jax.lax.map(lambda xr: mm(xr, w), xs) * cfg["lm_head_multiplier"]

    @jax.jit
    def reduced_head(w, xs, toks):
        """Over vocabulary blocks, per position of xs (M, H): the best
        logit, its index, the log-sum-exp, and the logits of ``toks``
        (M, K)."""
        m, v = xs.shape[0], w.shape[1]
        vb = v // vocab_blocks

        def block(carry, i):
            best, top, lse, got = carry
            wb = jax.lax.dynamic_slice_in_dim(w, i * vb, vb, axis=1)
            lg = mm(xs, wb) * cfg["lm_head_multiplier"]  # (M, vb)
            b_best, b_top = jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1) + i * vb
            top = jnp.where(b_best > best, b_top, top)
            best = jnp.maximum(best, b_best)
            lse = jnp.logaddexp(lse, jax.nn.logsumexp(lg, axis=-1))
            local = toks - i * vb
            here = (local >= 0) & (local < vb)
            picked = jnp.take_along_axis(lg, jnp.clip(local, 0, vb - 1), axis=-1)
            return (best, top, lse, jnp.where(here, picked, got)), None

        init = (jnp.full((m,), -jnp.inf), jnp.zeros((m,), jnp.int32),
                jnp.full((m,), -jnp.inf), jnp.zeros(toks.shape, jnp.float32))
        (best, top, lse, got), _ = jax.lax.scan(block, init, jnp.arange(vocab_blocks))
        return best, top, lse, got

    return embed, one_layer, final, full_head, reduced_head


def _hidden(cfg, get_leaf, seqs, at, mm, blocks, vocab_blocks=1):
    progs = _programs(json.dumps(cfg, sort_keys=True), mm, blocks, vocab_blocks)
    embed, one_layer, final = progs[:3]
    x = embed(get_leaf("embed"), jnp.asarray(seqs))
    for n in range(cfg["num_hidden_layers"]):
        x = one_layer({k: get_leaf(f"layer{n}/{k}") for k in LAYER_LEAVES}, x)
    return final(get_leaf("final_norm/scale"), x, jnp.asarray(at)), progs


def serve_logits(cfg: dict, get_leaf, seqs, at, *, mm="highest", blocks=4):
    """Logits (N, T, vocab) of one full forward pass over ``seqs`` (N, L)
    int32 (each row one sequence from position 0, padding after its end),
    read at positions ``at`` (N, T): the entry ``perfbench/reference.py``
    has, for sizes at which the array fits. ``get_leaf(name)`` returns a
    float32 weight; layers are fetched one at a time."""
    xs, progs = _hidden(cfg, get_leaf, seqs, at, mm, blocks)
    return progs[3](get_leaf("lm_head"), xs)


def serve_readings(cfg: dict, get_leaf, seqs, at, toks, *, mm="highest",
                   blocks=4, vocab_blocks=8):
    """The same forward pass, the head reduced to what a served token
    needs. ``toks`` (N, T, K) int32: K token ids a position. Returns
    ``best`` (N, T), ``top`` (N, T), ``lse`` (N, T) and ``got`` (N, T, K),
    the logits of ``toks``; ``got - lse`` are their log-probabilities."""
    if cfg["vocab_size"] % vocab_blocks:
        vocab_blocks = 1
    xs, progs = _hidden(cfg, get_leaf, seqs, at, mm, blocks, vocab_blocks)
    n, t, h = xs.shape
    toks = jnp.asarray(toks)
    best, top, lse, got = progs[4](
        get_leaf("lm_head"), xs.reshape(n * t, h), toks.reshape(n * t, -1))
    return (best.reshape(n, t), top.reshape(n, t), lse.reshape(n, t),
            got.reshape(n, t, -1))
