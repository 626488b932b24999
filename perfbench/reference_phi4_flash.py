"""The plain reference for Phi-4-mini-flash (SambaY): float32 ``jax.numpy``,
no kernel, no cache, no batching, no shared K/V trick.

The published description is the model's ``config.json``; ``cfg`` holds
its keys, and under ``mamba`` the sizes it leaves to the modelling code.
The layers (``counts_phi4_flash.layer_kinds``), pre-norm with LayerNorm,
on one row ``x`` (S, H)::

    x = x + Mix_l(LN1(x));   x = x + W_fc2 (SiLU(g) * v),  [g, v] = LN2(x) W_fc1

- Mamba-1, **as the recurrence itself, a ``lax.scan`` over positions**:
  ``[u, z] = n W_in``; ``u = SiLU(conv4(u) + b)`` (causal, depthwise,
  zeros before the first token); ``[d, B, C] = u W_x``; ``dt = softplus(d
  W_dt + b_dt)``; ``A = -exp(A_log)`` (c, N); a position at a time ``S =
  exp(dt A) * S + (dt u) B``, ``y = S C + D u``; out ``(y * SiLU(z))
  W_out``. The memory layer's ``y`` is the GMUs' memory.
- Differential attention, **both softmax maps written out**: heads ``(2i,
  2i + 1)`` are ``(q1, q2)`` of differential head ``i``, KV heads ``(2j,
  2j + 1)`` are ``(k1, k2)``, ``(v1, v2)`` of pair ``j``, head ``i`` reads
  pair ``i // 2``: ``a = softmax(q1 k1^T / sqrt(d)) [v1|v2] - lambda
  softmax(q2 k2^T / sqrt(d)) [v1|v2]``, ``lambda = exp(lq1 . lk1) - exp(lq2
  . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; RMSNorm
  of the 2d values times ``1 - lambda_init``; ``W_o``. Windowed layers see
  the last ``sliding_window`` positions, the full layer all before; a
  cross layer has queries only and attends the full layer's K and V.
- GMU: ``(SiLU(n W_1) * m) W_2``, ``m`` the memory at the same position.
- Model: ``embed[tokens]``, the layers **every one at every position**,
  ``final_norm``, ``x @ embed^T``.

It imports nothing of ``tensorflowonspark_tpu``. Every weight matmul goes
through the ``mm`` hook of ``perfbench/reference.py`` (``mm_highest``; the
control ``mm_fp8``); the head is reduced, block by block, to what a served
token needs.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from perfbench import counts_phi4_flash as counts
from perfbench.reference import HI, MM, _blocked, rms_norm


def layer_norm(x, scale, bias, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale + bias


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def mamba(cfg, w, n, mm):
    """The Mamba-1 mixer on one row's normed input n (S, H): (out, y)."""
    c, N, _, r = counts.mamba(cfg)
    uz = mm(n, w["mamba/in_proj/kernel"])
    u = jax.nn.silu(
        causal_conv(uz[:, :c], w["mamba/conv_weight"]) + w["mamba/conv_bias"])
    z = uz[:, c:]
    dbc = mm(u, w["mamba/x_proj/kernel"])
    dt = jax.nn.softplus(mm(dbc[:, :r], w["mamba/dt_proj/kernel"]) + w["mamba/dt_bias"])
    B, C = dbc[:, r : r + N], dbc[:, r + N :]
    A = -jnp.exp(w["mamba/A_log"])  # (c, N)

    def step(S, inp):
        ut, dtt, bt, ct = inp
        S = jnp.exp(dtt[:, None] * A) * S + (dtt * ut)[:, None] * bt[None, :]
        return S, jnp.einsum("cn,n->c", S, ct, precision=HI) + w["mamba/D"] * ut

    _, y = jax.lax.scan(step, jnp.zeros((c, N), jnp.float32), (u, dt, B, C))
    return mm(y * jax.nn.silu(z), w["mamba/out_proj/kernel"]), y


def causal_conv(x, w):
    """Causal depthwise convolution as the sum over ``k`` shifted copies,
    zeros before the first token: x (S, c), w (k, c), tap ``k - 1`` on
    the current token."""
    k, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j : j + s] * w[j] for j in range(k))


def attention_layer(cfg, w, n, kv, lam0, window, mm, blocks):
    """The attention mixer on one row: its output and its own K and V
    (the given ones for a cross layer)."""
    d = counts.head_dim(cfg)
    s = n.shape[0]
    q = mm(n, w["attn/q_proj/kernel"]).reshape(s, -1, 2, d)  # (S, heads, 2, d)
    if kv is None:
        k = mm(n, w["attn/k_proj/kernel"]).reshape(s, -1, d)
        v = mm(n, w["attn/v_proj/kernel"]).reshape(s, -1, d)
    else:
        k, v = kv
    pairs = k.shape[1] // 2
    k2 = k.reshape(s, pairs, 2, d)
    vv = v.reshape(s, pairs, 2 * d)  # [v1 | v2] a pair
    heads = q.shape[1]
    idx = jnp.arange(s)
    dot = lambda a, b: jnp.sum(w[f"attn/{a}"] * w[f"attn/{b}"])  # noqa: E731
    lam = jnp.exp(dot("lambda_q1", "lambda_k1")) - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam0

    def one_head(i):
        j = i // 2

        def block(qb, iq):
            ok = idx[None, :] <= iq[:, None]
            if window is not None:
                ok &= iq[:, None] - idx[None, :] < window
            maps = []
            for m in range(2):
                sc = jnp.einsum("qd,kd->qk", qb[:, m], k2[:, j, m], precision=HI) * d**-0.5
                p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
                maps.append(jnp.einsum("qk,kd->qd", p, vv[:, j], precision=HI))
            a = maps[0] - lam * maps[1]
            return rms_norm(a, w["attn/subln"], cfg["layer_norm_eps"]) * (1.0 - lam0)

        return _blocked(block, blocks, q[:, i], idx)

    a = jax.lax.map(one_head, jnp.arange(heads))  # (heads, S, 2d)
    out = mm(a.transpose(1, 0, 2).reshape(s, heads * 2 * d), w["attn/o_proj/kernel"])
    return out, (k, v)


def layer(cfg, w, x, kind, lam0, memory, kv, mm, blocks):
    """One layer on one row x (S, H): (x, memory, kv)."""
    eps = cfg["layer_norm_eps"]
    n = layer_norm(x, w["in_norm/scale"], w["in_norm/bias"], eps)
    if kind == "mamba":
        mixed, y = mamba(cfg, w, n, mm)
        memory = y
    elif kind == "gmu":
        mixed = mm(jax.nn.silu(mm(n, w["gmu_in/kernel"])) * memory, w["gmu_out/kernel"])
    else:
        window = cfg["sliding_window"] if kind == "swa" else None
        mixed, own = attention_layer(
            cfg, w, n, kv if kind == "cross" else None, lam0, window, mm, blocks)
        if kind == "full":
            kv = own
    x = x + mixed
    f = cfg["intermediate_size"]

    def mlp(xb):
        gv = mm(layer_norm(xb, w["ff_norm/scale"], w["ff_norm/bias"], eps), w["fc1/kernel"])
        return xb + mm(jax.nn.silu(gv[:, :f]) * gv[:, f:], w["fc2/kernel"])

    return _blocked(mlp, blocks, x), memory, kv


def layer_leaves(cfg: dict, kind: str) -> tuple[str, ...]:
    norms = ("in_norm/scale", "in_norm/bias", "ff_norm/scale", "ff_norm/bias",
             "fc1/kernel", "fc2/kernel")
    if kind == "mamba":
        return norms + tuple(f"mamba/{t}" for t in (
            "in_proj/kernel", "conv_weight", "conv_bias", "x_proj/kernel",
            "dt_proj/kernel", "dt_bias", "A_log", "D", "out_proj/kernel"))
    if kind == "gmu":
        return norms + ("gmu_in/kernel", "gmu_out/kernel")
    own = () if kind == "cross" else ("attn/k_proj/kernel", "attn/v_proj/kernel")
    return norms + own + tuple(f"attn/{t}" for t in (
        "q_proj/kernel", "o_proj/kernel", "lambda_q1", "lambda_k1", "lambda_q2",
        "lambda_k2", "subln"))


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mm_name, blocks, vocab_blocks):
    cfg = json.loads(cfg_key)
    mm = MM[mm_name]

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(2,))
    def one_layer(w, x, kind, lam0, memory, kv):  # x (N, L, H): each row from position 0
        return jax.lax.map(
            lambda a: layer(cfg, w, a[0], kind, lam0, a[1], a[2], mm, blocks),
            (x, memory, kv))

    @jax.jit
    def final(scale, bias, x, at):  # the hidden states to read, normed: (N, T, H)
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        return layer_norm(xs, scale, bias, cfg["layer_norm_eps"])

    @jax.jit
    def reduced_head(table, xs, toks):
        """Over vocabulary blocks of the tied head, per position of xs (M,
        H): the best logit, its index, the log-sum-exp, and the logits of
        ``toks`` (M, K)."""
        m, v = xs.shape[0], table.shape[0]
        vb = v // vocab_blocks

        def block(carry, i):
            best, top, lse, got = carry
            rows = jax.lax.dynamic_slice_in_dim(table, i * vb, vb, axis=0)
            lg = mm(xs, rows.T)  # (M, vb)
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

    return embed, one_layer, final, reduced_head


def serve_readings(cfg: dict, get_leaf, seqs, at, toks, *, mm="highest",
                   blocks=4, vocab_blocks=8):
    """One full forward pass over ``seqs`` (N, L) int32 (each row one
    sequence from position 0, padding after its end), read at positions
    ``at`` (N, T), the head reduced to what a served token needs. ``toks``
    (N, T, K) int32: K token ids a position. Returns ``best`` (N, T),
    ``top`` (N, T), ``lse`` (N, T) and ``got`` (N, T, K), the logits of
    ``toks``; ``got - lse`` are their log-probabilities. ``get_leaf(name)``
    returns a float32 weight; a layer's leaves are fetched together and
    dropped before the next layer's."""
    if cfg["vocab_size"] % vocab_blocks:
        vocab_blocks = 1
    with jax.default_matmul_precision("highest"):
        embed, one_layer, final, reduced_head = _programs(
            json.dumps(cfg, sort_keys=True), mm, blocks, vocab_blocks)
        table = get_leaf("embed")
        x = embed(table, jnp.asarray(seqs))
        n, L, _ = x.shape
        c, _, _, _ = counts.mamba(cfg)
        kvw = cfg["num_key_value_heads"]
        memory = jnp.zeros((n, L, c), jnp.float32)
        kv = (jnp.zeros((n, L, kvw, counts.head_dim(cfg)), jnp.float32),) * 2
        for i, kind in enumerate(counts.layer_kinds(cfg)):
            w = {k: get_leaf(f"layer{i}/{k}") for k in layer_leaves(cfg, kind)}
            x, memory, kv = one_layer(w, x, kind, jnp.float32(lambda_init(i)), memory, kv)
        xs = final(get_leaf("final_norm/scale"), get_leaf("final_norm/bias"), x,
                   jnp.asarray(at))
        t, h = xs.shape[1], xs.shape[2]
        toks = jnp.asarray(toks)
        best, top, lse, got = reduced_head(table, xs.reshape(n * t, h),
                                           toks.reshape(n * t, -1))
    return (best.reshape(n, t), top.reshape(n, t), lse.reshape(n, t),
            got.reshape(n, t, -1))
