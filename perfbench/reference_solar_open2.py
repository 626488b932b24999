"""The plain reference for Solar-Open2: float32 ``jax.numpy``, no kernel, no
cache, no batching, no chunked form.

The published description is the model's ``config.json``; ``cfg`` holds
its keys, with ``n_routed_experts`` the experts held here, ``first_expert``
the first of them and ``router_experts`` the router's range. One layer
``l``, pre-norm, on one row ``x`` (S, H)::

    x = x + Mix_l(N1(x));   x = x + MoE(N2(x))

- ``Mix_l``, ``l`` in ``gqa_layers``: gated grouped-query attention.
  ``q = n W_q``, ``k = n W_k``, ``v = n W_v`` (rotated only where
  ``use_rope``: published false), the full causal softmax of ``q . k /
  sqrt(d)``, ``o = (a * sigmoid(n W_g)) W_o``.
- ``Mix_l`` otherwise: the linear layer, **as the recurrence itself, a
  ``lax.scan`` over positions**. ``q~, k~, v~ = SiLU(Conv4(n W_.))``
  (causal, depthwise, no bias, zeros before the first token); ``q =
  L2norm(q~) / sqrt(d)``, ``k = L2norm(k~)`` a head; ``g = -exp(A_log) *
  softplus((n W_fa) W_fb + dt_bias)``, ``a = exp(g)``; ``b = sigmoid(n
  W_b)``, doubled where ``kda_allow_neg_eigval``; a position at a time::

      S <- Diag(a) S;  S <- S - b k (k^T S) + b k v^T;  o = S^T q

  then ``y = RMSNorm_d(o) * sigmoid((n W_ga) W_gb + bias)``, ``y W_o``.
- ``MoE``: ``reference_pangu_moe.experts``: sigmoid scores over the whole
  range, the ``num_experts_per_tok`` best renormalised and scaled, **a
  loop over the held experts**, plus the shared expert. What an absent
  expert would add is left out.
- Model: ``embed[tokens]``, the layers, ``final_norm``, ``x @ lm_head``.

It imports nothing of ``tensorflowonspark_tpu``. Every weight matmul goes
through the ``mm`` hook of ``perfbench/reference.py`` (``mm_highest``; the
control ``mm_fp8``); the head is reduced, block by block, to what a
served token needs.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from perfbench import reference
from perfbench.reference import HI, MM, _blocked, rms_norm, rope
from perfbench.reference_pangu_moe import experts


def gated_attention(cfg, w, n, pos, mm, blocks):
    hd, s = cfg["head_dim"], n.shape[0]
    q = mm(n, w["attn/q_proj/kernel"]).reshape(s, -1, hd)
    k = mm(n, w["attn/k_proj/kernel"]).reshape(s, -1, hd)
    v = mm(n, w["attn/v_proj/kernel"]).reshape(s, -1, hd)
    if cfg["use_rope"]:
        q, k = rope(q, pos, float(cfg["rope_theta"])), rope(k, pos, float(cfg["rope_theta"]))
    a = reference.attention(q, k, v, jnp.zeros((s,), jnp.int32), pos, None, blocks)
    if cfg["use_gqa_gate"]:
        a = a * jax.nn.sigmoid(mm(n, w["attn/g_proj/kernel"]))
    return mm(a, w["attn/o_proj/kernel"])


def conv_silu(x, w):
    """Causal depthwise convolution as the sum over ``k`` shifted copies,
    zeros before the first token, then SiLU: x (S, c), w (k, c), tap
    ``k - 1`` on the current token."""
    k, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(padded[j : j + s] * w[j] for j in range(k)))


def delta_rule(q, k, v, alpha, beta):
    """q, k, alpha (S, h, d_k), v (S, h, d_v), beta (S, h): one position
    at a time from a zero state, the transition applied as it is
    written."""

    def step(S, inp):
        qt, kt, vt, at, bt = inp
        S = at[:, :, None] * S
        kS = jnp.einsum("hk,hkv->hv", kt, S, precision=HI)
        S = S + (bt[:, None] * kt)[:, :, None] * (vt - kS)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(step, S0, (q, k, v, alpha, beta))
    return o


def linear_attention(cfg, w, n, mm):
    """The linear layer on one row's normed input n (S, H)."""
    lin = cfg["linear_attn_config"]
    h, d, s = lin["num_heads"], lin["head_dim"], n.shape[0]
    heads = lambda x: x.reshape(s, h, d)  # noqa: E731
    q, k, v = (
        heads(conv_silu(mm(n, w[f"mixer/{t}_proj/kernel"]), w[f"mixer/{t}_conv"]))
        for t in "qkv"
    )
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(q) * d**-0.5, unit(k)
    f = mm(mm(n, w["mixer/f_a_proj/kernel"]), w["mixer/f_b_proj/kernel"])
    g = -jnp.exp(w["mixer/A_log"])[:, None] * jax.nn.softplus(heads(f + w["mixer/dt_bias"]))
    beta = jax.nn.sigmoid(mm(n, w["mixer/b_proj/kernel"]))
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = rms_norm(delta_rule(q, k, v, jnp.exp(g), beta), w["mixer/o_norm"], cfg["rms_norm_eps"])
    gate = mm(mm(n, w["mixer/g_a_proj/kernel"]), w["mixer/g_b_proj/kernel"]) + w["mixer/g_b_proj/bias"]
    return mm(o.reshape(s, h * d) * jax.nn.sigmoid(gate), w["mixer/o_proj/kernel"])


def layer(cfg, w, x, pos, mm, blocks, gqa: bool):
    """One layer on one row x (S, H); ``w`` maps a layer's leaf names to
    float32 arrays."""
    eps = cfg["rms_norm_eps"]
    n = rms_norm(x, w["in_norm/scale"], eps)
    x = x + (gated_attention(cfg, w, n, pos, mm, blocks) if gqa
             else linear_attention(cfg, w, n, mm))
    return _blocked(
        lambda xb: xb + experts(cfg, w, rms_norm(xb, w["ff_norm/scale"], eps), mm),
        blocks, x)


MOE_LEAVES = (
    "in_norm/scale", "ff_norm/scale", "moe/router", "moe/w_gate", "moe/w_up", "moe/w_down",
    "moe/shared_gate/kernel", "moe/shared_up/kernel", "moe/shared_down/kernel",
)
GQA_LEAVES = MOE_LEAVES + tuple(f"attn/{t}_proj/kernel" for t in "qkvgo")
LINEAR_LEAVES = MOE_LEAVES + tuple(
    f"mixer/{t}" for t in (
        "q_proj/kernel", "k_proj/kernel", "v_proj/kernel", "f_a_proj/kernel",
        "f_b_proj/kernel", "g_a_proj/kernel", "g_b_proj/kernel", "g_b_proj/bias",
        "b_proj/kernel", "q_conv", "k_conv", "v_conv", "A_log", "dt_bias", "o_norm",
        "o_proj/kernel",
    ))


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mm_name, blocks, vocab_blocks):
    cfg = json.loads(cfg_key)
    mm = MM[mm_name]

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=2)
    def one_layer(w, x, gqa):  # x (N, L, H): each row one sequence from position 0
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        return jax.lax.map(lambda xr: layer(cfg, w, xr, pos, mm, blocks, gqa), x)

    @jax.jit
    def final(scale, x, at):  # the hidden states to read, normed: (N, T, H)
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        return rms_norm(xs, scale, cfg["rms_norm_eps"])

    @jax.jit
    def reduced_head(w, xs, toks):
        """Over vocabulary blocks, per position of xs (M, H): the best
        logit, its index, the log-sum-exp, and the logits of ``toks``
        (M, K)."""
        m, v = xs.shape[0], w.shape[1]
        vb = v // vocab_blocks

        def block(carry, i):
            best, top, lse, got = carry
            lg = mm(xs, jax.lax.dynamic_slice_in_dim(w, i * vb, vb, axis=1))  # (M, vb)
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
        x = embed(get_leaf("embed"), jnp.asarray(seqs))
        for n in range(cfg["num_hidden_layers"]):
            gqa = n in cfg["gqa_layers"]
            names = GQA_LEAVES if gqa else LINEAR_LEAVES
            x = one_layer({k: get_leaf(f"layer{n}/{k}") for k in names}, x, gqa)
        xs = final(get_leaf("final_norm/scale"), x, jnp.asarray(at))
        n, t, h = xs.shape
        toks = jnp.asarray(toks)
        best, top, lse, got = reduced_head(
            get_leaf("lm_head"), xs.reshape(n * t, h), toks.reshape(n * t, -1))
    return (best.reshape(n, t), top.reshape(n, t), lse.reshape(n, t),
            got.reshape(n, t, -1))
