"""The plain reference for openPangu-Ultra-MoE: float32 ``jax.numpy``, no
kernel, no cache, no batching, no absorbed form.

The published description is the model's ``config.json``; ``cfg`` holds
its keys, with ``n_routed_experts`` the experts held here, ``first_expert``
the first of them and ``router_experts`` the router's range. One layer
(``sandwich_norm``), on one row ``x`` (S, H)::

    x = x + N_post_attn(Attn(N_in(x)))
    x = x + N_post_mlp(F(N_pre_mlp(x)))

- Attention (latent), per head ``h``: ``c_q = RMSNorm(x W_qa)``,
  ``[q_nope_h | q_rope_h] = c_q W_qb``, ``[c_kv | k_r] = x W_kva``,
  ``c_kv <- RMSNorm(c_kv)``, ``[k_nope_h | v_h] = c_kv W_kvb``: **keys and
  values are expanded for every head and position**; the half-split
  rotation at ``rope_theta`` on ``q_rope_h`` and on ``k_r`` (one for all
  heads); causal softmax of ``(q_nope_h . k_nope_h + q_rope_h . k_r) /
  sqrt(nope + rope)``; ``o = concat_h(sum p v_h) W_o``.
- ``F`` in the first ``first_k_dense_replace`` layers: ``down(silu(gate(x))
  * up(x))``. After them: ``s = sigmoid(x W_g)`` over the whole range, the
  ``num_experts_per_tok`` best, ``w = s_top / (sum s_top + 1e-20) *
  routed_scaling_factor``, and **a loop over the held experts**, each
  applied to every token and weighted by ``w`` where it was chosen and by
  zero elsewhere, plus the shared expert. What an absent expert would add
  is left out.
- Model: ``embed[tokens]``, the layers, ``final_norm``, ``x @ lm_head``.

It imports nothing of ``tensorflowonspark_tpu``. Every weight matmul goes
through the ``mm`` hook of ``perfbench/reference.py`` (``mm_highest``; the
control ``mm_fp8``); the head is reduced, block by block, to what a
served token needs. The router is a weight matmul like any other, so the
control rounds it too.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from perfbench.reference import HI, MM, _blocked, rms_norm, rope


def attention(cfg, w, n, pos, mm, blocks):
    heads, rank, rot = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd, eps = cfg["qk_nope_head_dim"], cfg["v_head_dim"], cfg["rms_norm_eps"]
    s, theta = n.shape[0], float(cfg["rope_theta"])
    c_q = rms_norm(mm(n, w["attn/q_a_proj/kernel"]), w["attn/q_a_norm/scale"], eps)
    q = mm(c_q, w["attn/q_b_proj/kernel"]).reshape(s, heads, nope + rot)
    kv = mm(n, w["attn/kv_a_proj/kernel"])
    c_kv = rms_norm(kv[:, :rank], w["attn/kv_a_norm/scale"], eps)
    k_r = rope(kv[:, None, rank:], pos, theta)[:, 0]  # (S, rope): one for all heads
    up = mm(c_kv, w["attn/kv_b_proj"]).reshape(s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], axis=-1)
    idx = jnp.arange(s, dtype=jnp.int32)

    def one_head(args):
        qh, kh, vh = args  # (S, nope + rope), (S, nope), (S, vd)
        keys = jnp.concatenate([kh, k_r], axis=-1)

        def block(qb, idxq):
            sc = jnp.einsum("qd,kd->qk", qb, keys, precision=HI) * (nope + rot) ** -0.5
            p = jax.nn.softmax(jnp.where(idx[None, :] <= idxq[:, None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("qk,kd->qd", p, vh, precision=HI)

        return _blocked(block, blocks, qh, idx)

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2), up[..., :nope].transpose(1, 0, 2),
                                 up[..., nope:].transpose(1, 0, 2)))
    return mm(out.transpose(1, 0, 2).reshape(s, heads * vd), w["attn/o_proj/kernel"])


def swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def experts(cfg, w, n, mm):
    """The held experts' part and the shared expert, on normed n (S, H)."""
    k, first = cfg["num_experts_per_tok"], cfg.get("first_expert", 0)
    scores = jax.nn.sigmoid(mm(n, w["moe/router"]))  # (S, range)
    top, chosen = jax.lax.top_k(scores, k)
    weight = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    y = swiglu(n, w["moe/shared_gate/kernel"], w["moe/shared_up/kernel"],
               w["moe/shared_down/kernel"], mm)
    for e in range(cfg["n_routed_experts"]):
        we = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)  # (S,)
        y = y + we[:, None] * swiglu(n, w["moe/w_gate"][e], w["moe/w_up"][e],
                                     w["moe/w_down"][e], mm)
    return y


def layer(cfg, w, x, pos, mm, blocks, routed: bool):
    """One layer on one row x (S, H); ``w`` maps a layer's leaf names to
    float32 arrays."""
    eps = cfg["rms_norm_eps"]
    a = attention(cfg, w, rms_norm(x, w["in_norm/scale"], eps), pos, mm, blocks)
    x = x + rms_norm(a, w["post_attn_norm/scale"], eps)

    def ff(xb):
        n = rms_norm(xb, w["pre_mlp_norm/scale"], eps)
        if routed:
            y = experts(cfg, w, n, mm)
        else:
            y = swiglu(n, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"],
                       w["mlp/down_proj/kernel"], mm)
        return xb + rms_norm(y, w["post_mlp_norm/scale"], eps)

    return _blocked(ff, blocks, x)


ATTN_LEAVES = (
    "in_norm/scale", "attn/q_a_proj/kernel", "attn/q_a_norm/scale", "attn/q_b_proj/kernel",
    "attn/kv_a_proj/kernel", "attn/kv_a_norm/scale", "attn/kv_b_proj", "attn/o_proj/kernel",
    "post_attn_norm/scale", "pre_mlp_norm/scale", "post_mlp_norm/scale",
)
DENSE_LEAVES = ATTN_LEAVES + ("mlp/gate_proj/kernel", "mlp/up_proj/kernel", "mlp/down_proj/kernel")
ROUTED_LEAVES = ATTN_LEAVES + (
    "moe/router", "moe/w_gate", "moe/w_up", "moe/w_down", "moe/shared_gate/kernel",
    "moe/shared_up/kernel", "moe/shared_down/kernel",
)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mm_name, blocks, vocab_blocks):
    cfg = json.loads(cfg_key)
    mm = MM[mm_name]

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=2)
    def one_layer(w, x, routed):  # x (N, L, H): each row one sequence from position 0
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        return jax.lax.map(lambda xr: layer(cfg, w, xr, pos, mm, blocks, routed), x)

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
            routed = n >= cfg["first_k_dense_replace"]
            names = ROUTED_LEAVES if routed else DENSE_LEAVES
            x = one_layer({k: get_leaf(f"layer{n}/{k}") for k in names}, x, routed)
        xs = final(get_leaf("final_norm/scale"), x, jnp.asarray(at))
        n, t, h = xs.shape
        toks = jnp.asarray(toks)
        best, top, lse, got = reduced_head(
            get_leaf("lm_head"), xs.reshape(n * t, h), toks.reshape(n * t, -1))
    return (best.reshape(n, t), top.reshape(n, t), lse.reshape(n, t),
            got.reshape(n, t, -1))
