"""The plain reference for Laguna: float32 ``jax.numpy``, no kernel, no
cache, no batching, no ring.

The published description is the model's ``config.json``; ``cfg`` holds
its keys. One layer ``l``, pre-norm with RMSNorm, on one row ``x`` (S,
H)::

    x = x + Attn_l(N1(x));   x = x + F_l(N2(x))

- ``Attn_l``: ``h_l = num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` K/V heads of ``head_dim``, query head ``i``
  reading K/V head ``i // (h_l / kv)``. The rotary embedding of the
  layer's kind (``rope_parameters``): in a full layer the first
  ``partial_rotary_factor * head_dim`` channels of each q and k head turn
  at YaRN's frequencies, **written out here from the formula** (the
  correction range of ``beta_fast`` and ``beta_slow`` over
  ``original_max_position_embeddings``, floored and ceiled, a linear ramp
  between the extrapolated frequencies and those divided by ``factor``),
  cos and sin times ``attention_factor``, the other channels passed; in a
  windowed layer the whole head turns at ``rope_theta``. The softmax of
  ``q . k / sqrt(d)`` over the keys not after the query, and in a
  windowed layer **less than ``sliding_window`` before it (a mask)**;
  then, where ``gating``, each head times ``sigmoid(n W_g)`` of its own,
  and ``W_o``.
- ``F_l``: SwiGLU where ``mlp_layer_types[l]`` is ``dense``; else the
  softmax of ``n W_r`` over all experts, the ``num_experts_per_tok`` best
  renormalised and times ``moe_routed_scaling_factor``, **a loop over
  every expert** (each applied to the whole row, weighted by what the row's
  tokens give it, zero where it was not chosen), plus the shared expert.
- Model: ``embed[tokens]``, the layers, ``final_norm``, ``x @ lm_head``.

It imports nothing of ``tensorflowonspark_tpu``. Every weight matmul goes
through the ``mm`` hook of ``perfbench/reference.py`` (``mm_highest``; the
control ``mm_fp8``); the head is reduced, block by block, to what a served
token needs (``reference_solar_open2``'s reduction).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from perfbench import reference_solar_open2
from perfbench.reference import HI, MM, _blocked, rms_norm

FULL = "full_attention"


def yarn_inv_freq(dim: int, theta: float, p: dict):
    """HF's ``_compute_yarn_parameters`` for a rotary width ``dim``:
    ``(inv_freq (dim / 2,), attention_factor)``."""
    orig = p["original_max_position_embeddings"]
    factor = p["factor"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(p.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(p.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv = interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor
    af = p.get("attention_factor")
    if af is None:
        af = 0.1 * math.log(factor) + 1.0
    return inv, af


def layer_rope(cfg: dict, layer: int):
    """(rotary channels, inverse frequencies, cos/sin scale) of a layer."""
    kind = cfg["layer_types"][layer]
    p = cfg["rope_parameters"][kind]
    d = cfg["head_dim"]
    rot = int(d * p.get("partial_rotary_factor", 1.0))
    theta = float(p["rope_theta"])
    if p.get("rope_type", "default") == "yarn":
        inv, af = yarn_inv_freq(rot, theta, p)
        return rot, inv, af
    return rot, theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot), 1.0


def rotate(x, pos, rot, inv, scale):
    """x (S, heads, d): the first ``rot`` channels rotated (halves, as
    ``rotate_half``), the rest passed."""
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    r, rest = x[..., :rot], x[..., rot:]
    x1, x2 = r[..., : rot // 2], r[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(cfg, w, n, pos, mm, layer: int, q_blocks: int):
    d, s = cfg["head_dim"], n.shape[0]
    kv = cfg["num_key_value_heads"]
    window = None if cfg["layer_types"][layer] == FULL else cfg["sliding_window"]
    rot, inv, scale = layer_rope(cfg, layer)
    q = rotate(mm(n, w["attn/q_proj/kernel"]).reshape(s, -1, d), pos, rot, inv, scale)
    k = rotate(mm(n, w["attn/k_proj/kernel"]).reshape(s, kv, d), pos, rot, inv, scale)
    v = mm(n, w["attn/v_proj/kernel"]).reshape(s, kv, d)
    heads = q.shape[1]
    r = heads // kv
    qh = q.reshape(s, kv, r, d).transpose(1, 0, 2, 3)  # (kv, S, r, d)
    idx = jnp.arange(s, dtype=jnp.int32)

    def one_kv_head(args):
        qg, kg, vg = args

        def block(qb, iq):
            sc = jnp.einsum("qrd,kd->rqk", qb, kg, precision=HI) * d**-0.5
            ok = idx[None, :] <= iq[:, None]
            if window is not None:
                ok &= iq[:, None] - idx[None, :] < window
            p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("rqk,kd->qrd", p, vg, precision=HI)

        return _blocked(block, q_blocks, qg, idx)

    a = jax.lax.map(one_kv_head, (qh, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    a = a.transpose(1, 0, 2, 3).reshape(s, heads, d)
    if cfg["gating"]:
        a = a * jax.nn.sigmoid(mm(n, w["attn/g_proj/kernel"]))[..., None]  # a head's own
    return mm(a.reshape(s, heads * d), w["attn/o_proj/kernel"])


def swiglu(n, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(n, wg)) * mm(n, wu), wd)


def experts(cfg, w, n, mm):
    """The routed experts and the shared one on normed n (S, H)."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(n, w["moe/router"]), axis=-1)  # (S, E)
    top, chosen = jax.lax.top_k(probs, k)
    weight = top / jnp.sum(top, axis=-1, keepdims=True) * cfg["moe_routed_scaling_factor"]
    y = swiglu(n, w["moe/shared_gate/kernel"], w["moe/shared_up/kernel"],
               w["moe/shared_down/kernel"], mm)

    def one(y, e):
        we = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)  # (S,)
        out = swiglu(n, w["moe/w_gate"][e], w["moe/w_up"][e], w["moe/w_down"][e], mm)
        return y + we[:, None] * out, None

    y, _ = jax.lax.scan(one, y, jnp.arange(cfg["num_experts"]))
    return y


def layer(cfg, w, x, pos, mm, blocks, n: int):
    """Layer ``n`` on one row x (S, H); ``w`` maps a layer's leaf names to
    float32 arrays."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, w, rms_norm(x, w["in_norm/scale"], eps), pos, mm, n, blocks)
    h = rms_norm(x, w["ff_norm/scale"], eps)
    if cfg["mlp_layer_types"][n] == "dense":
        return x + swiglu(h, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"],
                          w["mlp/down_proj/kernel"], mm)
    return x + experts(cfg, w, h, mm)


ATTN_LEAVES = ("in_norm/scale", "ff_norm/scale") + tuple(
    f"attn/{t}_proj/kernel" for t in "qkvgo")
DENSE_LEAVES = ATTN_LEAVES + tuple(f"mlp/{t}_proj/kernel" for t in ("gate", "up", "down"))
SPARSE_LEAVES = ATTN_LEAVES + (
    "moe/router", "moe/w_gate", "moe/w_up", "moe/w_down",
    "moe/shared_gate/kernel", "moe/shared_up/kernel", "moe/shared_down/kernel",
)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mm_name, blocks):
    cfg = json.loads(cfg_key)
    mm = MM[mm_name]

    @functools.partial(jax.jit, static_argnums=2)
    def one_layer(w, x, n):  # x (N, L, H): each row one sequence from position 0
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        return jax.lax.map(lambda xr: layer(cfg, w, xr, pos, mm, blocks, n), x)

    return one_layer


def serve_readings(cfg: dict, get_leaf, seqs, at, toks, *, mm="highest",
                   blocks=8, vocab_blocks=8):
    """``reference_solar_open2.serve_readings`` for this model: one full
    forward pass over ``seqs`` (N, L) int32, read at positions ``at`` (N,
    T); ``toks`` (N, T, K). Returns ``best``, ``top``, ``lse`` (N, T) and
    ``got`` (N, T, K)."""
    if cfg["vocab_size"] % vocab_blocks:
        vocab_blocks = 1
    with jax.default_matmul_precision("highest"):
        one_layer = _programs(json.dumps(cfg, sort_keys=True), mm, blocks)
        # the embedding, the final norm and the blocked head are the
        # solar_open2 reference's, which read nothing else of a config
        embed, _, final, reduced_head = reference_solar_open2._programs(
            json.dumps({"rms_norm_eps": cfg["rms_norm_eps"]}), mm, blocks, vocab_blocks)
        x = embed(get_leaf("embed"), jnp.asarray(seqs))
        for n in range(cfg["num_hidden_layers"]):
            names = DENSE_LEAVES if cfg["mlp_layer_types"][n] == "dense" else SPARSE_LEAVES
            x = one_layer({k: get_leaf(f"layer{n}/{k}") for k in names}, x, n)
        xs = final(get_leaf("final_norm/scale"), x, jnp.asarray(at))
        n, t, h = xs.shape
        toks = jnp.asarray(toks)
        best, top, lse, got = reduced_head(
            get_leaf("lm_head"), xs.reshape(n * t, h), toks.reshape(n * t, -1))
    return (best.reshape(n, t), top.reshape(n, t), lse.reshape(n, t),
            got.reshape(n, t, -1))
