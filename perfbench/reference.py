"""The plain reference: a Mistral decoder in float32 ``jax.numpy``.

RMSNorm, rotary embedding (half-split, as the public implementation
rotates), grouped-query attention that is causal inside a document and
sees at most the last ``sliding_window`` positions, SwiGLU, untied head;
the next-token loss over packed rows, its gradient, and the AdamW update.
Every matmul runs at ``Precision.HIGHEST``. It imports nothing of
``tensorflowonspark_tpu`` and takes nothing the program made: weights come
from ``perfbench.weights`` by seed, which the benchmark also hands to the
program.

It works on one row at a time and in blocks (query blocks of one KV head,
token blocks of the MLP and of the head) under ``jax.checkpoint``, so that
a row of 8192 tokens at the published widths fits beside the parameters.

``mm`` is the one hook: the function every weight matmul goes through.
``mm_highest`` is the reference; ``mm_fp8`` is the control, the same
arithmetic with both operands rounded to float8 (e4m3 forward, e5m2 for
the cotangent, one scale per tensor), the precision below bfloat16 that
would tempt a later PR.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def mm_highest(x, w):
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32), precision=HI)


def _fp8(x, dtype=jnp.float8_e4m3fn, top=448.0):
    """Round to float8 with one scale per tensor."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def mm_fp8(x, w):
    """The usual float8 recipe: e4m3 operands forward, and in the
    backward pass the cotangent in e5m2 against the e4m3 operands."""
    return jnp.dot(_fp8(x), _fp8(w), precision=HI)


def _mm_fp8_fwd(x, w):
    return mm_fp8(x, w), (x, w)


def _mm_fp8_bwd(res, g):
    x, w = res
    g = _fp8(g, jnp.float8_e5m2, 57344.0)
    return (jnp.dot(g, _fp8(w).T, precision=HI).astype(x.dtype),
            jnp.dot(_fp8(x).T, g, precision=HI).astype(w.dtype))


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


MM = {"highest": mm_highest, "fp8": mm_fp8}


def _blocked(fn, n_blocks: int, *xs):
    """``fn`` over ``n_blocks`` equal blocks of the leading axis of each
    of ``xs``, one at a time, recomputed in the backward pass."""
    n = xs[0].shape[0]
    if n % n_blocks:
        n_blocks = 1
    split = [x.reshape(n_blocks, n // n_blocks, *x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a), tuple(split))
    return jax.tree.map(lambda o: o.reshape(n, *o.shape[2:]), out)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (S, heads, D), pos (S,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def documents(seg):
    """Per position of one row: the index of its document (a run of equal
    segment ids) and its position inside it."""
    idx = jnp.arange(seg.shape[0], dtype=jnp.int32)
    new = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    doc = jnp.cumsum(new.astype(jnp.int32)) - 1
    start = jax.lax.cummax(jnp.where(new, idx, 0))
    return doc, idx - start


def attention(q, k, v, doc, pos, window, q_blocks):
    """q (S, Hq, D), k and v (S, Hkv, D): softmax(QK^T/sqrt(D))V over the
    keys of the same document that are not after the query and less than
    ``window`` positions before it."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    r = hq // hkv
    idx = jnp.arange(s, dtype=jnp.int32)
    qh = q.reshape(s, hkv, r, d).transpose(1, 0, 2, 3)  # (Hkv, S, r, D)

    def one_kv_head(args):
        qg, kg, vg = args  # (S, r, D), (S, D), (S, D)

        def block(qb, docq, posq, idxq):
            sc = jnp.einsum("qrd,kd->rqk", qb, kg, precision=HI) * d**-0.5
            ok = (idx[None, :] <= idxq[:, None]) & (doc[None, :] == docq[:, None])
            if window is not None:
                ok &= posq[:, None] - pos[None, :] < window
            p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("rqk,kd->qrd", p, vg, precision=HI)

        return _blocked(block, q_blocks, qg, doc, pos, idx)

    out = jax.lax.map(one_kv_head, (qh, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, hq * d)  # (S, Hq*D)


def layer(cfg, w, x, doc, pos, mm, blocks):
    """One decoder layer on one row x (S, H); ``w`` maps the leaf names of
    a layer (``attn/q_proj/kernel`` ...) to float32 arrays."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    s = x.shape[0]
    h = rms_norm(x, w["attn_norm/scale"], cfg["rms_norm_eps"])
    q = mm(h, w["attn/q_proj/kernel"]).reshape(s, -1, d)
    k = mm(h, w["attn/k_proj/kernel"]).reshape(s, -1, d)
    v = mm(h, w["attn/v_proj/kernel"]).reshape(s, -1, d)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    a = attention(q, k, v, doc, pos, cfg.get("sliding_window"), blocks)
    x = x + mm(a, w["attn/o_proj/kernel"])

    def mlp(xb):
        hb = rms_norm(xb, w["mlp_norm/scale"], cfg["rms_norm_eps"])
        g, u = mm(hb, w["mlp/gate_proj/kernel"]), mm(hb, w["mlp/up_proj/kernel"])
        return xb + mm(jax.nn.silu(g) * u, w["mlp/down_proj/kernel"])

    return _blocked(mlp, blocks, x)


def _layer_weights(params: dict, n: int) -> dict:
    pre = f"layer{n}/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def row_nll(cfg, params, tokens, seg, mm, blocks):
    """Sum of the next-token losses of one packed row and how many count.

    ``tokens`` and ``seg`` are (S+1,). A position trains when it is not
    padding (segment id 0) and the next token belongs to its document.
    """
    doc_all, _ = documents(seg)
    mask = ((doc_all[:-1] == doc_all[1:]) & (seg[:-1] != 0)).astype(jnp.float32)
    doc, pos = documents(seg[:-1])
    x = params["embed"][tokens[:-1]].astype(jnp.float32)
    for n in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda xx, ww: layer(cfg, ww, xx, doc, pos, mm, blocks)
        )(x, _layer_weights(params, n))
    x = rms_norm(x, params["final_norm/scale"], cfg["rms_norm_eps"])

    def head(xb, tb, mb):
        logp = jax.nn.log_softmax(mm(xb, params["lm_head"]), axis=-1)
        nll = -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
        return (nll * mb)[:, None]

    nll = _blocked(head, blocks, x, tokens[1:], mask)
    return jnp.sum(nll), jnp.sum(mask)


def batch_loss(cfg, params, tokens, seg, mm=mm_highest, blocks=8, keep=None):
    """Mean loss over the positions of all rows that train. ``keep``
    (rows,) of 0/1 drops rows from both sums: the planted fault "half of
    the batch left out, the mean taken over the rest"."""
    def one(args):
        t, s, kp = args
        tot, n = jax.checkpoint(
            lambda p, tt, ss: row_nll(cfg, p, tt, ss, mm, blocks)
        )(params, t, s)
        return tot * kp, n * kp

    keep = jnp.ones((tokens.shape[0],), jnp.float32) if keep is None else keep
    tot, n = jax.lax.map(one, (tokens, seg, keep))
    return jnp.sum(tot) / jnp.maximum(jnp.sum(n), 1.0)


def adamw_update(opt: dict, step, p, g, mu, nu):
    """One AdamW step on one leaf, decoupled decay on every leaf; the
    moments are kept in the dtype they arrive in (the configuration's),
    the arithmetic is float32."""
    b1, b2 = opt["b1"], opt["b2"]
    g = g.astype(jnp.float32)
    m = b1 * mu.astype(jnp.float32) + (1 - b1) * g
    v = b2 * nu.astype(jnp.float32) + (1 - b2) * g * g
    t = step.astype(jnp.float32)
    u = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + opt["eps"])
    p = p - opt["learning_rate"] * (u + opt["weight_decay"] * p)
    return p, m.astype(mu.dtype), v.astype(nu.dtype)


@functools.lru_cache(maxsize=None)
def _train_programs(cfg_key, opt_key, mm_name, blocks):
    import json

    cfg, opt = json.loads(cfg_key), json.loads(opt_key)
    mm = MM[mm_name]
    grad = jax.jit(jax.value_and_grad(
        lambda p, t, s, kp: batch_loss(cfg, p, t, s, mm, blocks, kp)
    ))

    @functools.partial(jax.jit, donate_argnums=(1, 3, 4))
    def update(step, p, g, mu, nu):
        out = {k: adamw_update(opt, step, p[k], g[k], mu[k], nu[k]) for k in p}
        return tuple({k: o[i] for k, o in out.items()} for i in range(3))

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}

    @jax.jit
    def delta_norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a}

    return grad, update, norms, delta_norms


def train_readings(cfg: dict, opt: dict, make_params, batches, *, mm="highest",
                   blocks=8, moment_dtype=jnp.bfloat16, keep=None) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's parameters.

    ``make_params()`` returns a flat ``{"a/b/c": float32 array}`` dict and
    is called twice (the second copy is the starting point the change is
    measured from). Returns each step's loss, the norm of the first
    gradient of each leaf and the norm of each leaf's change over the
    steps, as floats.
    """
    import json

    grad, update, norms, delta_norms = _train_programs(
        json.dumps(cfg, sort_keys=True), json.dumps(opt, sort_keys=True), mm, blocks
    )
    p = make_params()
    mu = jax.tree.map(lambda x: jnp.zeros(x.shape, moment_dtype), p)
    nu = jax.tree.map(lambda x: jnp.zeros(x.shape, moment_dtype), p)
    rows = batches[0]["tokens"].shape[0]
    kp = jnp.ones((rows,), jnp.float32) if keep is None else jnp.asarray(keep, jnp.float32)
    losses, gnorm = [], None
    for n, b in enumerate(batches):
        loss, g = grad(p, jnp.asarray(b["tokens"]), jnp.asarray(b["segment_ids"]), kp)
        losses.append(float(loss))
        if n == 0:
            gnorm = {k: float(v) for k, v in norms(g).items()}
        p, mu, nu = update(jnp.asarray(n + 1, jnp.int32), p, g, mu, nu)
        del g
    del mu, nu
    dnorm = {k: float(v) for k, v in delta_norms(p, make_params()).items()}
    return {"losses": losses, "grad_norms": gnorm, "delta_norms": dnorm}


# -- serving ------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _serve_programs(cfg_key, mm_name, blocks):
    import json

    cfg = json.loads(cfg_key)
    mm = MM[mm_name]

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @jax.jit
    def one_layer(w, x):  # x (N, L, H), rows unpacked: one document each
        length = x.shape[1]
        doc = jnp.zeros((length,), jnp.int32)
        pos = jnp.arange(length, dtype=jnp.int32)
        return jax.lax.map(lambda xr: layer(cfg, w, xr, doc, pos, mm, blocks), x)

    @jax.jit
    def head(scale, w, x, at):  # at (N, T): the positions to read
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        xs = rms_norm(xs, scale, cfg["rms_norm_eps"])
        return jax.lax.map(lambda xr: mm(xr, w), xs)

    return embed, one_layer, head


def serve_logits(cfg: dict, get_leaf, seqs, at, *, mm="highest", blocks=4):
    """Logits (N, T, vocab) of one full forward pass over ``seqs`` (N, L)
    int32 (each row one sequence from position 0, padding after its end),
    read at positions ``at`` (N, T). ``get_leaf(name)`` returns a float32
    weight; layers are fetched one at a time so that only one is alive.
    """
    import json

    embed, one_layer, head = _serve_programs(json.dumps(cfg, sort_keys=True), mm, blocks)
    x = embed(get_leaf("embed"), jnp.asarray(seqs))
    names = ("attn_norm/scale", "attn/q_proj/kernel", "attn/k_proj/kernel",
             "attn/v_proj/kernel", "attn/o_proj/kernel", "mlp_norm/scale",
             "mlp/gate_proj/kernel", "mlp/up_proj/kernel", "mlp/down_proj/kernel")
    for n in range(cfg["num_hidden_layers"]):
        x = one_layer({k: get_leaf(f"layer{n}/{k}") for k in names}, x)
    return head(get_leaf("final_norm/scale"), get_leaf("lm_head"), x, jnp.asarray(at))
