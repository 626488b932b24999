"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692): the chunked form for prefill and the
one-position step for decode, each with the state it carries between
calls.

The recurrence, per head, state ``S`` of shape (d_k, d_v)::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``a_t`` in (0, 1)^{d_k} decays every key channel on its own, ``b_t`` in
[0, 2] (above 1 the eigenvalue of ``I - b k k^T`` along a unit ``k`` is
negative). Unlike the rank-one addition of ``ops/ssd.py``, the state is
*corrected* by what it already holds: with ``p = S_{t-1}^T (a_t * k_t)``
and ``u_t = b_t (v_t - p)``::

    S_t = Diag(a_t) S_{t-1} + k_t u_t^T
    o_t = S_{t-1}^T (a_t * q_t) + (k_t . q_t) u_t

so a step must reduce over the whole old state before it can rewrite it.
Its own file because nothing of ``ops/ssd.py`` but the convolution is
shared: the decay is a vector a head, not a scalar, the chunked form
needs a triangular solve a chunk, and the step is a kernel.

State and decays are float32 whatever the inputs' dtype, and the einsums
that meet them ask for ``Precision.HIGHEST`` (on a TPU the default would
round them to bfloat16 inside the MXU), as ``ops/ssd.py`` does. A decay
only ever appears as ``exp`` of a *difference* of cumulative logs, taken
where the difference is at most zero: never a quotient of cumulative
products, which overflows once a channel has decayed far.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST

# Test hook: run the step kernel in the Pallas interpreter (on a CPU).
INTERPRET = False
# Heads a grid step of the step kernel holds: 32 tiles of (128, 128)
# float32 are 2 MiB in and 2 MiB out, double-buffered 8 MiB of the 16 MiB
# a kernel may use; and 4 key-side vectors x 32 heads make one (128, 128)
# tile to transpose.
_STEP_HEADS = 32
# Positions of a sub-block of the chunked form's chunk: only the sub-blocks
# on a chunk's diagonal form the (t, s, channel) decays, the blocks below
# them are products on the MXU. 16 from the chip at chunk 32 (PERF.md §6,
# PR 36): 3.18 ms a 1024-wide row and layer where 8 takes 3.29.
_SUB = 16


def _pallas_step(S) -> bool:
    """Whether ``kda_step`` takes the Pallas kernel in this process: on
    one TPU without an ambient mesh (GSPMD cannot partition a
    ``pallas_call``), as ``ops.decode_attention.cache_block_k`` asks, at
    the shapes the kernel is written for."""
    from tensorflowonspark_tpu.ops import attention
    from tensorflowonspark_tpu.parallel.context import current_mesh

    _, h, dk, dv = S.shape
    return (
        attention._on_tpu() and current_mesh() is None
        and dk == 128 and dv % 128 == 0 and h % _STEP_HEADS == 0
    )


def kda_step_xla(S, q, k, v, alpha, beta):
    """:func:`kda_step` as plain ``jax.numpy``: two passes over the old
    state (the reduction, then the rewrite) and one write."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha, beta = alpha.astype(f32), beta.astype(f32)
    p = jnp.sum(S * (alpha * k)[..., None], axis=-2)  # (rows, h, dv)
    o = jnp.sum(S * (alpha * q)[..., None], axis=-2)
    u = beta[..., None] * (v - p)
    o = o + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, alpha[..., None] * S + k[..., None] * u[..., None, :]


def _step_kernel(x_ref, y_ref, s_ref, o_ref, out_ref, *, heads: int):
    # x: (4 * heads, dk) rows [a*k | a*q | a | k] a head; transposed, a
    # head's vector is a column that broadcasts along the value lanes
    xt = x_ref[0, 0].T
    for h in range(heads):
        s = s_ref[0, h]  # (dk, dv)
        wk = xt[:, h : h + 1]
        wq = xt[:, heads + h : heads + h + 1]
        a = xt[:, 2 * heads + h : 2 * heads + h + 1]
        kk = xt[:, 3 * heads + h : 3 * heads + h + 1]
        p = jnp.sum(s * wk, axis=0, keepdims=True)  # (1, dv)
        o = jnp.sum(s * wq, axis=0, keepdims=True)
        # y: rows [b * v | b | k . q], each along the value lanes
        u = y_ref[0, 0, h : h + 1] - y_ref[0, 1, h : h + 1] * p
        o_ref[0, h : h + 1] = o + y_ref[0, 2, h : h + 1] * u
        out_ref[0, h] = s * a + kk * u


def kda_step_pallas(S, q, k, v, alpha, beta):
    """:func:`kda_step` as one pass: a grid over rows and blocks of
    heads, a head's (d_k, d_v) tile resident while it is reduced and
    rewritten, the new state written over the old."""
    f32 = jnp.float32
    rows, h, dk, dv = S.shape
    hb = _STEP_HEADS
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha, beta = alpha.astype(f32), beta.astype(f32)
    x = jnp.stack([alpha * k, alpha * q, alpha, k], axis=1)  # (rows, 4, h, dk)
    x = x.reshape(rows, 4, h // hb, hb, dk).transpose(0, 2, 1, 3, 4)
    x = x.reshape(rows, h // hb, 4 * hb, dk)
    ones = jnp.ones((1, 1, dv), f32)
    y = jnp.stack([
        beta[..., None] * v, beta[..., None] * ones,
        jnp.sum(k * q, axis=-1, keepdims=True) * ones,
    ], axis=1)  # (rows, 3, h, dv)
    o, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid=(rows, h // hb),
        in_specs=[
            pl.BlockSpec((1, 1, 4 * hb, dk), lambda r, j: (r, j, 0, 0)),
            pl.BlockSpec((1, 3, hb, dv), lambda r, j: (r, 0, j, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda r, j: (r, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, dv), lambda r, j: (r, j, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda r, j: (r, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h, dv), f32),
            jax.ShapeDtypeStruct(S.shape, f32),
        ],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=INTERPRET,
        name="kda_step",
    )(x, y, S.astype(f32))
    return o, new


def kda_step(S, q, k, v, alpha, beta):
    """One position for every row, from the old state alone.

    ``S`` (rows, h, d_k, d_v) float32; ``q``, ``k``, ``alpha`` (rows, h,
    d_k); ``v`` (rows, h, d_v); ``beta`` (rows, h). Returns ``(o, S)``
    with ``o`` (rows, h, d_v) float32. ``alpha = 1, beta = 0`` leaves the
    state exactly as it was.
    """
    step = kda_step_pallas if _pallas_step(S) else kda_step_xla
    return step(S, q, k, v, alpha, beta)


def _unit_lower_inverse(L):
    """``(I + L)^-1`` for strictly lower-triangular ``L`` (..., C, C), by
    forward substitution a row at a time (row ``t`` of the inverse is
    ``e_t - L[t] @ inverse``, the rows from ``t`` on still being zero):
    backward-stable whatever ``L`` holds, where the product form
    ``(I - L)(I + L^2)(I + L^4)...`` cancels catastrophically once
    ``b k.k`` is not small."""
    C = L.shape[-1]
    eye = jnp.eye(C, dtype=L.dtype)

    def row(t, T):
        new = eye[t] - jnp.einsum(
            "...s,...sj->...j", L[..., t, :], T, precision=_HI
        )
        return jax.lax.dynamic_update_index_in_dim(T, new, t, axis=-2)

    return jax.lax.fori_loop(0, C, row, jnp.zeros_like(L))


def _block_lower_inverse(L, sub):
    """:func:`_unit_lower_inverse` by sub-blocks of ``sub`` positions:
    the diagonal blocks by substitution (``sub`` steps for all of them at
    once), then the block rows in order, each by products with the rows
    above it: ``T_i,<r = -T_ii L_i,<r T_<r,<r``."""
    C = L.shape[-1]
    n = C // sub
    diag = _unit_lower_inverse(jnp.stack(
        [L[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub] for i in range(n)],
        axis=-3,
    ))  # (..., n, sub, sub)
    zeros = jnp.zeros(L.shape[:-2] + (sub, C - sub), L.dtype)
    T = jnp.concatenate([diag[..., 0, :, :], zeros], axis=-1)  # (..., r, C)
    for i in range(1, n):
        r = i * sub
        x = jnp.einsum(
            "...ts,...sj->...tj", L[..., r:r + sub, :r], T[..., :r],
            precision=_HI,
        )
        off = -jnp.einsum(
            "...ts,...sj->...tj", diag[..., i, :, :], x, precision=_HI
        )
        row = [off, diag[..., i, :, :], zeros[..., : C - r - sub]]
        T = jnp.concatenate([T, jnp.concatenate(row, axis=-1)], axis=-2)
    return T


def _scores(q, k, G, sub):
    """``A`` (k with k, strictly lower) and ``B`` (q with k, lower) of
    chunks ``(..., C, d_k)`` cut into sub-blocks of ``sub`` positions.

    For an earlier ``s`` and a later ``t`` whose sub-block starts at
    ``r``, ``exp(G_t - G_s) = exp(G_t - G_r) exp(G_r - G_s)``, both
    factors at most 1 (each underflows only where their product does).
    So a block row's scores against the sub-blocks before it are one
    product of its rows scaled by ``exp(G_t - G_r)`` with every earlier
    key scaled by ``exp(G_r - G_s)``; only the diagonal sub-blocks form
    the (t, s, channel) decays.
    """
    *lead, C, dk = q.shape
    n = C // sub
    qb, kb, Gb = (t.reshape(*lead, n, sub, dk) for t in (q, k, G))
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    seg = Gb[..., :, None, :] - Gb[..., None, :, :]  # (..., n, t, s, dk)
    ks = kb[..., None, :, :] * jnp.exp(jnp.where(tri[..., None], seg, -jnp.inf))
    A = jnp.sum(kb[..., :, None, :] * ks, axis=-1)  # (..., n, sub, sub)
    B = jnp.sum(qb[..., :, None, :] * ks, axis=-1)
    Gr = Gb[..., :1, :]  # (..., n, 1, dk): each sub-block's first position
    to_r = jnp.exp(Gb - Gr)
    before = jnp.tri(n, k=-1, dtype=bool)[..., None, None]  # sub-block j < i
    k_r = (kb[..., None, :, :, :] * jnp.exp(jnp.where(
        before, Gr[..., :, None, :, :] - Gb[..., None, :, :, :], -jnp.inf
    ))).reshape(*lead, n, C, dk)  # (..., i, s, dk): s before sub-block i
    in_diag = (jnp.arange(C) // sub == jnp.arange(n)[:, None])[:, None, :]

    def whole(diag, rows):
        off = jnp.einsum(
            "...itk,...isk->...its", rows * to_r, k_r, precision=_HI
        )  # (..., i, t, s)
        return jnp.where(in_diag, jnp.tile(diag, n), off).reshape(*lead, C, C)

    return whole(A, kb), whole(B, qb)


def kda_chunked(
    q, k, v, log_alpha, beta, *, chunk: int = 32, initial_state=None,
    valid=None,
):
    """The chunked form over a sequence, from ``initial_state``.

    ``q``, ``k``, ``log_alpha`` (rows, L, h, d_k), ``log_alpha <= 0``;
    ``v`` (rows, L, h, d_v); ``beta`` (rows, L, h); ``initial_state``
    (rows, h, d_k, d_v) or None (zeros); ``valid`` (rows, L) bool or
    None. Returns ``(o, final_state)``: ``o`` (rows, L, h, d_v) float32
    and the float32 state after the last position. Any ``L`` (it is
    padded to a multiple of ``chunk`` with invalid positions). A position
    whose ``valid`` is false leaves the state as it was (``log_alpha``
    and ``beta`` are zero there); its output is don't-care.

    With ``G_t`` the chunk's inclusive cumulative ``log_alpha`` and
    ``S_0`` the state at its start, the corrections ``u_t`` solve a unit
    lower-triangular system a chunk and head (the WY / UT transform)::

        A_ts = sum_i k_t[i] k_s[i] exp(G_t[i] - G_s[i])      s < t
        (I + Diag(b) A) U = Diag(b) (V - (K * exp G) S_0)
        o_t = S_0^T (q_t * exp G_t) + sum_{s<=t} B_ts u_s     B: q_t for k_t
        S_C = Diag(exp G_C) S_0 + sum_s (k_s * exp(G_C - G_s)) u_s^T

    ``A`` and ``B`` are made a chunk at a time by sub-blocks
    (:func:`_scores`), the inverse for every chunk at once by sub-blocks;
    the state then follows the chunks sequentially, five small products a
    chunk, reading ``q``, ``k``, ``v`` and ``G`` as they are stored.
    ``chunk`` 32 from the chip (PERF.md §6, PR 36): one row of 1024
    positions, 64 heads of 128 x 128, ms: 3.2 at 32, 3.5 at 64, 4.4 at
    128 (the form before the sub-blocks: 5.7, 7.6, 12.0).
    """
    rows, L, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    g, beta = log_alpha.astype(f32), beta.astype(f32)
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    C = int(chunk)
    pad = -L % C
    if pad:
        # log_alpha = 0, beta = 0 on the padding: the state passes through
        q, k, v, g, beta = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    nc = (L + pad) // C
    # (rows, nc, C, h, d) as stored, what the carry reads a chunk of
    q, k, v, g = (t.reshape(rows, nc, C, h, -1) for t in (q, k, v, g))
    G = jnp.cumsum(g, axis=2)
    # chunks first, then heads: what the scores and the solve take
    qh, kh, Gh = (t.transpose(1, 0, 3, 2, 4) for t in (q, k, G))
    beta = beta.reshape(rows, nc, C, h).transpose(1, 0, 3, 2)  # (nc, rows, h, C)
    sub = _SUB if C % _SUB == 0 else C
    # a chunk at a time: faster on the chip than all chunks at once, whose
    # copies into sub-block order cost more (PERF.md §6, PR 36)
    A, B = jax.lax.map(lambda a: _scores(*a, sub), (qh, kh, Gh))
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), A, 0.0)
    T = _block_lower_inverse(beta[..., None] * A, sub)  # (nc, rows, h, C, C)
    S0 = (
        jnp.zeros((rows, h, dk, dv), f32)
        if initial_state is None
        else initial_state.astype(f32)
    )

    def carry(S, inp):
        ti, b, bc, qc, kc, vc, Gc = inp  # the last four (rows, C, h, d)
        end = Gc[:, -1:]
        # the corrections: U = T (Diag(b) (V - (K * exp G) S))
        ku = kc * jnp.exp(Gc)
        p = jnp.einsum("bthk,bhkv->bhtv", ku, S, precision=_HI)
        u = jnp.einsum(
            "bhts,bhsv->bhtv", ti, bc[..., None] * (jnp.swapaxes(vc, 1, 2) - p),
            precision=_HI,
        )
        o = jnp.einsum(
            "bthk,bhkv->bthv", qc * jnp.exp(Gc), S, precision=_HI
        ) + jnp.einsum("bhts,bhsv->bthv", b, u, precision=_HI)
        S = jnp.exp(end[:, 0, :, :, None]) * S + jnp.einsum(
            "bthk,bhtv->bhkv", kc * jnp.exp(end - Gc), u, precision=_HI
        )
        return S, o

    final, o = jax.lax.scan(
        carry, S0,
        (T, B, beta) + tuple(t.swapaxes(0, 1) for t in (q, k, v, G)),
    )
    o = o.swapaxes(0, 1).reshape(rows, nc * C, h, dv)
    return o[:, :L], final
