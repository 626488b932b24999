"""Selective state-space (Mamba-2 / SSD) ops: the chunked scan for
prefill, the one-step recurrence for decode, and the causal depthwise
convolution that feeds both, each with the state it carries between
calls.

The recurrence, per head ``i`` (of ``h``, ``p`` channels each) using the
``B``/``C`` of group ``i // (h / g)``, state ``S`` of shape (h, p, N)::

    a_t = exp(dt_t * A)                       A < 0, one a head
    S_t = a_t * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t . C_t + D * x_t

``dt`` is the step size AFTER its softplus. Decays and state are float32
whatever the inputs' dtype: a state carried over a thousand steps is no
place for bfloat16. Plain ``jax.numpy``/``lax`` that XLA compiles; the
einsums ask for ``Precision.HIGHEST`` (on a TPU the default would round
the float32 decays to bfloat16 inside the MXU), which costs little: the
scan is under 1 % of a layer's FLOPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _heads(t, h: int):
    """(..., g, N) group tensors repeated to (..., h, N) heads."""
    g = t.shape[-2]
    return t if g == h else jnp.repeat(t, h // g, axis=-2)


def ssm_step(S, x, dt, A, B, C, D):
    """One position for every row.

    ``S`` (rows, h, p, N) float32; ``x`` (rows, h, p); ``dt`` (rows, h);
    ``A``, ``D`` (h,); ``B``, ``C`` (rows, g, N). Returns ``(y, S)`` with
    ``y`` (rows, h, p) float32. Both results are written in terms of the
    OLD state, so that one pass over it can produce them together:
    ``y = a * (S . C) + dt * x * (B . C) + D * x``.
    """
    h = x.shape[-2]
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bh = _heads(B.astype(jnp.float32), h)
    Ch = _heads(C.astype(jnp.float32), h)
    a = jnp.exp(dt * A.astype(jnp.float32))  # (rows, h)
    dtx = dt[..., None] * x  # (rows, h, p)
    new = a[..., None, None] * S + dtx[..., None] * Bh[:, :, None, :]
    y = (
        a[..., None] * jnp.sum(S * Ch[:, :, None, :], axis=-1)
        + dtx * jnp.sum(Bh * Ch, axis=-1)[..., None]
        + D.astype(jnp.float32)[:, None] * x
    )
    return y, new


def ssd_scan(x, dt, A, B, C, D, *, chunk: int, initial_state=None, valid=None):
    """The chunked scan over a sequence, from ``initial_state``.

    ``x`` (rows, L, h, p); ``dt`` (rows, L, h); ``B``, ``C`` (rows, L, g,
    N); ``A``, ``D`` (h,); ``initial_state`` (rows, h, p, N) or None
    (zeros); ``valid`` (rows, L) bool or None. Returns ``(y, final_state)``:
    ``y`` (rows, L, h, p) float32 and the float32 state after the last
    position. Any ``L`` (it is padded to a multiple of ``chunk`` with
    invalid positions). A position whose ``valid`` is false leaves the
    state as it was (``dt`` is zero there: ``a = 1``, no input); its
    output is don't-care.

    Inside a chunk of Q positions the outputs are a masked quadratic form
    (``exp(segment sums of dt*A)`` times ``C_t . B_s``); between chunks
    the state follows the recurrence once a chunk, sequentially.
    """
    rows, L, h, p = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    B, C = B.astype(f32), C.astype(f32)
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    Q = int(chunk)
    pad = -L % Q
    if pad:
        # dt = 0 on the padding: the state passes through it unchanged
        x, dt, B, C = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (x, dt, B, C)
        )
    nc = (L + pad) // Q
    x = x.reshape(rows, nc, Q, h, p)
    dt = dt.reshape(rows, nc, Q, h)
    g = B.shape[-2]
    Bg, Cg = B.reshape(rows, nc, Q, g, N), C.reshape(rows, nc, Q, g, N)
    Bh, Ch = _heads(Bg, h), _heads(Cg, h)

    dA = dt * A.astype(f32)  # (rows, nc, Q, h), <= 0
    cum = jnp.cumsum(dA, axis=2)  # inclusive: log decay from the chunk's start
    # -- inside a chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t.B_s) x_s
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (rows, nc, t, s, h)
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", Cg, Bg, precision=_HI)
    w = jnp.repeat(cb, h // g, axis=-1) * decay * dt[:, :, None, :, :]
    y = jnp.einsum("bctsh,bcshp->bcthp", w, x, precision=_HI)
    # -- what each chunk adds to the state by its end
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (rows, nc, Q, h)
    chunk_state = jnp.einsum(
        "bcsh,bcshp,bcshn->bchpn", to_end * dt, x, Bh, precision=_HI
    )
    # -- between chunks: the recurrence, once a chunk
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (rows, nc, h)
    S0 = (
        jnp.zeros((rows, h, p, N), f32)
        if initial_state is None
        else initial_state.astype(f32)
    )

    def carry(S, inp):
        a, add = inp
        return a[..., None, None] * S + add, S  # emits the state at the chunk's START

    final, starts = jax.lax.scan(
        carry,
        S0,
        (chunk_decay.transpose(1, 0, 2), chunk_state.transpose(1, 0, 2, 3, 4)),
    )
    starts = starts.transpose(1, 0, 2, 3, 4)  # (rows, nc, h, p, N)
    y = y + jnp.einsum(
        "bcthn,bchpn->bcthp", Ch * jnp.exp(cum)[..., None], starts, precision=_HI
    )
    y = y + D.astype(f32)[:, None] * x
    return y.reshape(rows, nc * Q, h, p)[:, :L], final


def causal_conv1d(xBC, w, b, window=None, valid=None):
    """Causal depthwise convolution over the carried window followed by
    the new tokens.

    ``xBC`` (rows, L, c); ``w`` (k, c), tap ``k - 1`` on the current
    token; ``b`` (c,); ``window`` (rows, k - 1, c), the last ``k - 1``
    inputs before this call (None: zeros); ``valid`` (rows, L) bool or
    None. Returns ``(out, window)``: ``out`` (rows, L, c) in ``xBC``'s
    dtype BEFORE the activation, and the new window: the last ``k - 1``
    **valid** inputs (reaching back into the old window where fewer are
    valid). An invalid position is skipped: the valid ones see the
    sequence as if it were not there; its own output is don't-care.
    """
    rows, L, c = xBC.shape
    k = w.shape[0]
    if window is None:
        window = jnp.zeros((rows, k - 1, c), xBC.dtype)
    if valid is None:
        seq, rank = xBC, None
    else:
        # the valid tokens first, in order: position t goes to rank[t]
        order = jnp.argsort(~valid, axis=1, stable=True)
        seq = jnp.take_along_axis(xBC, order[..., None], axis=1)
        rank = jnp.cumsum(valid, axis=1) - 1
    ext = jnp.concatenate([window.astype(xBC.dtype), seq], axis=1)
    ext32 = ext.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    out = b.astype(jnp.float32) + sum(
        ext32[:, j : j + L] * w32[j] for j in range(k)
    )
    if rank is None:
        return out.astype(xBC.dtype), ext[:, L:]
    out = jnp.take_along_axis(out, jnp.maximum(rank, 0)[..., None], axis=1)
    n = jnp.sum(valid, axis=1)  # the new window starts n into ext
    take = n[:, None] + jnp.arange(k - 1)[None, :]
    return out.astype(xBC.dtype), jnp.take_along_axis(ext, take[..., None], axis=1)
