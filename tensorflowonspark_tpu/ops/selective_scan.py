"""The Mamba-1 selective scan (arXiv:2312.00752): the scan over a prompt
and the one-position step for decode, each with the state it carries
between calls.

The recurrence, per channel ``c`` and state index ``n``, state ``S`` of
shape (N, c)::

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n] + D[c] u_t[c]

``dt`` is the step AFTER its softplus. The decay differs for every channel
and every state index, so ``ops/ssd.py``'s chunked form (one scalar decay a
head) does not apply: the prompt is scanned a position at a time. The
state lies (N, c), channels minor: a minor dimension of N = 16 would be
padded to a TPU tile's 128 lanes, eight times its bytes.

State and decays are float32 whatever the inputs' dtype. On one TPU
without an ambient mesh the scan is a Pallas kernel (``name=
"selective_scan"``): a grid over rows, blocks of channels and chunks of
positions, the positions of a chunk in order inside it, the state of its
channels resident in VMEM across the chunks. Elsewhere, and in the tests
as its reference, the same recurrence is a ``lax.scan``. The step is plain
``jax.numpy``, which XLA fuses into one pass over the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook: run the scan kernel in the Pallas interpreter (on a CPU).
INTERPRET = False
# Channels a grid step holds: a (16, 256) float32 state is four vector
# registers, the decays of a position as many again.
_CHANNELS = 256
# Positions a grid step scans, unrolled: one lane tile of B and C.
_POSITIONS = 128


def _pallas_scan(c: int) -> bool:
    """Whether ``selective_scan`` takes the kernel in this process: on one
    TPU without an ambient mesh (GSPMD cannot partition a
    ``pallas_call``), at channel counts the kernel's block divides."""
    from tensorflowonspark_tpu.ops import attention
    from tensorflowonspark_tpu.parallel.context import current_mesh

    return (
        attention._on_tpu() and current_mesh() is None
        and (c <= _CHANNELS or c % _CHANNELS == 0)
    )


def selective_scan_step(S, u, dt, A, B, C, D):
    """One position for every row, from the old state.

    ``S`` (rows, N, c) float32; ``u``, ``dt`` (rows, c); ``A`` (N, c);
    ``B``, ``C`` (rows, N); ``D`` (c,). Returns ``(y, S)`` with ``y``
    (rows, c) float32. ``dt = 0`` leaves the state exactly as it was.
    """
    f32 = jnp.float32
    u, dt = u.astype(f32), dt.astype(f32)
    new = (
        jnp.exp(dt[:, None, :] * A.astype(f32)) * S
        + (dt * u)[:, None, :] * B.astype(f32)[:, :, None]
    )
    y = jnp.sum(new * C.astype(f32)[:, :, None], axis=1) + D.astype(f32) * u
    return y, new


def selective_scan_xla(u, dt, A, B, C, D, S0):
    """:func:`selective_scan` as a ``lax.scan`` of :func:`selective_scan_step`
    over positions (``dt`` already zero where invalid)."""

    def step(S, inp):
        ut, dtt, bt, ct = inp
        y, S = selective_scan_step(S, ut, dtt, A, bt, ct, D)
        return S, y

    final, y = jax.lax.scan(
        step, S0, tuple(t.swapaxes(0, 1) for t in (u, dt, B, C))
    )
    return y.swapaxes(0, 1), final


def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                 y_ref, s_ref, state, *, steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = s0_ref[0]

    A, D = a_ref[...], d_ref[...]  # (N, cb), (1, cb)
    Bt, Ct = b_ref[0], c_ref[0]  # (N, steps): a position a lane
    s = state[...]
    for g in range(steps // 8):
        u8 = u_ref[0, 8 * g : 8 * g + 8, :].astype(jnp.float32)
        d8 = dt_ref[0, 8 * g : 8 * g + 8, :]
        for r in range(8):
            t = 8 * g + r
            ut, dtt = u8[r : r + 1], d8[r : r + 1]  # (1, cb)
            s = jnp.exp(dtt * A) * s + (dtt * ut) * Bt[:, t : t + 1]
            y_ref[0, t : t + 1, :] = (
                jnp.sum(s * Ct[:, t : t + 1], axis=0, keepdims=True) + D * ut
            )
    state[...] = s

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _final():
        s_ref[0] = s


def selective_scan_pallas(u, dt, A, B, C, D, S0):
    """:func:`selective_scan` as one kernel (``dt`` already zero where
    invalid). The positions are padded to whole chunks with ``dt = 0``,
    through which the state passes unchanged."""
    f32 = jnp.float32
    rows, L, c = u.shape
    N = A.shape[0]
    T = _POSITIONS if L > _POSITIONS else -(-L // 8) * 8
    pad = -L % T
    if pad:
        u, dt, B, C = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (u, dt, B, C)
        )
    Lp, cb = L + pad, min(_CHANNELS, c)
    y, final = pl.pallas_call(
        functools.partial(_scan_kernel, steps=T),
        grid=(rows, c // cb, Lp // T),
        in_specs=[
            pl.BlockSpec((1, T, cb), lambda r, j, l: (r, l, j)),
            pl.BlockSpec((1, T, cb), lambda r, j, l: (r, l, j)),
            pl.BlockSpec((1, N, T), lambda r, j, l: (r, 0, l)),
            pl.BlockSpec((1, N, T), lambda r, j, l: (r, 0, l)),
            pl.BlockSpec((N, cb), lambda r, j, l: (0, j)),
            pl.BlockSpec((1, cb), lambda r, j, l: (0, j)),
            pl.BlockSpec((1, N, cb), lambda r, j, l: (r, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, T, cb), lambda r, j, l: (r, l, j)),
            pl.BlockSpec((1, N, cb), lambda r, j, l: (r, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, Lp, c), f32),
            jax.ShapeDtypeStruct((rows, N, c), f32),
        ],
        scratch_shapes=[pltpu.VMEM((N, cb), f32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=INTERPRET,
        name="selective_scan",
    )(
        u, dt.astype(f32), B.astype(f32).swapaxes(1, 2),
        C.astype(f32).swapaxes(1, 2), A.astype(f32),
        D.astype(f32)[None, :], S0,
    )
    return y[:, :L], final


def selective_scan(u, dt, A, B, C, D, state=None, valid=None):
    """The scan over a sequence, from ``state``.

    ``u``, ``dt`` (rows, L, c); ``A`` (N, c); ``B``, ``C`` (rows, L, N);
    ``D`` (c,); ``state`` (rows, N, c) float32 or None (zeros); ``valid``
    (rows, L) bool or None. Returns ``(y, final_state)``: ``y`` (rows, L,
    c) float32 and the state after the last position. A position whose
    ``valid`` is false leaves the state as it was (``dt`` is zero there);
    its output is don't-care. The Pallas kernel where the process takes
    it (:func:`_pallas_scan`), else the ``lax.scan``.
    """
    rows, _, c = u.shape
    dt = dt.astype(jnp.float32)
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    S0 = (
        jnp.zeros((rows, A.shape[0], c), jnp.float32)
        if state is None else state.astype(jnp.float32)
    )
    scan = selective_scan_pallas if _pallas_scan(c) else selective_scan_xla
    return scan(u, dt, A, B, C, D, S0)
