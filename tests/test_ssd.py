"""ops/ssd.py: the chunked scan, the one-step recurrence and the carried
convolution against a time-stepped recurrence written out in numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops.ssd import causal_conv1d, ssd_scan, ssm_step

H, P, G, N, CHUNK = 4, 8, 2, 16, 8


def _inputs(L, rows=2, seed=0):
    r = np.random.default_rng(seed)
    return dict(
        x=r.normal(size=(rows, L, H, P)).astype(np.float32),
        dt=np.logaddexp(0, r.normal(size=(rows, L, H)) - 1).astype(np.float32),
        A=-np.exp(r.uniform(0, 1.5, size=(H,))).astype(np.float32),
        B=r.normal(size=(rows, L, G, N)).astype(np.float32),
        C=r.normal(size=(rows, L, G, N)).astype(np.float32),
        D=r.normal(size=(H,)).astype(np.float32),
    )


def _stepped(x, dt, A, B, C, D, S0=None, valid=None):
    """The recurrence one position at a time, in float64."""
    rows, L = x.shape[:2]
    S = np.zeros((rows, H, P, N)) if S0 is None else np.array(S0, np.float64)
    rep = H // G
    y = np.zeros((rows, L, H, P))
    for t in range(L):
        for r in range(rows):
            if valid is not None and not valid[r, t]:
                continue
            for i in range(H):
                a = np.exp(dt[r, t, i] * A[i])
                S[r, i] = a * S[r, i] + dt[r, t, i] * np.outer(x[r, t, i], B[r, t, i // rep])
                y[r, t, i] = S[r, i] @ C[r, t, i // rep] + D[i] * x[r, t, i]
    return y, S


@pytest.mark.parametrize("L", [1, CHUNK - 1, CHUNK, 2 * CHUNK + 3])
def test_chunked_scan_is_the_stepped_recurrence(L):
    inp = _inputs(L)
    y, S = ssd_scan(**inp, chunk=CHUNK)
    y_ref, S_ref = _stepped(**inp)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S, S_ref, rtol=1e-4, atol=1e-4)


def test_scan_continues_from_an_initial_state():
    inp = _inputs(2 * CHUNK + 3, seed=1)
    cut = CHUNK + 2
    head = {k: (v[:, :cut] if v.ndim > 1 else v) for k, v in inp.items()}
    tail = {k: (v[:, cut:] if v.ndim > 1 else v) for k, v in inp.items()}
    _, S1 = ssd_scan(**head, chunk=CHUNK)
    y2, S2 = ssd_scan(**tail, chunk=CHUNK, initial_state=S1)
    y_ref, S_ref = _stepped(**inp)
    np.testing.assert_allclose(y2, y_ref[:, cut:], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S2, S_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mask", ["tail", "head"])
def test_masked_positions_leave_state_and_window_alone(mask):
    """A masked tail (padding after a prompt) and a masked head (the
    overlap of a chunk shifted back): the state and the window equal
    those of the valid tokens alone, and so do the valid outputs."""
    L, lo, hi = 2 * CHUNK + 3, 5, 2 * CHUNK - 2
    inp = _inputs(L, seed=2)
    valid = np.zeros((2, L), bool)
    if mask == "tail":
        valid[0, :hi], valid[1, : hi - 4] = True, True
    else:
        valid[0, lo:], valid[1, lo + 3 :] = True, True
    S0 = np.random.default_rng(3).normal(size=(2, H, P, N)).astype(np.float32)
    y, S = ssd_scan(**inp, chunk=CHUNK, initial_state=S0, valid=jnp.asarray(valid))
    y_ref, S_ref = _stepped(**inp, S0=S0, valid=valid)
    np.testing.assert_allclose(S, S_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(y)[valid], y_ref[valid], rtol=1e-4, atol=1e-4
    )
    # the convolution: per row, the valid tokens alone after the window
    r = np.random.default_rng(4)
    c, k = 6, 4
    xs = r.normal(size=(2, L, c)).astype(np.float32)
    w = r.normal(size=(k, c)).astype(np.float32)
    b = r.normal(size=(c,)).astype(np.float32)
    win = r.normal(size=(2, k - 1, c)).astype(np.float32)
    out, new = causal_conv1d(xs, w, b, win, jnp.asarray(valid))
    for row in range(2):
        alone = xs[row][valid[row]][None]
        o1, n1 = causal_conv1d(alone, w, b, win[row : row + 1])
        np.testing.assert_allclose(np.asarray(out)[row][valid[row]], o1[0], atol=1e-5)
        np.testing.assert_allclose(new[row], n1[0], atol=1e-6)


def test_conv_is_the_sum_over_shifted_copies_and_carries_its_window():
    r = np.random.default_rng(5)
    L, c, k = 11, 6, 4
    xs = r.normal(size=(1, L, c)).astype(np.float32)
    w = r.normal(size=(k, c)).astype(np.float32)
    b = r.normal(size=(c,)).astype(np.float32)
    full, win = causal_conv1d(xs, w, b)
    padded = np.concatenate([np.zeros((1, k - 1, c), np.float32), xs], axis=1)
    want = b + sum(padded[:, j : j + L] * w[j] for j in range(k))
    np.testing.assert_allclose(full, want, atol=1e-5)
    np.testing.assert_allclose(win, xs[:, -(k - 1) :])
    # token by token through the carried window
    window, outs = None, []
    for t in range(L):
        o, window = causal_conv1d(xs[:, t : t + 1], w, b, window)
        outs.append(o)
    np.testing.assert_allclose(np.concatenate(outs, axis=1), want, atol=1e-5)
    # fewer valid tokens than the window holds: it reaches into the old one
    valid = jnp.asarray([[True] + [False] * (L - 1)])
    _, short = causal_conv1d(xs, w, b, win, valid)
    np.testing.assert_allclose(short[0], np.concatenate([win[0, 1:], xs[0, :1]]))


def test_step_is_a_scan_of_length_one_with_groups_fewer_than_heads():
    assert G != H
    inp = _inputs(1, rows=3, seed=6)
    S0 = np.random.default_rng(7).normal(size=(3, H, P, N)).astype(np.float32)
    y_scan, S_scan = ssd_scan(**inp, chunk=CHUNK, initial_state=S0)
    y, S = jax.jit(ssm_step)(
        S0, inp["x"][:, 0], inp["dt"][:, 0], inp["A"], inp["B"][:, 0],
        inp["C"][:, 0], inp["D"],
    )
    np.testing.assert_allclose(y, y_scan[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S, S_scan, rtol=1e-5, atol=1e-5)
    y_ref, S_ref = _stepped(**inp, S0=S0)
    np.testing.assert_allclose(y, y_ref[:, 0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S, S_ref, rtol=1e-4, atol=1e-4)
