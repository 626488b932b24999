"""ops/kda.py: the chunked gated delta rule and its one-position step
against the recurrence written out a position at a time, float32 on the
CPU; and the convolution it is fed by, carried against uncarried."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import kda
from tensorflowonspark_tpu.ops.ssd import causal_conv1d


def draws(seed, rows=2, L=37, h=3, dk=8, dv=6, strong=False):
    """q, k unit vectors a head (q scaled as the model scales it), decays
    spread over (0.3, 1), beta in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, L, h, dk))) * dk**-0.5
    k = unit(jax.random.normal(ks[1], (rows, L, h, dk)))
    v = jax.random.normal(ks[2], (rows, L, h, dv))
    lo = -6.0 if strong else -1.2
    g = jax.random.uniform(ks[3], (rows, L, h, dk), minval=lo, maxval=-1e-3)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (rows, L, h)))
    S0 = jax.random.normal(ks[5], (rows, h, dk, dv))
    return q, k, v, g, beta, S0


def recurrence(q, k, v, g, beta, S0=None, valid=None):
    """S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T, o_t = S_t^T q_t: the
    definition, a position at a time in float64 numpy."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    rows, L, h, dk = q.shape
    dv = v.shape[-1]
    S = np.zeros((rows, h, dk, dv)) if S0 is None else np.asarray(S0, np.float64).copy()
    out = np.zeros((rows, L, h, dv))
    eye = np.eye(dk)
    for r in range(rows):
        for t in range(L):
            if valid is not None and not valid[r, t]:
                continue
            for i in range(h):
                kk, b = k[r, t, i], beta[r, t, i]
                S[r, i] = (eye - b * np.outer(kk, kk)) @ (
                    np.exp(g[r, t, i])[:, None] * S[r, i]
                ) + b * np.outer(kk, v[r, t, i])
                out[r, t, i] = S[r, i].T @ q[r, t, i]
    return out, S


def _holes(L, sub):
    """Invalid positions on the first and the last position of sub-blocks
    (the second and the third), on a later one's first and at the end."""
    valid = np.ones((2, L), bool)
    valid[0, [sub, 2 * sub - 1, 3 * sub - 1]] = False
    valid[1, [4 * sub, L - 1]] = False
    return valid


@pytest.mark.parametrize("L, chunk, holes", [
    (37, 8, False), (16, 8, False), (5, 8, False), (64, 16, False),
    (33, 64, False),
    (70, 32, False), (96, 32, False),  # chunks of several sub-blocks
    (40, 12, False),  # a chunk no sub-block size divides: one sub-block
    (19, 4, False),  # a chunk smaller than a sub-block
    (70, 32, True),  # holes on sub-blocks' first and last positions
])
def test_chunked_equals_recurrence(L, chunk, holes):
    """Across chunk and sub-block boundaries, for a width that is no
    multiple of the chunk, for one shorter than it, and with invalid
    positions where a sub-block's decays are split."""
    q, k, v, g, beta, _ = draws(L, L=L)
    valid = _holes(L, kda._SUB) if holes else None
    want_o, want_S = recurrence(q, k, v, g, beta, valid=valid)
    o, S = jax.jit(lambda *a: kda.kda_chunked(*a, chunk=chunk, valid=(
        None if valid is None else jnp.asarray(valid)
    )))(q, k, v, g, beta)
    keep = np.ones(q.shape[:2], bool) if valid is None else valid
    np.testing.assert_allclose(np.asarray(o)[keep], want_o[keep], atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


def _shapes(jaxpr):
    """Every intermediate's shape in ``jaxpr`` and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(getattr(var.aval, "shape", ()))
        for param in eqn.params.values():
            for inner in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _shapes(inner)


def test_only_the_diagonal_sub_blocks_form_the_per_channel_decays():
    """At chunk 32 no intermediate holds a (t, s, channel) tensor over the
    whole chunk; the diagonal sub-blocks' (sub, sub, channel) one is
    there."""
    dk, sub = 12, kda._SUB
    assert 32 % sub == 0 and sub < 32
    q, k, v, g, beta, _ = draws(10, rows=1, L=64, h=2, dk=dk)
    jaxpr = jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, chunk=32))(
        q, k, v, g, beta
    )
    tails = {shape[-3:] for shape in _shapes(jaxpr.jaxpr)}
    assert (32, 32, dk) not in tails
    assert (sub, sub, dk) in tails


def test_chunked_from_an_initial_state():
    q, k, v, g, beta, S0 = draws(1)
    want_o, want_S = recurrence(q, k, v, g, beta, S0)
    o, S = kda.kda_chunked(q, k, v, g, beta, chunk=8, initial_state=S0)
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5)


def test_chunked_in_two_calls_equals_one():
    """The state handed from a call to the next is the whole of what the
    second needs of the first."""
    q, k, v, g, beta, S0 = draws(2)
    o, S = kda.kda_chunked(q, k, v, g, beta, chunk=8, initial_state=S0)
    cut = 13
    o1, S1 = kda.kda_chunked(
        q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut], beta[:, :cut],
        chunk=8, initial_state=S0,
    )
    o2, S2 = kda.kda_chunked(
        q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:], beta[:, cut:],
        chunk=8, initial_state=S1,
    )
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=5e-5)
    np.testing.assert_allclose(S2, S, atol=5e-5)


def test_valid_holes_leave_the_state_alone():
    """Invalid positions in the middle, at a chunk's edge and after the
    end: the valid ones see the sequence as if they were not there."""
    q, k, v, g, beta, S0 = draws(3)
    valid = np.ones(q.shape[:2], bool)
    valid[0, [3, 7, 8, 20]] = False
    valid[1, 25:] = False
    want_o, want_S = recurrence(q, k, v, g, beta, S0, valid)
    o, S = kda.kda_chunked(
        q, k, v, g, beta, chunk=8, initial_state=S0, valid=jnp.asarray(valid)
    )
    np.testing.assert_allclose(np.asarray(o)[valid], want_o[valid], atol=5e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5)


def test_all_invalid_returns_the_state_bit_for_bit():
    q, k, v, g, beta, S0 = draws(4, L=9)
    _, S = kda.kda_chunked(
        q, k, v, g, beta, chunk=8, initial_state=S0,
        valid=jnp.zeros(q.shape[:2], bool),
    )
    np.testing.assert_array_equal(S, S0)


@pytest.mark.parametrize("chunk", [64, 32])
def test_strong_decay_neither_overflows_nor_loses_the_answer(chunk):
    """Cumulative log-decays of a chunk reach -380 here: a quotient of
    cumulative products would be inf / 0; differences are not. Across
    sub-blocks the two factors of a decay underflow where it does."""
    q, k, v, g, beta, S0 = draws(5, L=64, strong=True)
    want_o, want_S = recurrence(q, k, v, g, beta, S0)
    o, S = kda.kda_chunked(q, k, v, g, beta, chunk=chunk, initial_state=S0)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5)


@pytest.mark.parametrize("step", [kda.kda_step, kda.kda_step_xla])
def test_step_repeated_equals_chunked(step):
    q, k, v, g, beta, S0 = draws(6, L=19)
    want_o, want_S = kda.kda_chunked(q, k, v, g, beta, chunk=8, initial_state=S0)
    S, outs = S0, []
    for t in range(q.shape[1]):
        o, S = step(S, q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t])
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=5e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5)


def test_step_with_alpha_one_beta_zero_is_the_identity():
    q, k, v, _, _, S0 = draws(7, L=1)
    one = jnp.ones_like(k[:, 0])
    _, S = kda.kda_step(S0, q[:, 0], k[:, 0], v[:, 0], one, jnp.zeros(k.shape[:1] + k.shape[2:3]))
    np.testing.assert_array_equal(S, S0)


def test_step_kernel_in_the_interpreter_equals_the_xla_form(monkeypatch):
    """The Pallas step at the shapes it is written for (d_k 128, a block
    of 32 heads), interpreted on the CPU."""
    monkeypatch.setattr(kda, "INTERPRET", True)
    q, k, v, g, beta, S0 = draws(8, rows=2, L=1, h=32, dk=128, dv=128)
    args = (S0, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
    want_o, want_S = kda.kda_step_xla(*args)
    o, S = kda.kda_step_pallas(*args)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=1e-5)


def test_negative_eigenvalues_occur_in_these_draws():
    """beta above 1 is what ``kda_allow_neg_eigval`` allows: the draws
    the other tests use hold such positions, and there the transition
    ``I - b k k^T`` has the eigenvalue ``1 - b |k|^2 < 0``."""
    _, k, _, _, beta, _ = draws(0)
    over = np.asarray(beta) > 1.0
    assert 0.2 < over.mean() < 0.8
    r, t, i = np.argwhere(over)[0]
    kk = np.asarray(k[r, t, i], np.float64)
    eig = np.linalg.eigvalsh(np.eye(kk.size) - float(beta[r, t, i]) * np.outer(kk, kk))
    assert eig.min() < 0 and eig.min() == pytest.approx(1 - float(beta[r, t, i]), abs=1e-6)


def test_carried_convolution_equals_the_uncarried_one():
    """Width 4, no bias worth the name: a sequence convolved in pieces
    through the carried window, one with invalid positions, equals the
    whole sequence convolved at once."""
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    x = jax.random.normal(ks[0], (2, 23, 12))
    w = jax.random.normal(ks[1], (4, 12))
    b = jnp.zeros((12,))
    want, _ = causal_conv1d(x, w, b)
    window, outs = None, []
    for a, z in ((0, 5), (5, 6), (6, 17), (17, 23)):
        o, window = causal_conv1d(x[:, a:z], w, b, window)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=1e-5)
    # padding inside a piece is skipped and does not enter the window
    padded = jnp.concatenate([x[:, :9], jnp.full((2, 3, 12), 7.0), x[:, 9:]], 1)
    valid = jnp.asarray([True] * 9 + [False] * 3 + [True] * 14)[None].repeat(2, 0)
    o, win = causal_conv1d(padded, w, b, None, valid)
    np.testing.assert_allclose(o[:, valid[0]], want, atol=1e-5)
    np.testing.assert_allclose(win, x[:, -3:], atol=0)
