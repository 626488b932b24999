"""``ops/selective_scan.py``: the Mamba-1 scan's kernel (in the Pallas
interpreter) against its ``lax.scan`` twin against the recurrence written
out a position at a time in NumPy, with holes in ``valid`` and a carried
state; the one-position step against the same; the kernel's choice."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import attention, selective_scan as ss


def _inputs(rows=2, L=37, c=512, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        u=jax.random.normal(k[0], (rows, L, c)).astype(jnp.bfloat16),
        dt=jax.nn.softplus(jax.random.normal(k[1], (rows, L, c)) - 3.0),
        A=-jnp.exp(jax.random.normal(k[2], (N, c))),
        B=jax.random.normal(k[3], (rows, L, N)),
        C=jax.random.normal(k[4], (rows, L, N)),
        D=jax.random.normal(k[5], (c,)),
        state=jax.random.normal(k[6], (rows, N, c)),
    )


def _loop(u, dt, A, B, C, D, state, valid):
    """The recurrence as written, float64, a position at a time; an
    invalid position applies nothing."""
    u, dt, A, B, C, D, S = (np.asarray(x, np.float64) for x in (u, dt, A, B, C, D, state))
    rows, L, c = u.shape
    y = np.zeros((rows, L, c))
    for r in range(rows):
        for t in range(L):
            if valid is not None and not valid[r, t]:
                continue
            S[r] = np.exp(dt[r, t] * A) * S[r] + (dt[r, t] * u[r, t])[None] * B[r, t][:, None]
            y[r, t] = (S[r] * C[r, t][:, None]).sum(0) + D * u[r, t]
    return y, S


@pytest.mark.parametrize("L", [37, 300], ids=["one-chunk", "three-chunks"])
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "lax-scan"])
def test_kernel_and_lax_scan_equal_the_loop(monkeypatch, L, kernel):
    if kernel:  # the kernel, in the interpreter, where a TPU would take it
        monkeypatch.setattr(attention, "TREAT_AS_TPU", True)
        monkeypatch.setattr(ss, "INTERPRET", True)
    x = _inputs(L=L)
    valid = np.ones((2, L), bool)
    valid[0, L // 3 : L // 2] = False  # a hole
    valid[1, L - 5 :] = False  # right padding
    want_y, want_S = _loop(**x, valid=valid)
    y, S = ss.selective_scan(**x, valid=jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(S), want_S, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y)[valid], want_y[valid], rtol=2e-5, atol=2e-5)


def test_no_state_is_zeros_and_a_split_prompt_carries_its_state():
    x = _inputs(L=24)
    whole, S = ss.selective_scan(**{**x, "state": None})
    zero, _ = ss.selective_scan(**{**x, "state": jnp.zeros_like(x["state"])})
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(zero))
    part = {k: v[:, :10] if k in ("u", "dt", "B", "C") else v for k, v in x.items()}
    rest = {k: v[:, 10:] if k in ("u", "dt", "B", "C") else v for k, v in x.items()}
    y1, S1 = ss.selective_scan(**{**part, "state": None})
    y2, S2 = ss.selective_scan(**{**rest, "state": S1})
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.concatenate([y1, y2], 1), np.asarray(whole), rtol=1e-6, atol=1e-6)


def test_step_is_one_position_of_the_loop():
    x = _inputs(L=1)
    want_y, want_S = _loop(**x, valid=None)
    y, S = ss.selective_scan_step(
        x["state"], x["u"][:, 0], x["dt"][:, 0], x["A"], x["B"][:, 0], x["C"][:, 0], x["D"])
    np.testing.assert_allclose(np.asarray(S), want_S, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), want_y[:, 0], rtol=1e-5, atol=1e-5)
    # dt = 0 leaves the state as it was
    _, same = ss.selective_scan_step(
        x["state"], x["u"][:, 0], jnp.zeros_like(x["dt"][:, 0]), x["A"], x["B"][:, 0],
        x["C"][:, 0], x["D"])
    np.testing.assert_array_equal(np.asarray(same), np.asarray(x["state"]))


def test_the_kernel_is_taken_on_one_tpu_at_its_channel_blocks(monkeypatch):
    assert not ss._pallas_scan(5120)  # the CPU
    monkeypatch.setattr(attention, "TREAT_AS_TPU", True)
    assert ss._pallas_scan(5120) and ss._pallas_scan(128) and not ss._pallas_scan(640)
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.parallel.context import use_mesh

    with use_mesh(make_mesh({"data": -1})):
        assert not ss._pallas_scan(5120)
