"""The reference against itself at the ``tiny`` sizes, window and segment
ids on: blocked against unblocked, packed against separate documents, the
AdamW step against optax."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench import reference, weights
from perfbench.drivers import _llama

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "..", "configs", "tiny.json")) as f:
        cfg = _llama.model_keys(json.load(f))
    leaf = _llama.reference_leaves(cfg, weights.seed_key(3), jnp.float32)
    return cfg, {k: leaf(k) for k in leaf.names}


def naive_attention(q, k, v, doc, pos, window):
    s, hq, d = q.shape
    r = hq // k.shape[1]
    k, v = jnp.repeat(k, r, axis=1), jnp.repeat(v, r, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * d**-0.5
    i = jnp.arange(s)
    ok = (i[None, :] <= i[:, None]) & (doc[None, :] == doc[:, None])
    ok &= (pos[:, None] - pos[None, :]) < window
    p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision="highest").reshape(s, hq * d)


def test_blocked_attention_matches_naive():
    rng = np.random.default_rng(0)
    s, hq, hkv, d = 48, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s, hkv, d)), jnp.float32)
    seg = jnp.asarray([1] * 20 + [2] * 18 + [0] * 10)
    doc, pos = reference.documents(seg)
    got = reference.attention(q, k, v, doc, pos, 8, 4)
    want = naive_attention(q, k, v, doc, pos, 8)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_documents_restart_positions():
    doc, pos = reference.documents(jnp.asarray([1, 1, 1, 2, 2, 0, 0]))
    assert doc.tolist() == [0, 0, 0, 1, 1, 2, 2]
    assert pos.tolist() == [0, 1, 2, 0, 1, 0, 1]


def test_packed_row_equals_separate_documents(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(1)
    a = rng.integers(0, cfg["vocab_size"], 40)  # longer than the window of 24
    b = rng.integers(0, cfg["vocab_size"], 20)
    row = np.concatenate([a, b, np.zeros(5, int)]).astype(np.int32)
    seg = np.asarray([1] * 40 + [2] * 20 + [0] * 5, np.int32)
    tot, n = reference.row_nll(cfg, params, jnp.asarray(row), jnp.asarray(seg),
                               reference.mm_highest, 1)
    parts = [
        reference.row_nll(cfg, params, jnp.asarray(x.astype(np.int32)),
                          jnp.ones(len(x), jnp.int32), reference.mm_highest, 1)
        for x in (a, b)
    ]
    assert float(n) == 39 + 19 == sum(float(p[1]) for p in parts)
    np.testing.assert_allclose(float(tot), sum(float(p[0]) for p in parts), rtol=2e-6)


def test_window_changes_the_loss(tiny):
    cfg, params = tiny
    row = jnp.asarray(np.random.default_rng(2).integers(0, 256, 65), jnp.int32)
    seg = jnp.ones(65, jnp.int32)
    full = reference.row_nll({**cfg, "sliding_window": None}, params, row, seg,
                             reference.mm_highest, 1)[0]
    win = reference.row_nll(cfg, params, row, seg, reference.mm_highest, 1)[0]
    assert abs(float(full) - float(win)) > 1e-4


def test_blocks_do_not_change_the_loss_or_gradient(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, 256, (2, 65)), jnp.int32)
    seg = jnp.asarray(np.stack([[1] * 30 + [2] * 35, [1] * 60 + [0] * 5]), jnp.int32)
    f = lambda blocks: jax.value_and_grad(  # noqa: E731
        lambda p: reference.batch_loss(cfg, p, tokens, seg, blocks=blocks))(params)
    (l1, g1), (l4, g4) = f(1), f(4)
    np.testing.assert_allclose(l1, l4, rtol=1e-6)
    for k in g1:
        np.testing.assert_allclose(g1[k], g4[k], atol=1e-6, rtol=1e-4)


def test_half_batch_fault_is_the_mean_over_the_rest(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, 256, (2, 65)), jnp.int32)
    seg = jnp.ones((2, 65), jnp.int32)
    half = reference.batch_loss(cfg, params, tokens, seg, blocks=1, keep=jnp.asarray([1.0, 0.0]))
    one = reference.batch_loss(cfg, params, tokens[:1], seg[:1], blocks=1)
    np.testing.assert_allclose(half, one, rtol=1e-6)


def test_adamw_matches_optax():
    import optax

    opt = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    rng = np.random.default_rng(6)
    p = jnp.asarray(rng.normal(size=(7, 5)), jnp.float32)
    st, q = tx.init(p), p
    mu = nu = jnp.zeros_like(p)
    for step in range(1, 4):
        g = jnp.asarray(rng.normal(size=(7, 5)), jnp.float32)
        up, st = tx.update(g, st, q)
        q = optax.apply_updates(q, up)
        p, mu, nu = reference.adamw_update(opt, jnp.asarray(step), p, g, mu, nu)
        np.testing.assert_allclose(p, q, rtol=1e-5, atol=1e-7)


def test_fp8_control_differs_and_keeps_a_gradient(tiny):
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, 256, (1, 65)), jnp.int32)
    seg = jnp.ones((1, 65), jnp.int32)
    hi = reference.batch_loss(cfg, params, tokens, seg, blocks=1)
    lo, g = jax.value_and_grad(
        lambda p: reference.batch_loss(cfg, p, tokens, seg, mm=reference.mm_fp8, blocks=1)
    )(params)
    assert abs(float(hi) - float(lo)) / float(hi) > 1e-5
    assert float(jnp.linalg.norm(g["layer0/mlp/up_proj/kernel"])) > 0
