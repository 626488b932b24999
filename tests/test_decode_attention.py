"""The decode-attention kernel (``ops/decode_attention.py``, in the Pallas
interpreter) against the einsum path of ``llama.Attention._cached_attention``
that it stands in for: grouped-query shapes of both serve cells, lengths at
every edge of a block, a window that binds, and a cache poisoned with NaN
wherever the mask does not admit a position."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models.llama import Attention, LlamaConfig
from tensorflowonspark_tpu.ops import attention as attn_mod
from tensorflowonspark_tpu.ops import decode_attention as da

C, BK, D = 512, 128, 128
GQA = {"gqa8x4": (8, 4), "gqa4x5": (4, 5)}
LENGTHS = {
    "one": [1, 1, 1],
    "block_less_one": [BK - 1] * 3,
    "block": [BK] * 3,
    "block_plus_one": [BK + 1] * 3,
    "whole_cache": [C] * 3,
    "mixed": [1, BK - 1, BK, BK + 1, 300, C],
}


@pytest.fixture(autouse=True)
def _interpreter(monkeypatch):
    monkeypatch.setattr(da, "INTERPRET", True)


def _cfg(kv_heads, rep, window=None, dtype=jnp.float32):
    return LlamaConfig.tiny(
        hidden_size=kv_heads * rep * D, num_heads=kv_heads * rep,
        num_kv_heads=kv_heads, max_seq_len=C, dtype=dtype,
        sliding_window=window,
    )


def _operands(kv_heads, rep, lengths, seed=0):
    rows = len(lengths)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (rows, kv_heads * rep, D), jnp.float32)
    k = jax.random.normal(kk, (rows, C, kv_heads, D), jnp.float32)
    v = jax.random.normal(kv, (rows, C, kv_heads, D), jnp.float32)
    return q, k, v, jnp.asarray(lengths, jnp.int32)


class CachedAttention(Attention):
    """``_cached_attention`` alone, without the projections around it."""

    @nn.compact
    def __call__(self, q, k, v, positions, padded=True):
        return self._cached_attention(q, k, v, positions, padded)


def spy_on_the_kernel(monkeypatch) -> list:
    """Record (lengths, keywords) of every call ``llama`` makes to the
    kernel, and let it through."""
    from tensorflowonspark_tpu.models import llama

    calls = []
    real = da.decode_attention

    def spy(q, k, v, lengths, **kw):
        calls.append((lengths, kw))
        return real(q, k, v, lengths, **kw)

    monkeypatch.setattr(llama, "decode_attention", spy)
    return calls


def _cached(cfg, q, k, v, lengths):
    """``_cached_attention`` for one new position a row on a cache that
    already holds it (the scatter rewrites what is there)."""
    rows = q.shape[0]
    at = (lengths - 1)[:, None]
    take = jnp.arange(rows)[:, None], at
    cache = {
        "k": k, "v": v, "seg": jnp.zeros((rows, C), jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }
    if cfg.sliding_window is not None:
        cache["pos"] = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (rows, C))
    out, _ = CachedAttention(cfg).apply(
        {"cache": cache}, q[:, None], k[take], v[take], at,
        mutable=["cache"],
    )
    return out[:, 0]


def _poisoned(x, lengths, window):
    """NaN wherever the mask admits no position: at or past a row's
    length, and before its window."""
    pos = jnp.arange(C)[None, :]
    dead = pos >= lengths[:, None]
    if window is not None:
        dead = dead | (pos < lengths[:, None] - window)
    return jnp.where(dead[:, :, None, None], jnp.nan, x)


@pytest.mark.parametrize("gqa", GQA)
@pytest.mark.parametrize("lengths", LENGTHS)
def test_kernel_matches_the_einsum_path(gqa, lengths):
    kv_heads, rep = GQA[gqa]
    q, k, v, lens = _operands(kv_heads, rep, LENGTHS[lengths])
    want = _cached(_cfg(kv_heads, rep), q, k, v, lens)
    got = da.decode_attention(q, k, v, lens, block_k=BK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("gqa", GQA)
@pytest.mark.parametrize("window", [1, 100, BK, 300])
def test_kernel_under_a_window_that_binds(gqa, window):
    kv_heads, rep = GQA[gqa]
    q, k, v, lens = _operands(kv_heads, rep, [window + 1, 2 * BK, 301, 400, C], seed=1)
    want = _cached(_cfg(kv_heads, rep, window), q, k, v, lens)
    got = da.decode_attention(q, k, v, lens, window=window, block_k=BK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("gqa", GQA)
@pytest.mark.parametrize("window", [None, 100])
def test_kernel_uses_nothing_the_mask_leaves_out(gqa, window):
    """Every position at or past a row's length, and before its window,
    holds NaN: the output is finite and the clean cache's. A block that
    was fetched or a probability that was not an exact zero would show."""
    kv_heads, rep = GQA[gqa]
    q, k, v, lens = _operands(kv_heads, rep, LENGTHS["mixed"], seed=2)
    want = da.decode_attention(q, k, v, lens, window=window, block_k=BK)
    got = da.decode_attention(
        q, _poisoned(k, lens, window), _poisoned(v, lens, window), lens,
        window=window, block_k=BK,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ref = _cached(_cfg(kv_heads, rep, window), q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_kernel_in_the_models_dtype():
    """bfloat16 operands: probabilities narrow before the second matmul,
    as on the einsum path; the two agree to bfloat16's rounding."""
    kv_heads, rep = GQA["gqa8x4"]
    q, k, v, lens = (
        x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x
        for x in _operands(kv_heads, rep, LENGTHS["mixed"], seed=3)
    )
    want = _cached(_cfg(kv_heads, rep, dtype=jnp.bfloat16), q, k, v, lens)
    got = da.decode_attention(q, k, v, lens, block_k=BK)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2
    )


@pytest.mark.parametrize("gqa", GQA)
def test_cached_attention_takes_the_kernel_on_a_tpu(gqa, monkeypatch):
    """The branch itself: told it is on a TPU, the padded one-position
    step calls the kernel with lengths ``position + 1`` and the window,
    and returns what the einsum path returns."""
    kv_heads, rep = GQA[gqa]
    cfg = _cfg(kv_heads, rep, 100)
    q, k, v, lens = _operands(kv_heads, rep, LENGTHS["mixed"], seed=4)
    want = _cached(cfg, q, k, v, lens)
    calls = spy_on_the_kernel(monkeypatch)
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    got = _cached(cfg, q, k, v, lens)
    assert [(np.asarray(n).tolist(), kw) for n, kw in calls] == [
        (LENGTHS["mixed"], {"window": 100})
    ]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "lengths,window,block_k,want",
    [
        ([1, 128, 129, 512], None, 128, [128, 128, 256, 512]),
        ([300], None, 256, [512]),
        ([450], 100, 128, [256]),      # positions 350..449: blocks 2 and 3
        ([400], 260, 128, [384]),      # 140..399: blocks 1, 2, 3
        ([0, 9999], None, 256, [256, 512]),  # clipped into [1, C]
    ],
)
def test_positions_read(lengths, window, block_k, want):
    got = da.positions_read(lengths, C, window, block_k)
    assert got.tolist() == want
