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


def _poisoned(x, lengths, window, C=C):
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


# SambaY's shared plane as stored: flat, a position's 10 K/V pairs as rows,
# 40 query rows; a cache of 12 blocks
FLAT_KVH, FLAT_REP, FLAT_BK = 10, 4, 32
FLAT_C = 12 * FLAT_BK
FLAT_LENGTHS = {
    # every block edge, each from both sides
    "every_edge": sorted(
        {1, FLAT_C} | {e + o for e in range(FLAT_BK, FLAT_C, FLAT_BK) for o in (-1, 0, 1)}
    ),
    "every_row_one": [1] * 16,
    "whole_and_one": [FLAT_C, 1, FLAT_C, 2, FLAT_BK, FLAT_C - 1],
}


def _flat_operands(lengths, seed):
    rows = len(lengths)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (rows, FLAT_KVH * FLAT_REP, D), jnp.float32)
    plane = (rows, FLAT_C * FLAT_KVH, D)
    k = jax.random.normal(kk, plane, jnp.float32)
    v = jax.random.normal(kv, plane, jnp.float32)
    return q, k, v, jnp.asarray(lengths, jnp.int32)


def _flat_einsum(q, k, v, lens):
    """``llama.attend_plane`` on the CPU: the grouped einsum over the flat
    plane that the kernel stands in for on a cross layer."""
    from tensorflowonspark_tpu.models.llama import attend_plane

    cfg = LlamaConfig.tiny(
        hidden_size=FLAT_KVH * FLAT_REP * D, num_heads=FLAT_KVH * FLAT_REP,
        num_kv_heads=FLAT_KVH, max_seq_len=FLAT_C, dtype=jnp.float32,
        kv_heads_in_rows=True,
    )
    return attend_plane(q, k, v, lens, cfg)


@pytest.mark.parametrize("lengths", FLAT_LENGTHS)
def test_flat_plane_matches_the_einsum(lengths):
    """SambaY's layout: ``kv_heads`` given, the plane (rows, C x 10, d),
    at lengths on both sides of every block edge, rows of length 1 only,
    and rows of the whole cache beside rows of one block."""
    q, k, v, lens = _flat_operands(FLAT_LENGTHS[lengths], seed=5)
    got = da.decode_attention(q, k, v, lens, block_k=FLAT_BK, kv_heads=FLAT_KVH)
    want = _flat_einsum(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def _poisoned_flat(x, lengths, window):
    rows = x.shape[0]
    return _poisoned(
        x.reshape(rows, FLAT_C, FLAT_KVH, D), lengths, window, FLAT_C
    ).reshape(x.shape)


@pytest.mark.parametrize("window", [None, 100])
def test_flat_plane_uses_nothing_the_mask_leaves_out(window):
    """The NaN-poisoned cache of the stored layout's test, on the flat
    plane: a position outside a row's span, fetched or given a
    probability that is not an exact zero, would show."""
    q, k, v, lens = _flat_operands(FLAT_LENGTHS["every_edge"], seed=6)
    kw = dict(window=window, block_k=FLAT_BK, kv_heads=FLAT_KVH)
    want = da.decode_attention(q, k, v, lens, **kw)
    got = da.decode_attention(
        q, _poisoned_flat(k, lens, window), _poisoned_flat(v, lens, window), lens, **kw
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if window is None:
        ref = _flat_einsum(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


RING = 512  # two blocks of 256 at 8 KV heads of 128 in float32


def _ring_cached(cfg, q, k, v, positions):
    """``_cached_attention`` for one new position a row on a ring of
    exactly its window that already holds it: slot ``j`` keeps the latest
    position ``p <= position`` with ``p % RING == j``, -1 where none was
    written yet."""
    rows = q.shape[0]
    slot = jnp.arange(RING)[None, :]
    last = positions[:, None] - (positions[:, None] - slot) % RING
    pos = jnp.where(last >= 0, last, -1).astype(jnp.int32)
    at = positions[:, None]
    take = jnp.arange(rows)[:, None], at % RING
    planes = dict(k=k, v=v)
    if cfg.kv_heads_in_rows:
        planes = {n: x.reshape(rows, RING * cfg.num_kv_heads, D) for n, x in planes.items()}
    cache = dict(
        planes, seg=jnp.zeros((rows, RING), jnp.int32), pos=pos,
        idx=jnp.zeros((), jnp.int32),
    )
    out, _ = CachedAttention(cfg).apply(
        {"cache": cache}, q[:, None], k[take], v[take], at, mutable=["cache"],
    )
    return out[:, 0]


@pytest.mark.parametrize("flat", [False, True], ids=["stored", "flat"])
def test_ring_of_exactly_its_window(flat, monkeypatch):
    """A windowed layer's ring (``kv_cache_len == sliding_window``): on a
    TPU the step calls the kernel with lengths ``min(position + 1, C)``
    and no window, before the ring fills, as it fills and long after it
    has wrapped, and returns what the positional einsum returns."""
    kv_heads, rep = GQA["gqa8x4"]
    cfg = LlamaConfig.tiny(
        hidden_size=kv_heads * rep * D, num_heads=kv_heads * rep,
        num_kv_heads=kv_heads, max_seq_len=4 * RING, kv_cache_len=RING,
        sliding_window=RING, dtype=jnp.float32, kv_heads_in_rows=flat,
    )
    positions = jnp.asarray([0, 100, 255, 256, 510, 511, 512, 700, 1500], jnp.int32)
    rows = positions.shape[0]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (rows, kv_heads * rep, D), jnp.float32)
    k = jax.random.normal(kk, (rows, RING, kv_heads, D), jnp.float32)
    v = jax.random.normal(kv, (rows, RING, kv_heads, D), jnp.float32)
    want = _ring_cached(cfg, q, k, v, positions)
    calls = spy_on_the_kernel(monkeypatch)
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    got = _ring_cached(cfg, q, k, v, positions)
    assert len(calls) == 1
    lengths, kw = calls[0]
    assert np.asarray(lengths).tolist() == np.minimum(np.asarray(positions) + 1, RING).tolist()
    assert kw == ({"window": None, "kv_heads": kv_heads} if flat else {"window": None})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def _sambay_plane_lengths():
    rng = np.random.default_rng(0)
    return rng.integers(1, 3073, 128).tolist()


@pytest.mark.parametrize(
    "lengths,C,window,block_k",
    [
        (LENGTHS["mixed"], C, None, BK),
        ([1] * 16, C, None, BK),
        ([1] * 16, C, 100, BK),
        ([C] * 4, C, None, BK),
        ([450, 400, 1, 129], C, 100, BK),
        ([450, 400, 1, 129], C, 260, 64),
        (_sambay_plane_lengths(), 3072, None, 256),
        ([1, 300, 512, 512], 512, None, 256),  # a ring: min(length, C)
    ],
    ids=["mixed", "every_row_one", "every_row_one_window", "whole_cache",
         "window_100", "window_260", "sambay_plane", "ring"],
)
def test_step_tables_visit_each_live_block_once_in_row_order(lengths, C, window, block_k):
    """The grid's extent is the blocks ``positions_read`` counts; its steps
    name each live (row, block) pair once, the rows in order and each
    row's blocks in order; the steps past the extent repeat the last."""
    steps, row, blk = (
        np.asarray(x)
        for x in da.step_tables(jnp.asarray(lengths, jnp.int32), C, window, block_k)
    )
    rows = len(lengths)
    assert row.shape == blk.shape == (rows * (C // block_k),)
    assert int(steps) == da.positions_read(lengths, C, window, block_k).sum() // block_k
    first, last = da.live_blocks(np, np.asarray(lengths), window, block_k)
    first = np.broadcast_to(first, last.shape)
    want = [(r, b) for r in range(rows) for b in range(first[r], last[r] + 1)]
    assert list(zip(row[:steps].tolist(), blk[:steps].tolist())) == want
    assert (row[steps:] == row[steps - 1]).all() and (blk[steps:] == blk[steps - 1]).all()
