"""Pallas TPU decode attention: one new position a row against the dense
KV cache, reading only what the row has written.

The cache is taken as the model stores it, ``(rows, C, kv_heads, d)``,
and a grid step streams one block of ``block_k`` positions with all its
KV heads. Each row's written length arrives as a prefetched scalar: the
K and V index maps clamp the block index into the row's live range, so a
step outside it names a block that is already resident and the pipeline
starts no DMA for it, and ``pl.when`` skips its compute. A row
of 600 written positions in a cache of 2560 moves a quarter of the
bytes the masked einsum over the whole plane moves.

Inside a block the position and KV-head dimensions are one: the block is
read as ``(block_k * kv_heads, d)`` rows, which is how it lies in memory,
and every query head is multiplied against every row. A constant bias
keeps, for query head ``i``, the rows of its own KV head ``i // rep``
and sends the others to ``-inf`` with the positions the row has not
written; their probabilities are exact zeros in the second matmul. The
MXU does ``kv_heads`` times the products needed, which it has to spare
at four or five query rows a KV head, and no head is ever gathered out
of the sublanes it is stored in. Logits, the running maximum, the sum
and the accumulator are float32 (online softmax across the row's
blocks); probabilities narrow to the cache's dtype before the second
matmul, as the einsum path narrows them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.ops.flash_attention import NEG_INF, NUM_LANES

# Test hook: run the kernel in the Pallas interpreter (works on CPU).
INTERPRET = False


def _default_block_k(
    C: int, heads: int, kv_heads: int, d: int, itemsize: int
) -> int | None:
    """Positions a grid step streams, from the shapes: the largest power
    of two up to 512 that divides the cache, keeps a block of one plane
    within 1 MiB and the block's float32 logits within 1 MiB (two planes
    double-buffered, the bias and the softmax's temporaries then fit the
    16 MiB of VMEM a kernel may use). None where no such block exists:
    the caller keeps the einsum.

    512 is from the chip (PERF.md §6, PR 30): one layer's attention at
    both serve cells' shapes with their own length mixes, us at 128 / 256
    / 512 / 1024-or-1280 positions: (16, 2560, 8, 128) 118-130 / 82-100 /
    82-97 / 135-147 where the einsum takes 274; (48, 2048, 4, 128) 321-332
    / 231-241 / 203-221 / 234-252 where it takes 297. Smaller blocks
    fetch less past a row's length and pay more grid steps, a step's
    fixed cost showing once a block is under a megabyte; larger ones
    fetch more than they save."""
    # a K/V plane's block makes kv_heads columns of logits a position
    # and query row
    return _largest_block(
        C, kv_heads * d * itemsize, _padded_heads(heads) * kv_heads
    )


def _padded_heads(heads: int) -> int:
    """Query rows as whole sublane tiles, in either dtype."""
    return -(-heads // 16) * 16


def live_blocks(xp, length, window: int | None, block_k: int):
    """First and last block holding a position the row's newest query
    attends: positions ``max(0, length - window) .. length - 1``. One
    rule over ``jnp`` (index maps, kernel) and ``numpy`` (the count)."""
    first = 0 if window is None else xp.maximum(length - window, 0)
    return first // block_k, (length - 1) // block_k


def positions_read(lengths, C: int, window: int | None, block_k: int):
    """Positions of the blocks the kernel fetches for rows of these
    written lengths (numpy, on the host: what the engine counts). With
    ``block_k = C`` it is the whole row, what the einsum reads."""
    lengths = np.clip(np.asarray(lengths, np.int64), 1, C)
    first, last = live_blocks(np, lengths, window, block_k)
    return (last - first + 1) * block_k


def _head_bias(heads: int, heads_padded: int, kv_heads: int, block_k: int):
    """(heads_padded, block_k * kv_heads) float32: 0 where the column's
    KV head is the query head's own, ``NEG_INF`` elsewhere."""
    rep = heads // kv_heads
    own = np.arange(heads_padded)[:, None] // rep
    col = np.arange(block_k * kv_heads)[None, :] % kv_heads
    return np.where(own == col, 0.0, NEG_INF).astype(np.float32)


def _kernel(
    len_ref, q_ref, bias_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_k: int, kv_heads: int, scale: float, window: int | None,
):
    j = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    first, last = live_blocks(jnp, length, window, block_k)
    # the row's live blocks are its LAST grid steps (see kv_block)
    blk = j - (pl.num_programs(1) - 1 - last)
    n = block_k * kv_heads

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(edge: bool):
        q = q_ref[0]
        k = k_ref[0].reshape(n, k_ref.shape[-1])
        v = v_ref[0].reshape(n, v_ref.shape[-1])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[...]
        if edge:
            # column (and row of v) c is position blk * block_k + c // kv_heads
            hi = (length - blk * block_k) * kv_heads
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            written, keep = col < hi, row < hi
            if window is not None:
                lo = hi - window * kv_heads
                written, keep = written & (col >= lo), keep & (row >= lo)
            s = jnp.where(written, s, NEG_INF)
            # a zero probability times whatever lies there must be zero
            v = jnp.where(keep, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # a block wholly inside the attended span needs no position mask
    whole = (blk + 1) * block_k <= length
    if window is not None:
        whole = whole & (blk * block_k >= length - window)
    live = blk >= first
    pl.when(live & whole)(functools.partial(step, False))
    pl.when(live & ~whole)(functools.partial(step, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    window: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Attention of one query position a row over its written cache.

    ``q`` (rows, heads, d); ``k_cache`` / ``v_cache`` (rows, C, kv_heads,
    d) as stored; ``lengths`` (rows,) int32, the positions each row has
    written, the query's own included: position ``p`` is attended iff
    ``max(0, length - window) <= p < length``. Returns (rows, heads, d)
    in ``q``'s dtype. Nothing past a row's last live block is fetched.
    """
    rows, heads, d = q.shape
    _, C, kv_heads, _ = k_cache.shape
    if heads % kv_heads:
        raise ValueError(f"q heads {heads} not divisible by kv heads {kv_heads}")
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    block_k = block_k or _default_block_k(
        C, heads, kv_heads, d, k_cache.dtype.itemsize
    )
    if block_k is None or C % block_k:
        raise ValueError(f"block_k={block_k} does not divide the cache's {C}")
    hp = _padded_heads(heads)
    if hp != heads:
        q = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))
    n = block_k * kv_heads
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, C)

    def kv_block(r, j, lens):
        # A row's live blocks first..last take its last grid steps and
        # the dead steps before them name ``first``: the pipeline, which
        # fetches one step ahead, then starts a row's first block under
        # the previous row's last block of work, not under a dead step
        # that is over before the copy is.
        first, last = live_blocks(jnp, lens[r], window, block_k)
        return r, jnp.maximum(j - (C // block_k - 1 - last), first), 0, 0

    out = pl.pallas_call(
        functools.partial(
            _kernel, block_k=block_k, kv_heads=kv_heads, scale=d**-0.5,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, C // block_k),
            in_specs=[
                pl.BlockSpec((1, hp, d), lambda r, j, lens: (r, 0, 0)),
                pl.BlockSpec((hp, n), lambda r, j, lens: (0, 0)),
                pl.BlockSpec((1, block_k, kv_heads, d), kv_block),
                pl.BlockSpec((1, block_k, kv_heads, d), kv_block),
            ],
            out_specs=pl.BlockSpec((1, hp, d), lambda r, j, lens: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hp, NUM_LANES), jnp.float32),
                pltpu.VMEM((hp, NUM_LANES), jnp.float32),
                pltpu.VMEM((hp, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, hp, d), q.dtype),
        interpret=INTERPRET,
        name="decode_attention",
    )(lengths, q, jnp.asarray(_head_bias(heads, hp, kv_heads, block_k)),
      k_cache, v_cache)
    return out[:, :heads]


def _largest_block(C: int, plane_bytes: int, logit_cols: int) -> int | None:
    """The block rule: the largest power of two up to 512 that divides
    the cache and keeps a block of a plane of ``plane_bytes`` a position,
    and its ``logit_cols`` float32 logits a position, within 1 MiB each."""
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if (
            C % cand == 0
            and cand * plane_bytes <= 1 << 20
            and cand * logit_cols * 4 <= 1 << 20
        ):
            return cand
    return None


def latent_entry_width(rank: int, rope: int) -> int:
    """Values a position of a latent plane is stored in: the ``rank``
    compressed values and the ``rope`` rotary ones, then zeros up to
    whole 128-lane tiles. The TPU's tiled layout pads the minor
    dimension so in any case, and given a minor dimension that is no
    multiple of a tile it makes the positions minor instead, which the
    kernel could take only through a copy of the whole plane."""
    return -(-(rank + rope) // NUM_LANES) * NUM_LANES


def _latent_block_k(C: int, heads: int, width: int, itemsize: int) -> int | None:
    """The block rule for a latent plane: one entry of ``width`` values a
    position, ``heads`` query rows of float32 logits."""
    return _largest_block(C, width * itemsize, _padded_heads(heads))


def _latent_kernel(
    len_ref, q_ref, c_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_k: int, rank: int, scale: float,
):
    j = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    _, last = live_blocks(jnp, length, None, block_k)
    # the row's live blocks are its LAST grid steps (see latent_block)
    blk = j - (pl.num_programs(1) - 1 - last)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(edge: bool):
        # one fetched block is K (the whole entry) and V (its first
        # ``rank`` values, a slice at a tile's edge)
        c = c_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        v = c[:, :rank]
        if edge:
            hi = length - blk * block_k
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            s = jnp.where(col < hi, s, NEG_INF)
            # a zero probability times whatever lies there must be zero
            v = jnp.where(row < hi, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    whole = (blk + 1) * block_k <= length
    live = blk >= 0
    pl.when(live & whole)(functools.partial(step, False))
    pl.when(live & ~whole)(functools.partial(step, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def latent_decode_attention(
    q_lat: jax.Array,
    q_rope: jax.Array,
    cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: float,
    block_k: int | None = None,
) -> jax.Array:
    """Absorbed latent attention of one query position a row over its
    written cache.

    ``cache`` (rows, C, width) as stored: per position the compressed
    key-value ``c_kv`` (rank values), the rotary key shared by every
    head, and zeros up to ``width`` (:func:`latent_entry_width`).
    ``q_lat`` (rows, heads, rank) is each head's query carried through
    its key up-projection, ``q_rope`` (rows, heads, rope) its rotary
    part; ``lengths`` (rows,) the positions each row has written, the
    query's own included. Scores are ``(q_lat . c_kv + q_rope . k_r) *
    scale`` over positions below the length, softmax in float32, and the
    result (rows, heads, rank) is ``sum p * c_kv``, to be carried through
    the value up-projection by the caller. A block of the plane is
    fetched once and is both K and V; nothing past a row's last live
    block is fetched. ``scale`` is the model's (from the width of the
    unabsorbed query, not of what is multiplied here).
    """
    rows, heads, rank = q_lat.shape
    _, C, width = cache.shape
    fill = width - rank - q_rope.shape[-1]
    if fill < 0:
        raise ValueError(
            f"q_lat {rank} + q_rope {q_rope.shape[-1]} do not fit the "
            f"cache entry's {width}"
        )
    block_k = block_k or _latent_block_k(C, heads, width, cache.dtype.itemsize)
    if block_k is None or C % block_k:
        raise ValueError(f"block_k={block_k} does not divide the cache's {C}")
    hp = _padded_heads(heads)
    # one query as wide as an entry: its zeros meet the entry's
    q = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((rows, heads, fill), q_lat.dtype)], axis=-1
    )
    if hp != heads:
        q = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, C)

    def latent_block(r, j, lens):
        # live blocks on the row's last grid steps, as decode_attention's
        # kv_block has them and for its reason
        _, last = live_blocks(jnp, lens[r], None, block_k)
        return r, jnp.maximum(j - (C // block_k - 1 - last), 0), 0

    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, block_k=block_k, rank=rank, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, C // block_k),
            in_specs=[
                pl.BlockSpec((1, hp, width), lambda r, j, lens: (r, 0, 0)),
                pl.BlockSpec((1, block_k, width), latent_block),
            ],
            out_specs=pl.BlockSpec((1, hp, rank), lambda r, j, lens: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hp, NUM_LANES), jnp.float32),
                pltpu.VMEM((hp, NUM_LANES), jnp.float32),
                pltpu.VMEM((hp, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, hp, rank), q_lat.dtype),
        interpret=INTERPRET,
        name="latent_decode_attention",
    )(lengths, q, cache)
    return out[:, :heads]


def cache_block_k(cfg) -> int | None:
    """``block_k`` of the kernel that a padded one-position step against
    ``cfg``'s cache takes in this process, or None where that step keeps
    the einsum: a rolling or an int8 cache, an ambient mesh (GSPMD
    partitions the einsum and cannot partition a ``pallas_call``), no
    TPU. ``cfg`` is a model config as ``llama.Attention`` reads it, or
    one whose cache entry is a latent (it then carries ``kv_lora_rank``
    and ``qk_rope_head_dim``: ``models/pangu_moe.py``)."""
    from tensorflowonspark_tpu.ops import attention
    from tensorflowonspark_tpu.parallel.context import current_mesh

    C = cfg.kv_cache_len or cfg.max_seq_len
    if (
        C < cfg.max_seq_len
        or cfg.kv_cache_dtype != "model"
        or current_mesh() is not None
        or not attention._on_tpu()
    ):
        return None
    itemsize = jnp.dtype(cfg.dtype).itemsize
    rank = getattr(cfg, "kv_lora_rank", None)
    if rank:
        return _latent_block_k(
            C, cfg.num_heads,
            latent_entry_width(rank, cfg.qk_rope_head_dim), itemsize,
        )
    return _default_block_k(
        C, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, itemsize
    )
