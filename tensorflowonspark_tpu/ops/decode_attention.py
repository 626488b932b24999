"""Pallas TPU decode attention: one new position a row against the dense
KV cache, reading only what the row has written.

The cache is taken as the model stores it, ``(rows, C, kv_heads, d)``,
and a grid step streams one block of ``block_k`` positions with all its
KV heads. The grid visits only the live blocks: the positions
``max(0, length - window) .. length - 1`` of each row, by its written
length. It is one dimension long, one step per live (row, block) pair,
the rows in order and each row's blocks in order, and its extent is the
traced count of those pairs. Two scalar-prefetched tables, built with
XLA from the lengths (:func:`step_tables`), give each step its row and
its block; the K and V index maps read the block, the query's and the
output's read the row. A row of 600 written positions in a cache of
2560 moves a quarter of the bytes the masked einsum over the whole plane
moves, in three grid steps of 256 where a grid over every block would
take ten.

The pipeline fetches one step ahead, so a row's first block is fetched
under the previous row's last block of work. A row's first DMA must
never be exposed: a grid whose live blocks came first in each row lost
a block's copy time per row, and one that padded every row to all its
blocks hid it under dead steps that cost a step's fixed time each. Here
every step is live and every copy but the first of the call is hidden.

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

The Pallas interpreter (``INTERPRET``, on the CPU) runs the same kernel
over the same traced extent.
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


def step_tables(lengths, C: int, window: int | None, block_k: int):
    """The grid of a call: its extent, the count of live (row, block)
    pairs, and per step the row and the block, the rows in order and each
    row's blocks in order (``jnp``, inside the caller's program). Both
    tables are ``rows * C // block_k`` long, the static bound; the steps
    past the extent repeat the last live one, so that any entry read there
    names a block already resident. ``lengths`` as the kernel
    takes them, in ``[1, C]``: every row has at least one live block."""
    rows = lengths.shape[0]
    first, last = live_blocks(jnp, lengths, window, block_k)
    first = jnp.broadcast_to(first, last.shape)
    count = last - first + 1
    end = jnp.cumsum(count)
    step = jnp.arange(rows * (C // block_k), dtype=jnp.int32)
    # a step's row: the rows whose steps all come before it
    row = jnp.minimum(
        jnp.sum(step[:, None] >= end[None, :], axis=1, dtype=jnp.int32),
        rows - 1,
    )
    blk = jnp.minimum(first[row] + step - (end - count)[row], last[row])
    return end[-1], row, blk


def _kernel(
    len_ref, row_ref, blk_ref, q_ref, bias_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, block_k: int, kv_heads: int, scale: float, window: int | None,
):
    i = pl.program_id(0)
    length = len_ref[row_ref[i]]
    blk = blk_ref[i]
    first, last = live_blocks(jnp, length, window, block_k)
    n = block_k * kv_heads

    @pl.when(blk == first)
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
    pl.when(whole)(functools.partial(step, False))
    pl.when(~whole)(functools.partial(step, True))

    @pl.when(blk == last)
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
    kv_heads: int | None = None,
) -> jax.Array:
    """Attention of one query position a row over its written cache.

    ``q`` (rows, heads, d); ``k_cache`` / ``v_cache`` (rows, C, kv_heads,
    d) as stored, or (rows, C * kv_heads, d) with ``kv_heads`` given (a
    position's heads as rows: how the kernel reads a block in any case,
    and a layout that pads nothing where ``kv_heads`` is no multiple of
    a tile's 8 rows); ``lengths`` (rows,) int32, the positions each row has
    written, the query's own included: position ``p`` is attended iff
    ``max(0, length - window) <= p < length``. Returns (rows, heads, d)
    in ``q``'s dtype. Only a row's live blocks are visited and fetched.
    """
    rows, heads, d = q.shape
    if kv_heads is None:
        _, C, kv_heads, _ = k_cache.shape
    else:
        C = k_cache.shape[1] // kv_heads
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
    steps, row, blk = step_tables(lengths, C, window, block_k)

    def by_row(i, lens, row, blk):
        return row[i], 0, 0

    kv_spec = pl.BlockSpec(
        (1, block_k, kv_heads, d), lambda i, lens, row, blk: (row[i], blk[i], 0, 0)
    )
    if k_cache.ndim == 3:
        kv_spec = pl.BlockSpec(
            (1, n, d), lambda i, lens, row, blk: (row[i], blk[i], 0)
        )
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_k=block_k, kv_heads=kv_heads, scale=d**-0.5,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, hp, d), by_row),
                pl.BlockSpec((hp, n), lambda i, lens, row, blk: (0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, hp, d), by_row),
            scratch_shapes=[
                pltpu.VMEM((hp, NUM_LANES), jnp.float32),
                pltpu.VMEM((hp, NUM_LANES), jnp.float32),
                pltpu.VMEM((hp, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, hp, d), q.dtype),
        interpret=INTERPRET,
        name="decode_attention",
    )(lengths, row, blk, q,
      jnp.asarray(_head_bias(heads, hp, kv_heads, block_k)), k_cache, v_cache)
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
        # A row's live blocks take its last grid steps and the dead steps
        # before them name block 0: the pipeline, which fetches one step
        # ahead, then starts a row's first block under the previous row's
        # last block of work, not under a dead step that is over before
        # the copy is.
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
    the einsum: a rolling cache longer than its window, an int8 cache, an
    ambient mesh (GSPMD partitions the einsum and cannot partition a
    ``pallas_call``), no TPU. A ring of exactly its window
    (``models/phi4_flash.py``) takes the kernel: once full, every slot it
    holds is attended, in whatever order. ``cfg`` is a model config as
    ``llama.Attention`` reads it, or one whose cache entry is a latent (it
    then carries ``kv_lora_rank`` and ``qk_rope_head_dim``:
    ``models/pangu_moe.py``)."""
    from tensorflowonspark_tpu.ops import attention
    from tensorflowonspark_tpu.parallel.context import current_mesh

    C = cfg.kv_cache_len or cfg.max_seq_len
    if (
        (C < cfg.max_seq_len and C != cfg.sliding_window)
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
