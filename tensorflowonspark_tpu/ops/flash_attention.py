"""Pallas TPU flash attention (blockwise online-softmax, fwd + bwd).

The kernels stream one (block_q x block_k) tile per grid step, keeping the
O(Sq x Sk) logits matrix out of HBM entirely — the standard flash recipe
expressed for the MXU/VPU split (matmuls in the MXU, the online-softmax
rescale on the VPU). See /opt/skills/guides/pallas_guide.md for the kernel
idioms used here.

Memory shape: the K-block (or Q-block, in backward) index is a *grid*
dimension — innermost, so accumulators live in VMEM scratch across steps —
which keeps VMEM pressure at O(block x d) regardless of sequence length.
GQA is a BlockSpec index-map (each Q head reads its KV group's block
directly from HBM), not a materialized ``jnp.repeat``.

Backward follows FlashAttention's two-pass scheme against saved
log-sum-exp residuals: a dQ kernel (grid over Q blocks, streaming K), and
a dK/dV kernel (grid over K blocks, streaming Q). dK/dV are computed per
*query* head and group-summed outside the kernel — inside, multiple grid
rows would otherwise race on one KV head's output block.

Packed rows (``segment_ids``) skip the tiles their documents mask whole.
The ids are reduced once, outside the kernels, to each block's smallest
and largest id and, from those, to the first and last live block of every
row of tiles (:func:`_tile_tables`); the kernels get them as one
scalar-prefetched int32 table. A tile whose q range and k range are
disjoint holds no equal pair whatever the order of the ids, so leaving it
out drops only terms that were exactly zero (ids that are not monotone
merely skip less). A dead step at either end of a row computes nothing and
its index maps name the block its live neighbour fetched, so the pipeline
issues no DMA for it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# Per-row stats (LSE, delta) are stored lane-replicated to NUM_LANES so
# their blocks satisfy Mosaic's (8, 128) tiling rule — a (1, block_q)
# block on a (rows, seq) array is rejected on real TPUs. Same layout the
# reference TPU kernel in jax.experimental.pallas.ops.tpu uses. Segment
# ids ride the same way: q ids lane-replicated, kv ids sublane-replicated
# (so the kernel reads a (1, block_k) row without a transpose).
NUM_LANES = 128
NUM_SUBLANES = 8

# Test hook: run the kernel in the Pallas interpreter (works on CPU).
INTERPRET = False


def _causal_live(qi, ki, block_q: int, block_k: int, offset: int):
    """This (Q, K) block pair intersects the causal frontier."""
    return ki * block_k <= (qi + 1) * block_q - 1 + offset


def _window_live(qi, ki, block_q, block_k, offset, window):
    """This block pair has keys inside the sliding window's lower edge
    (query i attends j >= i + offset - window + 1)."""
    return (ki + 1) * block_k - 1 >= qi * block_q + offset - (window - 1)


def _window_grid_k(window, block_q, block_k, num_k_blocks):
    """K-block grid extent per q block under a window: the live key span
    of one q block is block_q + window - 1 elements, so this many blocks
    always cover it (+1 for alignment slack). The grid — and therefore
    the K/V block DMAs — shrinks with it: windowed cost is O(S·W) in
    BOTH compute and HBM traffic, not just masked-out compute."""
    if window is None:
        return num_k_blocks
    return min(num_k_blocks, (block_q + window - 2) // block_k + 2)


def _first_k_block(qi, offset, window, block_q, block_k, nk, num_k_blocks):
    """First k block of this q block's restricted span, clamped so the
    nk-wide span stays inside [0, num_k_blocks). Blocks pulled in by the
    clamp are dead and get masked by the live/window checks."""
    first = (qi * block_q + offset - (window - 1)) // block_k
    return jnp.clip(first, 0, num_k_blocks - nk)


def _window_grid_q(window, block_q, block_k, num_q_blocks):
    """Q-block grid extent per k block (the dkv kernel's restriction)."""
    if window is None:
        return num_q_blocks
    return min(num_q_blocks, (block_k + window - 2) // block_q + 2)


def _first_q_block(ki, offset, window, block_q, block_k, nq, num_q_blocks):
    """First q block that can attend this k block (the causal lower edge
    q >= k - offset), clamped like :func:`_first_k_block`."""
    first = (ki * block_k - offset) // block_q
    return jnp.clip(first, 0, num_q_blocks - nq)


def _tile_logits(
    q, k, qi, ki, block_q, block_k, offset, causal, scale, window=None
):
    """Scaled (block_q, block_k) logits with the causal (and optional
    sliding-window) mask applied."""
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if causal or window is not None:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if causal:
            s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
        if window is not None:
            s = jnp.where(q_pos + offset - k_pos < window, s, NEG_INF)
    return s


def _segment_masked(s, qseg_ref, kseg_ref, block_k: int):
    """Mask logits where q and k segment ids differ (trace-time no-op
    when no segment refs are bound). The online-softmax rescale makes a
    leading fully-masked tile harmless: its uniform exp(0) garbage is
    zeroed by alpha the moment a live tile raises the running max."""
    if qseg_ref is None:
        return s
    q_ids = qseg_ref[0]  # (block_q, NUM_LANES), lane-replicated
    if block_k % NUM_LANES == 0:
        q_ids = jnp.tile(q_ids, (1, block_k // NUM_LANES))
    else:  # short sequences: block_k < one lane tile
        q_ids = q_ids[:, :block_k]
    k_ids = kseg_ref[0][:1, :]  # (1, block_k) from the sublane-replicated row
    return jnp.where(q_ids == k_ids, s, NEG_INF)


# --------------------------------------------------------------------------
# tiles that segment ids mask whole
# --------------------------------------------------------------------------


def _ranges_disjoint(qmin, qmax, kmin, kmax):
    """No id of the q block equals any id of the k block: the tile is
    masked whole. Safe for any order of ids (a tile of disjoint ranges
    cannot hold an equal pair); overlapping ranges decide nothing."""
    return (qmax < kmin) | (qmin > kmax)


def _span(xp, live, axis: int):
    """First and last True along ``axis``; the whole axis where none is."""
    n = live.shape[axis]
    return live.argmax(axis), n - 1 - xp.flip(live, axis).argmax(axis)


def _tile_tables(xp, segment_ids, block_q, block_k, causal, window):
    """What ``segment_ids`` (B, S) decide about the (S/block_q, S/block_k)
    tiles of each batch row, over ``xp`` (``jnp`` inside the jitted
    wrappers, ``numpy`` for :func:`segment_tile_counts`): each block's
    smallest and largest id, ``live`` (B, nq, nk) by the kernels' own
    predicate, ``in_window`` (nq, nk) by the causal edge and the window
    alone, and each row of tiles' first and last live block."""
    b, s = segment_ids.shape
    nq, nk = s // block_q, s // block_k
    qb = segment_ids.reshape(b, nq, block_q)
    kb = segment_ids.reshape(b, nk, block_k)
    t = dict(
        qmin=qb.min(-1), qmax=qb.max(-1), kmin=kb.min(-1), kmax=kb.max(-1)
    )
    qi, ki = xp.arange(nq)[:, None], xp.arange(nk)[None, :]
    in_window = xp.ones((nq, nk), bool)
    if causal:  # segment ids need sq == sk: the offset is 0
        in_window = in_window & _causal_live(qi, ki, block_q, block_k, 0)
    if window is not None:
        in_window = in_window & _window_live(
            qi, ki, block_q, block_k, 0, window
        )
    live = in_window[None] & ~_ranges_disjoint(
        t["qmin"][:, :, None], t["qmax"][:, :, None],
        t["kmin"][:, None, :], t["kmax"][:, None, :],
    )
    t["kfirst"], t["klast"] = _span(xp, live, 2)  # (B, nq)
    t["qfirst"], t["qlast"] = _span(xp, live, 1)  # (B, nk)
    return dict(t, live=live, in_window=in_window)


@dataclasses.dataclass(frozen=True)
class _TileTable:
    """Layout of the one int32 table the kernels prefetch into SMEM: four
    sections of B * nq entries (per q block: smallest id, largest id,
    first and last live k block), then four of B * nk (per k block: the
    same, with its first and last live q block). One dimension, so SMEM
    pads it once."""

    batch: int
    nq: int
    nk: int

    Q_SECTIONS = ("qmin", "qmax", "kfirst", "klast")
    K_SECTIONS = ("kmin", "kmax", "qfirst", "qlast")

    def pack(self, tables) -> jax.Array:
        return jnp.concatenate(
            [
                tables[name].reshape(-1).astype(jnp.int32)
                for name in self.Q_SECTIONS + self.K_SECTIONS
            ]
        )

    def _at(self, tab, name: str, b, i):
        """Entry of block ``i`` of batch row ``b`` in section ``name``."""
        if name in self.Q_SECTIONS:
            section, n, base = self.Q_SECTIONS.index(name), self.nq, 0
        else:
            section, n = self.K_SECTIONS.index(name), self.nk
            base = len(self.Q_SECTIONS) * self.batch * self.nq
        return tab[base + (section * self.batch + b) * n + i]

    def dead(self, tab, b, qi, ki):
        """Tile (qi, ki) of batch row b is masked whole by segment ids."""
        return _ranges_disjoint(
            self._at(tab, "qmin", b, qi), self._at(tab, "qmax", b, qi),
            self._at(tab, "kmin", b, ki), self._at(tab, "kmax", b, ki),
        )

    def clamp_k(self, tab, b, qi, ki):
        """``ki`` pulled into q block qi's live span."""
        return jnp.clip(
            ki, self._at(tab, "kfirst", b, qi), self._at(tab, "klast", b, qi)
        )

    def clamp_q(self, tab, b, ki, qi):
        """``qi`` pulled into k block ki's live span."""
        return jnp.clip(
            qi, self._at(tab, "qfirst", b, ki), self._at(tab, "qlast", b, ki)
        )


def _segment_tile_table(segment_ids, block_q, block_k, causal, window):
    """The layout and the packed table of these ids, built inside the
    caller's jit (a few reductions over (B, S) int32)."""
    b, s = segment_ids.shape
    layout = _TileTable(b, s // block_q, s // block_k)
    return layout, layout.pack(
        _tile_tables(jnp, segment_ids, block_q, block_k, causal, window)
    )


def segment_tile_counts(
    segment_ids, *, window, block_q=None, block_k=None
) -> tuple[int, int]:
    """How often the skip engages on host ``segment_ids`` (B, S) under
    causal attention with ``window``: ``(tiles_in_window, tiles_run)``
    summed over the batch rows, one head's worth — the tiles the causal
    edge and the window leave, and those of them the kernels compute. The
    same tables and predicate the kernels use, over numpy."""
    ids = np.asarray(segment_ids)
    bq, bk = _default_blocks(ids.shape[1], ids.shape[1], True)
    t = _tile_tables(np, ids, block_q or bq, block_k or bk, True, window)
    return ids.shape[0] * int(t["in_window"].sum()), int(t["live"].sum())


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(
    *refs, block_q: int, block_k: int, seq_q: int, seq_k: int,
    causal: bool, scale: float, num_k_blocks: int,
    tiles: _TileTable | None, heads_q: int, window: int | None = None,
):
    if tiles is not None:
        (tab_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kr = pl.program_id(2)  # restricted index: kr-th block of the window span
    offset = seq_k - seq_q
    nk = _window_grid_k(window, block_q, block_k, num_k_blocks)
    if window is None:
        ki = kr
    else:
        ki = kr + _first_k_block(
            qi, offset, window, block_q, block_k, nk, num_k_blocks
        )

    @pl.when(kr == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # End-aligned causal semantics (matches the XLA path's tril(k=sk-sq)):
    # query i attends keys j <= i + (sk - sq).
    live = (
        _causal_live(qi, ki, block_q, block_k, offset) if causal else ki >= 0
    )
    if window is not None:
        live = live & _window_live(qi, ki, block_q, block_k, offset, window)
    if tiles is not None:
        live = live & ~tiles.dead(tab_ref, pl.program_id(0) // heads_q, qi, ki)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = _tile_logits(
            q, k, qi, ki, block_q, block_k, offset, causal, scale, window
        )
        s = _segment_masked(s, qseg_ref, kseg_ref, block_k)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kr == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m_ref[...] + jnp.log(l), (o_ref.shape[1], NUM_LANES)
        )


def _segment_operands(segment_ids, sq: int, sk: int):
    """Broadcast (B, S) segment ids into the kernel layouts: q ids
    lane-replicated (B, Sq, NUM_LANES), kv ids sublane-replicated
    (B, NUM_SUBLANES, Sk)."""
    b = segment_ids.shape[0]
    seg = segment_ids.astype(jnp.int32)
    qseg = jax.lax.broadcast_in_dim(seg, (b, sq, NUM_LANES), (0, 1))
    kseg = jax.lax.broadcast_in_dim(seg, (b, NUM_SUBLANES, sk), (0, 2))
    return qseg, kseg


def _check_segment_ids(segment_ids, b: int, sq: int, sk: int) -> None:
    if segment_ids is None:
        return
    if sq != sk:
        raise ValueError(
            "segment_ids needs sq == sk (one id array covers both sides)"
        )
    if segment_ids.shape != (b, sq):
        raise ValueError(
            f"segment_ids shape {segment_ids.shape} != {(b, sq)}"
        )


def _block_maps(sq, sk, block_q, block_k, window, heads_q, tiles):
    """The index maps' streamed block: ``k_block(h, qi, kr, *tab)`` for a
    grid over q blocks (forward, dq), ``q_block(h, ki, qr, *tab)`` for one
    over k blocks (dkv). The restricted index of a windowed grid becomes
    the actual block (windowed kernels DMA only the ~window-span blocks);
    ``tab`` is the prefetched tile table, there only with segment ids: a
    dead step before or after the row's live span then names the block
    its neighbour fetched, so the pipeline issues no DMA for it."""
    num_q_blocks, num_k_blocks = sq // block_q, sk // block_k
    nk_w = _window_grid_k(window, block_q, block_k, num_k_blocks)
    nq_w = _window_grid_q(window, block_q, block_k, num_q_blocks)

    def k_block(h, qi, kr, *tab):
        ki = kr
        if window is not None:
            ki = kr + _first_k_block(
                qi, sk - sq, window, block_q, block_k, nk_w, num_k_blocks
            )
        if tab:
            ki = tiles.clamp_k(tab[0], h // heads_q, qi, ki)
        return ki

    def q_block(h, ki, qr, *tab):
        qi = qr
        if window is not None:
            qi = qr + _first_q_block(
                ki, sk - sq, window, block_q, block_k, nq_w, num_q_blocks
            )
        if tab:
            qi = tiles.clamp_q(tab[0], h // heads_q, ki, qi)
        return qi

    return k_block, q_block


def _pallas(
    kernel, grid, in_specs, out_specs, out_shape, scratch_shapes,
    prefetch: bool,
):
    """``pl.pallas_call`` over ``grid``. With ``prefetch`` the first
    operand is the tile table: prefetched into SMEM, handed to every
    index map after the grid indices and to the kernel ahead of its
    other refs. Without it the call is the plain one."""
    if not prefetch:
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            interpret=INTERPRET,
        )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        interpret=INTERPRET,
    )


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    scale: float | None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    return_lse: bool = False,
    segment_ids: jax.Array | None = None,
    window: int | None = None,
):
    """(B, Sq, H, D) attention with GQA head broadcast, Pallas forward."""
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    scale = (d**-0.5) if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash attention needs seq lengths divisible by block sizes: "
            f"sq={sq} block_q={block_q}, sk={sk} block_k={block_k}; "
            "pad sequences or use impl='xla'"
        )
    if hq % hk:
        raise ValueError(f"q heads {hq} not divisible by kv heads {hk}")
    _check_segment_ids(segment_ids, b, sq, sk)
    group = hq // hk

    # (B, S, H, D) -> (B*H, S, D): one grid row per (batch, q-head); K/V
    # stay at their kv-head count — the index map does the GQA broadcast.
    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)

    num_k_blocks = sk // block_k
    nk_w = _window_grid_k(window, block_q, block_k, num_k_blocks)
    grid = (b * hq, sq // block_q, nk_w)
    tiles = None
    operands = [qt, kt, vt]
    if segment_ids is not None:
        tiles, table = _segment_tile_table(
            segment_ids, block_q, block_k, causal, window
        )
        operands = [table, *operands, *_segment_operands(segment_ids, sq, sk)]

    k_block, _ = _block_maps(sq, sk, block_q, block_k, window, hq, tiles)

    def kv_row(h, qi, kr, *tab):
        # grid row h = batch * hq + q_head; its KV row in the (b*hk) array
        return (h // hq) * hk + (h % hq) // group, k_block(h, qi, kr, *tab), 0

    def q_row(h, qi, kr, *tab):
        return h, qi, 0

    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_q=sq,
        seq_k=sk,
        causal=causal,
        scale=scale,
        num_k_blocks=num_k_blocks,
        tiles=tiles,
        heads_q=hq,
        window=window,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_row),
        pl.BlockSpec((1, block_k, d), kv_row),
        pl.BlockSpec((1, block_k, d), kv_row),
    ]
    if tiles is not None:
        in_specs += [
            pl.BlockSpec(
                (1, block_q, NUM_LANES),
                lambda h, qi, kr, *tab: (h // hq, qi, 0),
            ),
            pl.BlockSpec(
                (1, NUM_SUBLANES, block_k),
                lambda h, qi, kr, *tab: (
                    h // hq, 0, k_block(h, qi, kr, *tab)
                ),
            ),
        ]
    out, lse = _pallas(
        kernel,
        grid,
        in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_row),
            pl.BlockSpec((1, block_q, NUM_LANES), q_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * hq, sq, NUM_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denominator
        ],
        prefetch=tiles is not None,
    )(*operands)
    out = out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
    if return_lse:
        return out, lse[:, :, 0]
    return out


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _probs(s, lse_col):
    """p = exp(s - lse), zeroed for fully-masked rows.

    A row with no live keys has lse = NEG_INF, and ``NEG_INF - NEG_INF``
    would make every masked entry exp(0) = 1. The forward emits 0 for such
    rows (a constant), so their correct gradient contribution is exactly 0.
    """
    return jnp.where(lse_col > NEG_INF / 2, jnp.exp(s - lse_col), 0.0)


def _dq_kernel(
    *refs, block_q: int, block_k: int, seq_q: int, seq_k: int,
    causal: bool, scale: float, num_k_blocks: int,
    tiles: _TileTable | None, heads_q: int, window: int | None = None,
):
    if tiles is not None:
        (tab_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kr = pl.program_id(2)
    offset = seq_k - seq_q
    nk = _window_grid_k(window, block_q, block_k, num_k_blocks)
    if window is None:
        ki = kr
    else:
        ki = kr + _first_k_block(
            qi, offset, window, block_q, block_k, nk, num_k_blocks
        )

    @pl.when(kr == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (
        _causal_live(qi, ki, block_q, block_k, offset) if causal else ki >= 0
    )
    if window is not None:
        live = live & _window_live(qi, ki, block_q, block_k, offset, window)
    if tiles is not None:
        live = live & ~tiles.dead(tab_ref, pl.program_id(0) // heads_q, qi, ki)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = _tile_logits(
            q, k, qi, ki, block_q, block_k, offset, causal, scale, window
        )
        s = _segment_masked(s, qseg_ref, kseg_ref, block_k)
        p = _probs(s, lse_ref[0][:, :1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0][:, :1])
        dq_acc[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kr == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    *refs, block_q: int, block_k: int, seq_q: int, seq_k: int,
    causal: bool, scale: float, num_q_blocks: int,
    tiles: _TileTable | None, heads_q: int, window: int | None = None,
):
    if tiles is not None:
        (tab_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qseg_ref = kseg_ref = None
    ki = pl.program_id(1)
    qr = pl.program_id(2)
    offset = seq_k - seq_q
    nq = _window_grid_q(window, block_q, block_k, num_q_blocks)
    if window is None:
        qi = qr
    else:
        qi = qr + _first_q_block(
            ki, offset, window, block_q, block_k, nq, num_q_blocks
        )

    @pl.when(qr == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (
        _causal_live(qi, ki, block_q, block_k, offset) if causal else qi >= 0
    )
    if window is not None:
        live = live & _window_live(qi, ki, block_q, block_k, offset, window)
    if tiles is not None:
        live = live & ~tiles.dead(tab_ref, pl.program_id(0) // heads_q, qi, ki)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = _tile_logits(
            q, k, qi, ki, block_q, block_k, offset, causal, scale, window
        )
        s = _segment_masked(s, qseg_ref, kseg_ref, block_k)
        p = _probs(s, lse_ref[0][:, :1])  # (block_q, block_k)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qr == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, g, causal, scale,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    segment_ids: jax.Array | None = None,
    window: int | None = None,
):
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    scale = (d**-0.5) if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    group = hq // hk

    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    ot = out.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    gt = g.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    # delta_i = rowsum(dO_i * O_i): cheap elementwise; XLA fuses it.
    delta = jnp.sum(
        gt.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1
    )
    # Lane-replicate the per-row stats so their blocks tile legally (see
    # NUM_LANES above).
    lse_l = jnp.broadcast_to(lse[:, :, None], (b * hq, sq, NUM_LANES))
    delta_l = jnp.broadcast_to(delta[:, :, None], (b * hq, sq, NUM_LANES))
    num_q_blocks = sq // block_q
    num_k_blocks = sk // block_k
    nk_w = _window_grid_k(window, block_q, block_k, num_k_blocks)
    nq_w = _window_grid_q(window, block_q, block_k, num_q_blocks)
    tiles = None
    operands = [qt, kt, vt, gt, lse_l, delta_l]
    if segment_ids is not None:
        tiles, table = _segment_tile_table(
            segment_ids, block_q, block_k, causal, window
        )
        operands = [table, *operands, *_segment_operands(segment_ids, sq, sk)]

    def kv_row(h):
        return (h // hq) * hk + (h % hq) // group

    k_block, q_block = _block_maps(
        sq, sk, block_q, block_k, window, hq, tiles
    )

    common = dict(
        block_q=block_q,
        block_k=block_k,
        seq_q=sq,
        seq_k=sk,
        causal=causal,
        scale=scale,
        tiles=tiles,
        heads_q=hq,
        window=window,
    )

    def dq_q_row(h, qi, kr, *tab):
        return h, qi, 0

    def dq_kv_row(h, qi, kr, *tab):
        return kv_row(h), k_block(h, qi, kr, *tab), 0

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), dq_q_row),
        pl.BlockSpec((1, block_k, d), dq_kv_row),
        pl.BlockSpec((1, block_k, d), dq_kv_row),
        pl.BlockSpec((1, block_q, d), dq_q_row),
        pl.BlockSpec((1, block_q, NUM_LANES), dq_q_row),
        pl.BlockSpec((1, block_q, NUM_LANES), dq_q_row),
    ]
    if tiles is not None:
        dq_in_specs += [
            pl.BlockSpec(
                (1, block_q, NUM_LANES),
                lambda h, qi, kr, *tab: (h // hq, qi, 0),
            ),
            pl.BlockSpec(
                (1, NUM_SUBLANES, block_k),
                lambda h, qi, kr, *tab: (
                    h // hq, 0, k_block(h, qi, kr, *tab)
                ),
            ),
        ]
    dq = _pallas(
        functools.partial(_dq_kernel, num_k_blocks=num_k_blocks, **common),
        (b * hq, num_q_blocks, nk_w),
        dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), dq_q_row),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        prefetch=tiles is not None,
    )(*operands)

    # dK/dV per *query* head (b*hq rows): several q heads share one KV head,
    # and revisiting an output block from non-consecutive grid rows is not
    # allowed — group-sum afterwards instead.
    def dkv_q_row(h, ki, qr, *tab):
        return h, q_block(h, ki, qr, *tab), 0

    def dkv_kv_row(h, ki, qr, *tab):
        return kv_row(h), ki, 0

    def dkv_out_row(h, ki, qr, *tab):
        return h, ki, 0

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), dkv_q_row),
        pl.BlockSpec((1, block_k, d), dkv_kv_row),
        pl.BlockSpec((1, block_k, d), dkv_kv_row),
        pl.BlockSpec((1, block_q, d), dkv_q_row),
        pl.BlockSpec((1, block_q, NUM_LANES), dkv_q_row),
        pl.BlockSpec((1, block_q, NUM_LANES), dkv_q_row),
    ]
    if tiles is not None:
        dkv_in_specs += [
            pl.BlockSpec(
                (1, block_q, NUM_LANES),
                lambda h, ki, qr, *tab: (
                    h // hq, q_block(h, ki, qr, *tab), 0
                ),
            ),
            pl.BlockSpec(
                (1, NUM_SUBLANES, block_k),
                lambda h, ki, qr, *tab: (h // hq, 0, ki),
            ),
        ]
    dk_q, dv_q = _pallas(
        functools.partial(_dkv_kernel, num_q_blocks=num_q_blocks, **common),
        (b * hq, num_k_blocks, nq_w),
        dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), dkv_out_row),
            pl.BlockSpec((1, block_k, d), dkv_out_row),
        ],
        out_shape=[
            # f32: the group-sum below must accumulate in full precision —
            # bf16 kernel outputs would round before the reduction.
            jax.ShapeDtypeStruct((b * hq, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hq, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        prefetch=tiles is not None,
    )(*operands)

    dq = dq.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
    dk = (
        dk_q.reshape(b, hk, group, sk, d).sum(axis=2).transpose(0, 2, 1, 3)
    ).astype(k.dtype)
    dv = (
        dv_q.reshape(b, hk, group, sk, d).sum(axis=2).transpose(0, 2, 1, 3)
    ).astype(v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public op
# --------------------------------------------------------------------------


def _default_blocks(
    sq: int, sk: int, segments: bool = False
) -> tuple[int, int]:
    """Block sizes by sequence length, measured on v5e: bigger blocks
    amortize grid overhead once the sequence is long enough (512 wins at
    >=4k, 256 at >=1k, 128 below). Packed rows (``segments``) of 8192
    and more take 1024-wide k blocks: at (2, 8192, 32/8, 128) under a
    4096 window, 512 x 1024 skips fewer tiles than 512 x 512 (30 %
    against 35 %) and still takes a tenth less time, in 0.6 of the grid
    steps; smaller blocks skip more and lose more (PERF.md §6, PR 28)."""

    def pick(s):
        for cand in (512, 256, 128):
            if s >= 4096 and cand == 512 and s % cand == 0:
                return cand
            if s >= 1024 and cand == 256 and s % cand == 0:
                return cand
        return 128

    bq, bk = pick(sq), pick(sk)
    if segments and sk >= 8192 and sk % 1024 == 0:
        bk = 1024
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    window: int | None = None,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Flash attention; ``segment_ids`` (B, S) masks cross-segment
    attention for packed sequences (requires sq == sk). ``window``
    restricts each query to the last ``window`` keys (sliding-window /
    Mistral-style local attention; requires ``causal=True``) — blocks
    entirely below the window edge are skipped, so cost is O(S·W)."""
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    bq, bk = _default_blocks(
        q.shape[1], k.shape[1], segment_ids is not None
    )
    return _flash_forward(
        q, k, v, causal, scale, block_q or bq, block_k or bk,
        segment_ids=segment_ids, window=window,
    )


def _fwd(q, k, v, causal, scale, block_q, block_k, window, segment_ids):
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    bq, bk = _default_blocks(
        q.shape[1], k.shape[1], segment_ids is not None
    )
    out, lse = _flash_forward(
        q, k, v, causal, scale, block_q or bq, block_k or bk,
        return_lse=True, segment_ids=segment_ids, window=window,
    )
    return out, (q, k, v, out, lse, segment_ids)


def _bwd(causal, scale, block_q, block_k, window, res, g):
    q, k, v, out, lse, segment_ids = res
    bq, bk = _default_blocks(
        q.shape[1], k.shape[1], segment_ids is not None
    )
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, g, causal, scale, block_q or bq, block_k or bk,
        segment_ids=segment_ids, window=window,
    )
    return dq, dk, dv, None


flash_attention.defvjp(_fwd, _bwd)
