"""Attention op tests: XLA path semantics + Pallas kernel numerics
(interpreter mode on CPU; the same kernel compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import flash_attention as fa
from tensorflowonspark_tpu.ops.attention import _xla_attention, dot_product_attention


def _qkv(b=2, sq=256, sk=256, hq=4, hk=4, d=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hk, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hk, d), dtype)
    return q, k, v


def test_xla_attention_causal():
    q, k, v = _qkv(sq=8, sk=8, d=4)
    out = _xla_attention(q, k, v, causal=True)
    # position 0 attends only to itself: out[0] == v[0] (softmax of 1 element)
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(v[:, 0]), rtol=1e-5
    )


def test_xla_attention_gqa():
    q, k, v = _qkv(hq=8, hk=2, sq=16, sk=16, d=8)
    out = _xla_attention(q, k, v)
    assert out.shape == q.shape
    # GQA must equal manually-repeated full MHA
    k_full = jnp.repeat(k, 4, axis=2)
    v_full = jnp.repeat(v, 4, axis=2)
    ref = _xla_attention(q, k_full, v_full)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_xla(causal, monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv()
    out_flash = fa._flash_forward(q, k, v, causal, None)
    out_ref = _xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_ref), rtol=2e-3, atol=2e-3
    )


def test_flash_gqa_matches_xla(monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(hq=8, hk=2)
    out_flash = fa._flash_forward(q, k, v, True, None)
    out_ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_ref), rtol=2e-3, atol=2e-3
    )


def test_flash_gqa_multibatch_kv_rows(monkeypatch):
    """The BlockSpec kv-row index map must land each (batch, q-head) grid
    row on ITS batch's kv head — wrong arithmetic reads another batch's
    K/V, which only shows up with b > 1 and asymmetric heads."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(b=3, hq=6, hk=3, sq=128, sk=128)
    out_flash = fa._flash_forward(q, k, v, False, None)
    out_ref = _xla_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_ref), rtol=2e-3, atol=2e-3
    )


def test_flash_causal_cross_attention_alignment(monkeypatch):
    """sq != sk causal: flash must match XLA's end-aligned tril(k=sk-sq)."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(sq=128, sk=256)
    out_flash = fa._flash_forward(q, k, v, True, None)
    out_ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_ref), rtol=2e-3, atol=2e-3
    )


def test_flash_rejects_ragged_seq(monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(sq=192, sk=192)
    with pytest.raises(ValueError, match="divisible"):
        fa._flash_forward(q, k, v, False, None)


def test_flash_grad_matches_xla(monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(sq=128, sk=128)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, None) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-3
        )


@pytest.mark.parametrize(
    "kw",
    [
        dict(hq=8, hk=2, sq=128, sk=128),  # GQA: dk/dv group-sum path
        dict(sq=128, sk=256),  # causal cross-length (offset != 0)
        dict(b=3, hq=6, hk=3, sq=128, sk=128, d=32),  # multibatch + GQA
    ],
)
def test_flash_grad_variants_match_xla(kw, monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(**kw)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, None) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-3
        )


def test_flash_grad_fully_masked_rows(monkeypatch):
    """causal with sq > sk leaves the first sq-sk query rows with NO live
    keys. The forward emits 0 for them (a constant), so their grads must be
    exactly 0 and must not pollute dk/dv; live rows must match XLA when the
    loss only reads live rows."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(sq=128, sk=64)
    dead = 64  # queries 0..63 attend nothing (offset = -64)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, None)[:, dead:] ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True)[:, dead:] ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_array_equal(np.asarray(g_flash[0][:, :dead]), 0.0)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-3
        )


def test_dot_product_attention_auto_on_cpu():
    q, k, v = _qkv(sq=16, sk=16, d=8)
    out = dot_product_attention(q, k, v, causal=True, impl="auto")
    assert out.shape == q.shape


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_match_xla(causal, monkeypatch):
    """Packed-sequence masking: flash forward+grad == XLA with the same
    segment ids (incl. a GQA head layout and a leading fully-masked
    tile for some rows — segment boundaries not block-aligned)."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(b=2, sq=256, sk=256, hq=4, hk=2)
    rng = np.random.default_rng(0)
    # 3 packed segments per row with uneven, non-block-aligned boundaries
    seg = np.zeros((2, 256), np.int32)
    for b in range(2):
        cuts = np.sort(rng.choice(np.arange(10, 250), size=2, replace=False))
        seg[b, cuts[0]:] = 1
        seg[b, cuts[1]:] = 2
    seg = jnp.asarray(seg)

    def loss_flash(q, k, v):
        return jnp.sum(
            fa.flash_attention(q, k, v, causal, None, None, None, None, seg)
            ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            _xla_attention(q, k, v, causal=causal, segment_ids=seg) ** 2
        )

    out_flash = fa.flash_attention(
        q, k, v, causal, None, None, None, None, seg
    )
    out_ref = _xla_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_ref), rtol=5e-3, atol=5e-3
    )
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-3
        )


def test_dot_product_attention_routes_segments():
    """segment_ids flows through the dispatcher on every impl."""
    q, k, v = _qkv(sq=16, sk=16, d=8)
    seg = jnp.asarray(np.repeat([[0, 1]], 8, axis=1).reshape(1, 16))
    seg = jnp.broadcast_to(seg, (2, 16))
    out = dot_product_attention(q, k, v, segment_ids=seg, impl="xla")
    # queries in segment 0 must ignore keys in segment 1: compare with
    # attention over the first half only
    out_half = dot_product_attention(
        q[:, :8], k[:, :8], v[:, :8], impl="xla"
    )
    np.testing.assert_allclose(
        np.asarray(out[:, :8]), np.asarray(out_half), rtol=1e-5, atol=1e-6
    )


def test_dispatcher_flash_segments_matches_xla(monkeypatch):
    """The dispatcher's flash+segment_ids route (positional arg wiring):
    forcing impl='flash' must equal the xla route bit-for-intent."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(sq=128, sk=128)
    seg = jnp.asarray(
        np.array([[0] * 50 + [1] * 78, [0] * 100 + [1] * 28], np.int32)
    )
    out_flash = dot_product_attention(
        q, k, v, causal=True, segment_ids=seg, impl="flash"
    )
    out_ref = dot_product_attention(
        q, k, v, causal=True, segment_ids=seg, impl="xla"
    )
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_ref), rtol=5e-3, atol=5e-3
    )


# -- tiles that segment ids mask whole are skipped ----------------------


def _ids(*runs):
    """A row of ids from (id, length) runs."""
    return np.concatenate([np.full(n, i, np.int32) for i, n in runs])


# one row of 512 positions each; the kernels run 128-wide blocks on them
_SKIP_ROWS = {
    "on_block_edges": _ids((1, 128), (2, 256), (3, 128)),
    "off_block_edges": _ids((1, 100), (2, 190), (3, 222)),
    "one_document": _ids((1, 512)),
    "several_in_one_block": _ids((1, 40), (2, 30), (3, 50), (4, 136), (5, 256)),
    "trailing_padding": _ids((1, 200), (2, 150), (0, 162)),
    "not_monotone": _ids((1, 128), (2, 128), (1, 256)),
}


def _assert_flash_matches_xla_with_segments(
    q, k, v, seg, window, block_q, block_k
):
    """Forward, dq, dk and dv of the kernels against the XLA path."""
    seg = jnp.asarray(seg)

    def flash(q, k, v):
        return fa.flash_attention(
            q, k, v, True, None, block_q, block_k, window, seg
        )

    def ref(q, k, v):
        return _xla_attention(
            q, k, v, causal=True, segment_ids=seg, window=window
        )

    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)
    out_f, vjp_f = jax.vjp(flash, q, k, v)
    out_r, vjp_r = jax.vjp(ref, q, k, v)
    for name, got, want in zip(
        ("out", "dq", "dk", "dv"), (out_f, *vjp_f(g)), (out_r, *vjp_r(g))
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-3, atol=5e-3,
            err_msg=name,
        )


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("layout", sorted(_SKIP_ROWS))
def test_flash_skips_segment_tiles_and_matches_xla(
    layout, window, monkeypatch
):
    """Each layout of documents over the blocks, with and without the
    window: the kernels leave out the tiles the ids mask whole and still
    give the XLA path's forward, dq, dk and dv. The second batch row is
    laid out otherwise, so a table read for the wrong row would show."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    other = "on_block_edges" if layout == "off_block_edges" else "off_block_edges"
    seg = np.stack([_SKIP_ROWS[layout], _SKIP_ROWS[other]])
    in_window, run = fa.segment_tile_counts(
        seg[:1], window=window, block_q=128, block_k=128
    )
    if layout == "one_document":
        assert run == in_window
    else:
        assert run < in_window
    q, k, v = _qkv(b=2, sq=512, sk=512, hq=2, hk=2, d=32)
    _assert_flash_matches_xla_with_segments(q, k, v, seg, window, 128, 128)


@pytest.mark.parametrize("blocks", [(128, 64), (64, 128)])
@pytest.mark.parametrize("layout", ["off_block_edges", "trailing_padding"])
def test_flash_skips_segment_tiles_gqa_uneven_blocks(
    layout, blocks, monkeypatch
):
    """GQA 4/2 with block_q != block_k, either the larger (one of them
    under one lane tile), under the window."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    seg = np.stack([_SKIP_ROWS[layout], _SKIP_ROWS["not_monotone"]])
    q, k, v = _qkv(b=2, sq=512, sk=512, hq=4, hk=2, d=32)
    _assert_flash_matches_xla_with_segments(q, k, v, seg, 200, *blocks)


def _random_packing(rng, rows, s):
    """Rows of documents with log-uniform lengths; some rows end in id 0
    padding, some reuse an id further on (ids that are not monotone)."""
    seg = np.zeros((rows, s), np.int32)
    for r in range(rows):
        pos, i = 0, 1
        fill = s if rng.random() < 0.5 else int(s * rng.uniform(0.6, 1.0))
        while pos < fill:
            n = int(np.exp(rng.uniform(np.log(4), np.log(s))))
            seg[r, pos:min(pos + n, fill)] = i if rng.random() < 0.8 else 1
            pos, i = pos + n, i + 1
    return seg


@pytest.mark.parametrize("window", [None, 96, 300])
@pytest.mark.parametrize("blocks", [(64, 64), (128, 64), (64, 128)])
def test_segment_tile_counts_against_the_dense_mask(blocks, window):
    """Over random packings: no tile that holds an unmasked pair is ever
    skipped; ``tiles_run`` is what the kernels' own predicate counts over
    the packed table they prefetch; jnp and numpy build the same tables."""
    bq, bk = blocks
    rng = np.random.default_rng([bq, bk, window or 0])
    b, s = 6, 512
    seg = _random_packing(rng, b, s)
    pos = np.arange(s)
    dense = (seg[:, :, None] == seg[:, None, :]) & (pos[:, None] >= pos[None, :])
    if window is not None:
        dense &= pos[:, None] - pos[None, :] < window
    holds_pair = dense.reshape(b, s // bq, bq, s // bk, bk).any(axis=(2, 4))

    tables = fa._tile_tables(np, seg, bq, bk, True, window)
    assert not (holds_pair & ~tables["live"]).any()
    in_window, run = fa.segment_tile_counts(
        seg, window=window, block_q=bq, block_k=bk
    )
    assert in_window == b * int(tables["in_window"].sum())
    assert holds_pair.sum() <= run <= in_window

    layout, table = fa._segment_tile_table(jnp.asarray(seg), bq, bk, True, window)
    table = np.asarray(table)
    kernel_run = 0
    for row in range(b):
        for qi in range(s // bq):
            for ki in range(s // bk):
                live = fa._causal_live(qi, ki, bq, bk, 0)
                if window is not None:
                    live &= fa._window_live(qi, ki, bq, bk, 0, window)
                live &= not layout.dead(table, row, qi, ki)
                kernel_run += bool(live)
                # a live tile is fetched under its own index
                if live:
                    assert layout.clamp_k(table, row, qi, ki) == ki
                    assert layout.clamp_q(table, row, ki, qi) == qi
    assert kernel_run == run


def test_segment_tile_counts_on_the_train_cells_pool():
    """The figure ISSUE 28 rests on: of the tiles the window leaves on
    the rows of ``perfbench/traffic/packed8k.json``, packed as the train
    cell packs them, 30-40 % are masked whole at 512 x 512."""
    from perfbench import harness, traffic
    from tensorflowonspark_tpu.data.packing import pack_batches

    spec = harness.load_json("traffic", "packed8k.json")
    seg = []
    for lengths in traffic.document_rows(spec):
        (row,) = pack_batches(
            [[1] * n for n in lengths], 1, spec["seq_len"], drop_remainder=False
        )
        seg.append(row["segment_ids"][0, :-1])
    seg = np.stack(seg)
    assert seg.shape == (32, 8192)
    in_window, run = fa.segment_tile_counts(
        seg, window=4096, block_q=512, block_k=512
    )
    assert in_window == 32 * 108
    assert 0.30 <= 1 - run / in_window <= 0.40
    # the blocks the kernels take on such rows skip a little less
    assert fa._default_blocks(8192, 8192, True) == (512, 1024)
    in_window, run = fa.segment_tile_counts(seg, window=4096)
    assert in_window == 32 * 60
    assert 0.27 <= 1 - run / in_window <= 0.33


# -- sliding-window (Mistral-style local) attention --------------------


def _naive_window(q, k, v, window):
    """O(S^2) reference: causal AND within the last `window` keys."""
    b, s, h, d = q.shape
    logits = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k))
    logits *= d**-0.5
    qp = np.arange(s)[:, None]
    kp = np.arange(s)[None, :]
    mask = (kp <= qp) & (qp - kp < window)
    logits = np.where(mask[None, None], logits, -1e30)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, np.asarray(v))


@pytest.mark.parametrize("window", [1, 7, 16])
def test_xla_window_matches_naive(window):
    q, k, v = _qkv(sq=16, sk=16, d=8)
    out = _xla_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out), _naive_window(q, k, v, window), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window", [96, 128, 200, 256])
def test_flash_window_matches_xla(window, monkeypatch):
    """Window edges inside, at, and across block boundaries; both the
    forward and all three gradients must match the XLA mask."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv()

    def loss_flash(q, k, v):
        return jnp.sum(
            fa.flash_attention(q, k, v, True, None, None, None, window) ** 2
        )

    def loss_xla(q, k, v):
        return jnp.sum(
            _xla_attention(q, k, v, causal=True, window=window) ** 2
        )

    out_flash = fa.flash_attention(q, k, v, True, None, None, None, window)
    out_xla = _xla_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_xla), rtol=2e-5, atol=2e-5
    )
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


def test_flash_window_composes_with_segments(monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(sq=256, sk=256)
    seg = jnp.concatenate(
        [jnp.zeros((2, 100), jnp.int32), jnp.ones((2, 156), jnp.int32)],
        axis=1,
    )
    out_flash = fa.flash_attention(
        q, k, v, True, None, None, None, 64, seg
    )
    out_xla = _xla_attention(
        q, k, v, causal=True, window=64, segment_ids=seg
    )
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_xla), rtol=2e-5, atol=2e-5
    )


def test_window_validation():
    q, k, v = _qkv(sq=16, sk=16, d=8)
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        dot_product_attention(q, k, v, causal=True, window=0)
    # ring+window is SUPPORTED (window-shortened rotation); without an
    # ambient mesh the ring impl fails on that, not on the window
    with pytest.raises(ValueError, match="mesh"):
        dot_product_attention(q, k, v, causal=True, window=4, impl="ring")


def test_window_grid_restriction_covers_all_live_blocks():
    """The restricted grid must (a) actually shrink — windowed DMA cost
    is O(S·W) — and (b) still cover every causally-live in-window block
    for every q/k block, across awkward alignments."""
    for sq, sk, bq, bk, w in [
        (4096, 4096, 128, 128, 128),
        (4096, 4096, 128, 256, 300),
        (2048, 4096, 256, 128, 96),  # cross-attention offset
        (1024, 1024, 128, 128, 1000),
    ]:
        nqb, nkb = sq // bq, sk // bk
        off = sk - sq
        nk = fa._window_grid_k(w, bq, bk, nkb)
        nq = fa._window_grid_q(w, bq, bk, nqb)
        if w * 4 < sk:
            assert nk < nkb, (nk, nkb)  # the shrink is real
        for qi in range(nqb):
            first = int(fa._first_k_block(qi, off, w, bq, bk, nk, nkb))
            live = [
                ki
                for ki in range(nkb)
                if fa._causal_live(qi, ki, bq, bk, off)
                and fa._window_live(qi, ki, bq, bk, off, w)
            ]
            assert all(first <= ki < first + nk for ki in live), (
                qi, first, nk, live,
            )
        for ki in range(nkb):
            firstq = int(fa._first_q_block(ki, off, w, bq, bk, nq, nqb))
            liveq = [
                qi
                for qi in range(nqb)
                if fa._causal_live(qi, ki, bq, bk, off)
                and fa._window_live(qi, ki, bq, bk, off, w)
            ]
            assert all(firstq <= qi < firstq + nq for qi in liveq), (
                ki, firstq, nq, liveq,
            )


# ---------------------------------------------------------------------------
# Mesh-safe flash: the shard_map route for multi-device TPU processes.
# GSPMD can't partition a pallas_call, so `auto` on a multi-device backend
# must either place the kernel per-shard (ambient mesh published) or fall
# back to XLA — never hand sharded operands to the raw kernel.
# ---------------------------------------------------------------------------

import tensorflowonspark_tpu.ops.attention as attn_mod
from tensorflowonspark_tpu.ops.attention import (
    _flash_mesh,
    mesh_flash_attention,
)
from tensorflowonspark_tpu.parallel import use_mesh


def _tp_mesh():
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    return make_mesh({"data": 2, "fsdp": 2, "model": 2})


@pytest.mark.parametrize("causal", [False, True])
def test_mesh_flash_matches_xla(causal, monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    mesh = _tp_mesh()
    q, k, v = _qkv(b=4, sq=128, sk=128, hq=4, hk=2, d=64)
    out = mesh_flash_attention(q, k, v, mesh, causal=causal)
    ref = _xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_mesh_flash_segments_match_xla(monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    mesh = _tp_mesh()
    q, k, v = _qkv(b=4, sq=128, sk=128, hq=4, hk=2, d=64)
    seg = jnp.concatenate(
        [jnp.zeros((4, 64), jnp.int32), jnp.ones((4, 64), jnp.int32)],
        axis=1,
    )
    out = mesh_flash_attention(
        q, k, v, mesh, causal=True, segment_ids=seg
    )
    ref = _xla_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_mesh_flash_grad_matches_xla(monkeypatch):
    """The flash custom-VJP must transpose cleanly through shard_map:
    per-shard backward kernels, no collectives, sharded cotangents."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    mesh = _tp_mesh()
    q, k, v = _qkv(b=4, sq=128, sk=128, hq=4, hk=2, d=64)

    def loss_mesh(q, k, v):
        return jnp.sum(mesh_flash_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    g_mesh = jax.grad(loss_mesh, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gm, gr in zip(g_mesh, g_ref):
        np.testing.assert_allclose(
            np.asarray(gm), np.asarray(gr), rtol=5e-3, atol=5e-3
        )


def test_auto_routes_to_mesh_flash(monkeypatch):
    """`auto` + multi-device 'TPU' + ambient mesh -> the shard_map route,
    with numerics matching XLA."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    calls = []
    real = attn_mod.mesh_flash_attention

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod, "mesh_flash_attention", spy)
    q, k, v = _qkv(b=4, sq=128, sk=128, hq=4, hk=2, d=64)
    with use_mesh(_tp_mesh()):
        out = dot_product_attention(q, k, v, causal=True, impl="auto")
    assert calls, "auto did not take the mesh flash route"
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_auto_multidevice_without_mesh_falls_back(monkeypatch):
    """No ambient mesh on a multi-device backend: auto must NOT reach any
    pallas path (non-interpret pallas would crash on CPU; GSPMD would
    all-gather on TPU) — it falls back to XLA and stays correct."""
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    q, k, v = _qkv(b=4, sq=128, sk=128, hq=4, hk=2, d=64)
    out = dot_product_attention(q, k, v, causal=True, impl="auto")
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_flash_mesh_gate(monkeypatch):
    """The route gate: shapes/divisibility failures and sharded
    seq/pipe/expert axes all veto the mesh route (-> None)."""
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    mesh = _tp_mesh()
    q, k, v = _qkv(b=4, sq=128, sk=128, hq=4, hk=2, d=64)
    with use_mesh(mesh):
        assert _flash_mesh(q, k, None) is mesh
        # batch not divisible by (data, fsdp) extent
        q3, k3, v3 = _qkv(b=3, sq=128, sk=128, hq=4, hk=2, d=64)
        assert _flash_mesh(q3, k3, None) is None
        # kv heads not divisible by model extent
        qh, kh, vh = _qkv(b=4, sq=128, sk=128, hq=4, hk=1, d=64)
        assert _flash_mesh(qh, kh, None) is None
        # seq not a multiple of 128
        qs, ks_, vs = _qkv(b=4, sq=64, sk=64, hq=4, hk=2, d=64)
        assert _flash_mesh(qs, ks_, None) is None
    # no ambient mesh
    assert _flash_mesh(q, k, None) is None
    # sequence-sharded mesh wants ring/ulysses, not the flash route
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    with use_mesh(make_mesh({"data": 2, "seq": 4})):
        assert _flash_mesh(q, k, None) is None
    # not TPU: route closed even with a mesh
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", False)
    with use_mesh(mesh):
        assert _flash_mesh(q, k, None) is None


def test_ulysses_inner_auto_uses_flash_per_shard(monkeypatch):
    """Inside the ulysses shard_map body the operands are shard-LOCAL:
    auto must resolve to the flash kernel there (not the dispatcher's
    multi-device XLA downgrade, and never a nested shard_map)."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    seen = []
    real = attn_mod._flash_shapes_ok

    def spy(q, k, seg):
        ok = real(q, k, seg)
        seen.append(ok)
        return ok

    monkeypatch.setattr(attn_mod, "_flash_shapes_ok", spy)
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    mesh = make_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(b=4, sq=256, sk=256, hq=4, hk=4, d=64)
    with use_mesh(mesh):
        out = dot_product_attention(q, k, v, causal=True, impl="ulysses")
    assert any(seen), "per-shard auto resolution never saw flash-ok shapes"
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_ring_degenerate_mesh_reenters_auto_dispatch(monkeypatch):
    """impl='ring' on a mesh with seq==1 falls through to the auto
    dispatcher — which must still find the mesh-flash route on a
    batch-sharded multi-device mesh."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    calls = []
    real = attn_mod.mesh_flash_attention

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod, "mesh_flash_attention", spy)
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    mesh = make_mesh({"data": 8})
    q, k, v = _qkv(b=8, sq=128, sk=128, hq=4, hk=2, d=64)
    with use_mesh(mesh):
        out = dot_product_attention(q, k, v, causal=True, impl="ring")
    assert calls, "degenerate ring did not re-enter the mesh-flash route"
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )
