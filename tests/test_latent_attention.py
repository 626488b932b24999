"""Latent attention (``models/pangu_moe.py``) and its decode kernel
(``ops/decode_attention.latent_decode_attention``): the absorbed form
against the expanded one, prefill then decode through the cache against
one full pass, and the kernel in the Pallas interpreter against the
einsum over the absorbed form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models.pangu_moe import LatentAttention, PanguMoEConfig
from tensorflowonspark_tpu.ops import attention as attn_mod
from tensorflowonspark_tpu.ops import decode_attention as da

RANK, ROPE = 32, 8
WIDTH = da.latent_entry_width(RANK, ROPE)


def _operands(rows, C, heads, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_lat = jax.random.normal(k[0], (rows, heads, RANK), dtype)
    q_rope = jax.random.normal(k[1], (rows, heads, ROPE), dtype)
    entries = jax.random.normal(k[2], (rows, C, RANK + ROPE), dtype)
    cache = jnp.pad(entries, ((0, 0), (0, 0), (0, WIDTH - RANK - ROPE)))
    return q_lat, q_rope, cache


def _einsum(q_lat, q_rope, cache, lengths, scale):
    """The absorbed form over the whole plane, masked by position."""
    f32 = jnp.float32
    s = (
        jnp.einsum("rhc,rkc->rhk", q_lat.astype(f32), cache[..., :RANK].astype(f32))
        + jnp.einsum(
            "rhc,rkc->rhk", q_rope.astype(f32),
            cache[..., RANK : RANK + ROPE].astype(f32),
        )
    ) * scale
    mask = jnp.arange(cache.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    v = jnp.where(mask.transpose(0, 2, 1), cache[..., :RANK].astype(f32), 0.0)
    return jnp.einsum("rhk,rkc->rhc", p, v)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(da, "INTERPRET", True)


BLOCK, C = 16, 64


@pytest.mark.parametrize("lengths", [
    [1], [BLOCK - 1], [BLOCK], [BLOCK + 1], [C], [1, 15, 16, 17, 33, 64, 40],
], ids=["one", "block-1", "block", "block+1", "whole", "mixed"])
def test_kernel_equals_the_einsum_and_reads_nothing_past_a_length(
    interpret, lengths
):
    """NaN in every position a row has not written: fetched or not, it
    must not reach the result."""
    lengths = jnp.asarray(lengths, jnp.int32)
    q_lat, q_rope, cache = _operands(len(lengths), C, heads=8)
    want = _einsum(q_lat, q_rope, cache, lengths, 0.2)
    poison = jnp.where(
        jnp.arange(C)[None, :, None] >= lengths[:, None, None], jnp.nan, cache
    )
    got = da.latent_decode_attention(
        q_lat, q_rope, poison, lengths, scale=0.2, block_k=BLOCK
    )
    assert got.shape == q_lat.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_kernel_in_bfloat16_and_with_heads_that_are_no_tile(interpret):
    lengths = jnp.asarray([5, 64, 31], jnp.int32)
    q_lat, q_rope, cache = _operands(3, C, heads=5, dtype=jnp.bfloat16, seed=1)
    got = da.latent_decode_attention(
        q_lat, q_rope, cache, lengths, scale=0.25, block_k=32
    )
    assert got.dtype == jnp.bfloat16 and got.shape == (3, 5, RANK)
    want = _einsum(q_lat, q_rope, cache, lengths, 0.25)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.05
    )


def test_kernel_refuses_what_does_not_fit(interpret):
    q_lat, q_rope, cache = _operands(2, C, heads=4)
    lengths = jnp.asarray([3, 4], jnp.int32)
    with pytest.raises(ValueError, match="block_k"):
        da.latent_decode_attention(q_lat, q_rope, cache, lengths, scale=1.0, block_k=48)
    with pytest.raises(ValueError, match="entry"):
        da.latent_decode_attention(
            q_lat, q_rope, cache[..., : RANK + ROPE - 1], lengths, scale=1.0
        )


def test_entry_width_block_rule_and_positions_read():
    """576 values are stored in 640; the latent block rule is the K/V
    rule's (`_largest_block`) and `positions_read` counts its fetches."""
    assert da.latent_entry_width(512, 64) == 640
    assert da.latent_entry_width(32, 8) == 128
    assert da._latent_block_k(3072, 128, 640, 2) == 512
    assert da._latent_block_k(64, 4, 128, 4) == 64
    assert da._latent_block_k(100, 4, 128, 4) is None
    read = da.positions_read([1, 512, 513, 3072, 9999], 3072, None, 512)
    assert read.tolist() == [512, 512, 1024, 3072, 3072]


def test_cache_block_k_takes_the_latent_rule_for_a_latent_config(monkeypatch):
    cfg = PanguMoEConfig(max_seq_len=3072)
    assert da.cache_block_k(cfg) is None  # no TPU here: the einsum
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    assert da.cache_block_k(cfg) == 512
    from tensorflowonspark_tpu.models.llama import LlamaConfig

    assert da.cache_block_k(LlamaConfig.mistral_7b(max_seq_len=2560)) == 512


# -- the module ---------------------------------------------------------------


@pytest.fixture(scope="module")
def attn():
    cfg = PanguMoEConfig.tiny(dtype=jnp.float32)
    layer = LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 24, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (3, 24))
    params = layer.init(jax.random.PRNGKey(1), x, pos)["params"]
    # flax's 0.02 makes flat scores: peak them, and let rope matter
    for name in ("q_b_proj", "kv_a_proj"):
        params[name]["kernel"] = params[name]["kernel"] * 12.0
    params["kv_b_proj"] = params["kv_b_proj"] * 12.0
    return cfg, layer, params, x, pos


def _through_the_cache(layer, params, x, pos, prompt):
    """A call that makes the cache over the first ``prompt`` positions,
    then one position at a time against it (the absorbed form)."""
    out, state = layer.apply(
        {"params": params}, x[:, :prompt], pos[:, :prompt], True,
        mutable=["cache"],
    )
    outs, cache = [out], state["cache"]
    for i in range(prompt, x.shape[1]):
        o, state = layer.apply(
            {"params": params, "cache": cache}, x[:, i : i + 1],
            pos[:, i : i + 1], True, mutable=["cache"],
        )
        outs.append(o)
        cache = state["cache"]
    return jnp.concatenate(outs, axis=1), cache


def test_absorbed_decode_equals_the_expanded_pass(attn):
    cfg, layer, params, x, pos = attn
    full = layer.apply({"params": params}, x, pos)
    got, cache = _through_the_cache(layer, params, x, pos, prompt=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full), atol=2e-5)
    entry = np.asarray(cache["latent"])
    assert entry.shape == (3, cfg.max_seq_len, 128)
    assert not entry[:, :, cfg.kv_lora_rank + cfg.qk_rope_head_dim :].any()
    assert not entry[:, 24:].any() and entry[:, :24, :40].all()


def test_prefill_then_decode_equals_one_full_pass(attn):
    _, layer, params, x, pos = attn
    full = layer.apply({"params": params}, x, pos)
    got, _ = _through_the_cache(layer, params, x, pos, prompt=13)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full), atol=2e-5)


def test_a_chunk_against_the_cache_and_invalid_positions(attn):
    """Several positions against an existing cache take the einsum over
    the absorbed form; a position marked invalid writes no entry."""
    _, layer, params, x, pos = attn
    full = layer.apply({"params": params}, x, pos)
    _, state = layer.apply(
        {"params": params}, x[:, :8], pos[:, :8], True, mutable=["cache"]
    )
    valid = jnp.ones((3, 16), bool).at[:, 12:].set(False)
    out, state = layer.apply(
        {"params": params, "cache": state["cache"]}, x[:, 8:], pos[:, 8:],
        True, None, valid, mutable=["cache"],
    )
    np.testing.assert_allclose(
        np.asarray(out[:, :12]), np.asarray(full[:, 8:20]), atol=2e-5
    )
    entry = np.asarray(state["cache"]["latent"])
    assert entry[:, :20, :40].all() and not entry[:, 20:].any()


def test_the_decode_step_takes_the_kernel_on_a_tpu(attn, monkeypatch):
    """With the dispatch steered as on a TPU, one position a row against
    the cache goes through the kernel (here in the interpreter) and gives
    what the einsum gives; under a mesh it keeps the einsum."""
    _, layer, params, x, pos = attn
    want, _ = _through_the_cache(layer, params, x, pos, prompt=20)
    calls = []
    real = da.latent_decode_attention
    import tensorflowonspark_tpu.models.pangu_moe as pm

    def spy(*a, **kw):
        calls.append(a[2].shape)
        return real(*a, **kw)

    monkeypatch.setattr(pm, "latent_decode_attention", spy)
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    monkeypatch.setattr(da, "INTERPRET", True)
    got, _ = _through_the_cache(layer, params, x, pos, prompt=20)
    assert len(calls) == 4 and calls[0] == (3, 128, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_an_int8_or_rolling_latent_cache_is_refused(attn):
    _, _, params, x, pos = attn
    for over, match in (
        (dict(kv_cache_dtype="int8"), "int8"), (dict(kv_cache_len=64), "rolling"),
    ):
        layer = LatentAttention(PanguMoEConfig.tiny(dtype=jnp.float32, **over))
        with pytest.raises(ValueError, match=match):
            layer.apply({"params": params}, x, pos, True, mutable=["cache"])


def test_auto_keeps_the_einsum_for_a_value_width_that_is_not_the_querys(
    monkeypatch,
):
    """Query-key width 192 and value width 128: the flash kernel takes
    one width from q, so ``auto`` may not choose it and ``flash`` refuses."""
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    q = jnp.zeros((1, 256, 4, 192), jnp.bfloat16)
    v = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    assert attn_mod._flash_shapes_ok(q, q, None)
    assert attn_mod._one_head_width(q, q) and attn_mod._one_head_width(q, None)
    assert not attn_mod._one_head_width(q, v)
    assert attn_mod._local_auto_impl(q, q, None, v) == "xla"
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.parallel import use_mesh

    with use_mesh(make_mesh({"data": 8})):
        qb = jnp.zeros((8, 256, 4, 192), jnp.bfloat16)
        assert attn_mod._flash_mesh(qb, qb, None, qb) is not None
        assert attn_mod._flash_mesh(qb, qb, None, qb[..., :128]) is None
    with pytest.raises(ValueError, match="one head width"):
        attn_mod.dot_product_attention(q, q, v, causal=True, impl="flash")
    out = attn_mod.dot_product_attention(q, q, v, causal=True)
    assert out.shape == (1, 256, 4, 128)
