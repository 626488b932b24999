"""Laguna (``models/laguna.py``) at a tiny size: the model against the
benchmark's plain reference on seeded weights (one full pass; a prefill
wider than the windowed layers' ring, then decode through the cache as the
ring wraps; through the serving engine), YaRN's frequencies against a
transcription of HF's formula, the partial rotary embedding and the
per-head gate against explicit forms, what ``from_hf_config`` refuses, the
engine's K/V counters by kind of layer, and the traced programs of the
accepted models at the defaults of ``llama``'s new fields."""

import dataclasses
import hashlib
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, reference_laguna, weights_laguna
from perfbench.drivers import _llama, serve_laguna
from tensorflowonspark_tpu.models import laguna, llama, zoo
from tensorflowonspark_tpu.models.laguna import Laguna, LagunaConfig, from_hf_config
from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig, RopeScaling
from tensorflowonspark_tpu.models.pangu_moe import PanguMoE, PanguMoEConfig
from tensorflowonspark_tpu.models.phi4_flash import Phi4Flash, Phi4FlashConfig
from tensorflowonspark_tpu.serving import ContinuousBatcher
from tests.test_engine_hybrid import _serve_all

ATOL = 2e-4


@pytest.fixture(scope="module")
def seeded():
    """The rehearsal configuration with the benchmark's seeded weights:
    the dense full layer, three windowed, a full one; 4 and 6 query heads
    over 2 K/V heads of 16; a window of 8; 16 experts, top 4, a shared
    one."""
    config = harness.load_json("configs", "tiny-laguna.json")
    cfg = _llama.model_keys(config)
    model = serve_laguna.build_model(config)
    key = weights_laguna.seed_key(7)
    params = weights_laguna.make_params(cfg, key, jnp.float32)
    return cfg, model, params, key


def _reference(cfg, key, seqs, toks):
    at = np.broadcast_to(np.arange(seqs.shape[1], dtype=np.int32), seqs.shape)
    get_leaf = serve_laguna.reference_leaves(cfg, key, jnp.float32)
    return [np.asarray(x) for x in reference_laguna.serve_readings(
        cfg, get_leaf, seqs, at, toks, blocks=2, vocab_blocks=4)]


def published(layers=40):
    """The cell's configuration file with its per-layer lists grown back
    to ``layers`` by the published pattern."""
    hf = _llama.model_keys(harness.load_json("configs", "laguna-xs2-d5-serve.json"))
    kinds = laguna.pattern(layers)
    return dict(
        hf, num_hidden_layers=layers, layer_types=list(kinds),
        mlp_layer_types=["dense"] + ["sparse"] * (layers - 1),
        num_attention_heads_per_layer=[48 if k == laguna.FULL else 64 for k in kinds],
    )


def test_defaults_are_the_published_config():
    cfg = LagunaConfig()
    assert from_hf_config(published(), max_seq_len=cfg.max_seq_len) == cfg
    five = from_hf_config(harness.load_json("configs", "laguna-xs2-d5-serve.json"))
    assert five.heads_per_layer == (48, 64, 64, 64, 48)
    assert five.mlp_layer_types == ("dense",) + ("sparse",) * 4
    full, window = cfg.attention(0), cfg.attention(1)
    assert (full.num_heads, full.rotary_dim, full.sliding_window, full.kv_cache_len) == (
        48, 64, None, None)
    assert full.rope_scaling.kind == "yarn" and full.rope_theta == 500000.0
    assert (window.num_heads, window.rotary_dim, window.sliding_window,
            window.kv_cache_len, window.rope_scaling, window.rope_theta) == (
        64, None, 512, 512, None, 10000.0)
    assert full.attention_output_gate == window.attention_output_gate == "per_head"


@pytest.mark.parametrize("change,match", [
    ({"moe_apply_router_weight_on_input": True}, "moe_apply_router_weight_on_input"),
    ({"moe_router_logit_softcapping": 30.0}, "softcapping"),
    ({"gating": "elementwise"}, "per-head"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3", "rope_theta": 5e5},
                          "sliding_attention": {"rope_theta": 1e4}}}, "rope_type"),
    ({"rope_parameters": {"full_attention": {"rope_theta": 5e5},
                          "sliding_attention": {"rope_type": "yarn", "rope_theta": 1e4,
                                                "factor": 4,
                                                "original_max_position_embeddings": 64}}},
     "rope_type"),
    ({"num_attention_heads_per_layer": [48, 60, 64, 64, 48]}, "no multiple"),
    ({"layer_types": ["full_attention"] * 4}, "do not cover"),
])
def test_what_is_not_computed_is_refused_not_dropped(change, match):
    hf = dict(harness.load_json("configs", "laguna-xs2-d5-serve.json"), **change)
    with pytest.raises(ValueError, match=match):
        from_hf_config(hf)


def test_seeded_leaves_are_the_models_tree(seeded):
    _, model, params, _ = seeded
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert jax.tree.map(lambda x: x.shape, init) == jax.tree.map(lambda x: x.shape, params)
    assert params["layer1"]["attn"]["g_proj"]["kernel"].shape == (64, 6)  # a value a head
    assert params["layer4"]["attn"]["q_proj"]["kernel"].shape == (64, 4 * 16)


def test_model_equals_the_plain_reference_then_through_the_cache(seeded):
    """Logits of one full pass against ``perfbench/reference_laguna.py``
    (YaRN written out, the window a mask, a loop over every expert), which
    shares no code with the program; then a prefill of 12 positions, wider
    than the windowed layers' ring of 8, and one position at a time through
    the cache as the ring wraps four times."""
    cfg, model, params, key = seeded
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, cfg["vocab_size"], size=(2, 44)).astype(np.int32)
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(seqs)))
    toks = np.argmax(logits, axis=-1).astype(np.int32)[..., None]
    best, top, lse, got = _reference(cfg, key, seqs, toks)
    assert (top == toks[..., 0]).mean() > 0.97  # ties aside, the same best token
    np.testing.assert_allclose(got[..., 0], logits.max(-1), atol=ATOL)
    np.testing.assert_allclose(lse, np.asarray(jax.nn.logsumexp(logits, -1)), atol=ATOL)
    pos = jnp.broadcast_to(jnp.arange(44, dtype=jnp.int32), (2, 44))
    out, state = model.apply(
        {"params": params}, jnp.asarray(seqs[:, :12]), positions=pos[:, :12],
        decode=True, padded=True, valid=pos[:, :12] < 12, mutable=["cache"],
    )
    np.testing.assert_allclose(np.asarray(out), logits[:, :12], atol=ATOL)
    cache = state["cache"]
    ring, plane = cache["layer1"]["attn"], cache["layer0"]["attn"]
    # the ring holds the last 8 positions of the 12, each at position % 8;
    # the full layers a dense plane of max_seq_len
    np.testing.assert_array_equal(np.asarray(ring["pos"][0]), [8, 9, 10, 11, 4, 5, 6, 7])
    assert ring["k"].shape == (2, 8, 2, 16) and plane["k"].shape == (2, 128, 2, 16)
    assert "pos" not in plane
    for i in range(12, 44):
        out, state = model.apply(
            {"params": params, "cache": cache}, jnp.asarray(seqs[:, i : i + 1]),
            positions=pos[:, i : i + 1], decode=True, padded=True, mutable=["cache"],
        )
        cache = state["cache"]
        np.testing.assert_allclose(np.asarray(out[:, 0]), logits[:, i], atol=ATOL)


def test_engine_prefill_then_decode_equals_the_reference(seeded):
    """Through ``ContinuousBatcher``: more requests than slots, prompts
    right-padded to a width wider than the ring (each ring keeps the last
    window of the *real* positions), decoded past several ring wraps;
    every served log-probability against the reference's full pass over
    the prompt and the served tokens."""
    cfg, model, params, key = seeded
    rng = np.random.default_rng(3)
    sizes = [(5, 30), (21, 40), (13, 9), (30, 33)]
    requests = [(rng.integers(1, cfg["vocab_size"], size=p).tolist(), n) for p, n in sizes]
    assert max(p + n for p, n in sizes) > 7 * cfg["sliding_window"]
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(16, 32), decode_block=4)
    try:
        served = _serve_all(eng, requests)
    finally:
        eng.close()
    for (prompt, n), (toks, lps) in zip(requests, served):
        row = np.asarray([prompt + toks], np.int32)
        want = np.zeros((1, row.shape[1], 1), np.int32)
        want[0, len(prompt) - 1 : -1, 0] = toks
        _, _, lse, got = _reference(cfg, key, row, want)
        ref_lp = (got[0, :, 0] - lse[0])[len(prompt) - 1 : -1]
        assert len(toks) == n
        np.testing.assert_allclose(lps, ref_lp, atol=1e-3)


def _hf_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """HF's ``_compute_yarn_parameters``, transcribed to NumPy."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv = (inv_freq_interpolation * (1 - extrapolation_factor)
           + inv_freq_extrapolation * extrapolation_factor)
    return inv, (low, high)


def test_yarn_frequencies_are_hfs_at_the_published_numbers():
    scaling = LagunaConfig().full_rope_scaling
    want, ramp = _hf_yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert ramp == (5, 16)
    got = np.asarray(llama._scaled_rope_freqs(64, 500000.0, scaling))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    ref, af = reference_laguna.yarn_inv_freq(64, 500000.0, {
        "factor": 64, "original_max_position_embeddings": 4096, "beta_fast": 64,
        "beta_slow": 1, "attention_factor": 1.4158883083359672})
    np.testing.assert_allclose(np.asarray(ref), want, rtol=1e-6)
    assert af == scaling.cos_sin_scale == 1.4158883083359672
    # below the ramp the frequency is kept, above it divided by the factor
    base = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:5], base[:5], rtol=1e-6)
    np.testing.assert_allclose(got[16:], base[16:] / 64, rtol=1e-6)
    # no attention factor given: 0.1 ln(factor) + 1, as HF's get_mscale
    assert RopeScaling(kind="yarn", factor=64.0).cos_sin_scale == pytest.approx(
        0.1 * math.log(64) + 1)


def test_channels_past_the_rotary_dim_pass_untouched():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 7, 3, 128)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(7, dtype=jnp.int32), (2, 7)) * 1000
    scaling = LagunaConfig().full_rope_scaling
    out = llama.rope(x, pos, 500000.0, scaling, rotary_dim=64)
    np.testing.assert_array_equal(np.asarray(out[..., 64:]), np.asarray(x[..., 64:]))
    # the first 64 as a head of 64 rotates them, cos and sin times the factor
    np.testing.assert_allclose(
        np.asarray(out[..., :64]), np.asarray(llama.rope(x[..., :64], pos, 500000.0, scaling)),
        rtol=1e-6)
    inv = llama._scaled_rope_freqs(64, 500000.0, scaling)
    ang = np.asarray(pos, np.float32)[..., None, None] * np.asarray(inv)  # float32, as rope's
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    c, s = np.cos(ang) * scaling.cos_sin_scale, np.sin(ang) * scaling.cos_sin_scale
    np.testing.assert_allclose(np.asarray(out[..., :32]), x1 * c - x2 * s, rtol=2e-5, atol=2e-5)
    # the whole head (rotary_dim None or equal) traces what it always did
    whole = llama.rope(x, pos, 10000.0)
    assert jax.make_jaxpr(lambda a: llama.rope(a, pos, 10000.0, None, 128))(x).pretty_print() \
        == jax.make_jaxpr(lambda a: llama.rope(a, pos, 10000.0))(x).pretty_print()
    np.testing.assert_array_equal(np.asarray(llama.rope(x, pos, 10000.0, None, 128)),
                                  np.asarray(whole))


def test_per_head_gate_is_an_explicit_per_head_product():
    """The gated layer's output is the ungated layer's heads, each times
    its own ``sigmoid(x W_g)``, through ``o_proj``: the ungated heads are
    read where they enter ``o_proj``."""
    cfg = LagunaConfig.tiny(dtype=jnp.float32).attention(1)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 5, cfg.hidden_size)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32), (2, 5))
    attn = llama.Attention(cfg)
    params = attn.init(jax.random.PRNGKey(0), x, pos)["params"]
    assert params["g_proj"]["kernel"].shape == (cfg.hidden_size, cfg.num_heads)
    got = attn.apply({"params": params}, x, pos)
    seen = {}

    def o_proj_input(next_fun, args, kwargs, context):
        if context.module.name == "o_proj":
            seen["x"] = np.asarray(args[0])
        return next_fun(*args, **kwargs)

    plain = llama.Attention(dataclasses.replace(cfg, attention_output_gate=False))
    with nn.intercept_methods(o_proj_input):
        plain.apply({"params": {k: v for k, v in params.items() if k != "g_proj"}}, x, pos)
    heads = seen["x"].reshape(2, 5, cfg.num_heads, cfg.head_dim)
    g = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(params["g_proj"]["kernel"]))))
    gated = np.stack([heads[:, :, h] * g[:, :, h : h + 1] for h in range(cfg.num_heads)], 2)
    want = gated.reshape(2, 5, -1) @ np.asarray(params["o_proj"]["kernel"])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_engine_counts_kv_reads_by_kind(seeded):
    """Three rings of 8 and two dense planes of 128: a decode step of a
    slot reads each ring whole and each plane whole (the einsum here). The
    new counter sums each kind's layers; the accepted one stays their mean
    a layer."""
    _, model, params, _ = seeded
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(16,), decode_block=1)
    try:
        assert dict(eng._kv_groups) == {(8, 8, 8): 3, (128, None, 128): 2}
        eng.submit(list(range(1, 12)), 5, eos_id=-1)
        reg = eng.metrics.window()
        steps = reg["engine_decode_steps_total"]["series"][""]["delta"]
        by_kind = reg["engine_decode_kv_positions_read_by_kind_total"]["series"]
        assert by_kind['{kind="window"}']["delta"] == steps * 2 * 3 * 8
        assert by_kind['{kind="full"}']["delta"] == steps * 2 * 2 * 128
        assert reg["engine_decode_kv_positions_read_total"]["series"][""]["delta"] == (
            pytest.approx(steps * 2 * (3 * 8 + 2 * 128) / 5))
    finally:
        eng.close()


def test_one_kind_models_count_by_kind_too():
    """A model of one kind of layer publishes that kind alone, the sum
    over its layers: the accepted mean times the layers."""
    model = Llama(LlamaConfig.tiny(dtype=jnp.float32, remat=False, max_seq_len=56))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8,), decode_block=1)
    try:
        eng.submit([1, 2, 3], 4, eos_id=-1)
        reg = eng.metrics.window()
        by_kind = reg["engine_decode_kv_positions_read_by_kind_total"]["series"]
        assert set(by_kind) == {'{kind="full"}'}
        assert by_kind['{kind="full"}']["delta"] == 2 * reg[
            "engine_decode_kv_positions_read_total"]["series"][""]["delta"]
    finally:
        eng.close()


def test_zoo_builds_the_tiny_model():
    entry = zoo.build("laguna_xs2", tiny=True)
    batch = entry.make_input(2)
    params = entry.model.init(jax.random.PRNGKey(0), batch["tokens"][:, :-1])["params"]
    assert np.isfinite(float(entry.make_loss()(params, batch)))
    assert isinstance(entry.model, Laguna)


# sha256 of the jaxprs (first 16 hex digits) of more accepted programs'
# forward, fresh prefill and decode step at a tiny size, as traced before
# ``rotary_dim``, the ``yarn`` kind and the per-head gate existed (beside
# tests/test_phi4_flash.py's Mistral, Falcon-H1 and Solar-Open2). A change
# that means to alter these programs updates them here.
JAXPRS = {
    "llama3-scaled": ("b446e3b665cc22f6", "41664a653335daf2", "590834e34df10762"),
    "linear-scaled": ("8184e3fc38c4bf7d", "47c8571d1bbbe70e", "1598da2ec288ac1b"),
    "pangu_moe": ("19d739b4e891ee3a", "2b1565200087b443", "81d569a407a955ca"),
    "phi4_flash": ("4c1fc295a28396ce", "b4ad12c0925b027b", "bb1b9e908c2903b6"),
}


@pytest.mark.parametrize("name", sorted(JAXPRS))
def test_more_accepted_models_trace_what_they_traced(name):
    model = {
        "llama3-scaled": lambda: Llama(LlamaConfig.tiny(dtype=jnp.float32, rope_scaling=RopeScaling(
            kind="llama3", factor=8.0, original_max_seq_len=32))),
        "linear-scaled": lambda: Llama(LlamaConfig.tiny(dtype=jnp.float32, rope_scaling=RopeScaling(
            kind="linear", factor=4.0))),
        "pangu_moe": lambda: PanguMoE(PanguMoEConfig.tiny(dtype=jnp.float32)),
        "phi4_flash": lambda: Phi4Flash(Phi4FlashConfig.tiny(dtype=jnp.float32)),
    }[name]()
    toks = jnp.zeros((2, 8), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), toks))["params"]

    def fresh(p):
        return model.apply({"params": p}, toks, positions=pos, decode=True, padded=True,
                           valid=pos < 5, mutable=["cache"])

    def step(p, c):
        return model.apply({"params": p, "cache": c}, toks[:, :1], positions=pos[:, :1] + 5,
                           decode=True, padded=True, mutable=["cache"])

    _, st = jax.eval_shape(fresh, params)
    got = tuple(
        hashlib.sha256(str(j).encode()).hexdigest()[:16] for j in (
            jax.make_jaxpr(lambda p: model.apply({"params": p}, toks))(params),
            jax.make_jaxpr(fresh)(params),
            jax.make_jaxpr(step)(params, st["cache"]),
        )
    )
    assert got == JAXPRS[name]
