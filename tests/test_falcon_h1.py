"""models/falcon_h1.py against the plain reference
(perfbench/reference_falcon_h1.py, which imports nothing of the program)
on seeded weights with every multiplier away from 1, in float32."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference_falcon_h1 as reference
from perfbench import weights_falcon_h1 as weights
from tensorflowonspark_tpu.models.falcon_h1 import (
    FalconH1,
    FalconH1Config,
    from_hf_config,
)

CATALOG_ROW = "/opt/skills/guides/model-configs/architectures.jsonl"


def hf_dict(cfg: FalconH1Config) -> dict:
    """The public config.json keys of a program config: what the
    reference and the seeded weights read."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_seq_len,
        "ssm_multipliers": list(cfg.ssm_multipliers),
        "mlp_multipliers": list(cfg.mlp_multipliers),
        **{
            k: getattr(cfg, k)
            for k in (
                "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
                "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
                "mamba_chunk_size", "embedding_multiplier",
                "lm_head_multiplier", "key_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "ssm_in_multiplier", "ssm_out_multiplier",
            )
        },
    }


@pytest.fixture(scope="module")
def tiny():
    cfg = FalconH1Config.tiny(dtype=jnp.float32)
    hf = hf_dict(cfg)
    params = weights.make_params(hf, weights.seed_key(11), jnp.float32)
    def get_leaf(name):
        node = params
        for part in name.split("/"):
            node = node[part]
        return node

    assert from_hf_config(hf, dtype=jnp.float32) == cfg
    return cfg, hf, params, get_leaf


def test_full_forward_is_the_reference(tiny):
    cfg, hf, params, get_leaf = tiny
    assert all(
        m != 1.0
        for m in (
            cfg.embedding_multiplier, cfg.lm_head_multiplier,
            cfg.key_multiplier, cfg.attention_in_multiplier,
            cfg.attention_out_multiplier, cfg.ssm_in_multiplier,
            cfg.ssm_out_multiplier, *cfg.ssm_multipliers,
            *cfg.mlp_multipliers,
        )
    )
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 29)).astype(np.int32)
    got = FalconH1(cfg).apply({"params": params}, jnp.asarray(tokens))
    at = np.broadcast_to(np.arange(29, dtype=np.int32), (2, 29))
    want = reference.serve_logits(hf, get_leaf, tokens, at, blocks=1)
    assert float(jnp.std(want)) > 0.1  # the comparison is of something
    np.testing.assert_allclose(got, want, atol=1e-4)
    # the reduced head reads the same logits
    toks = np.stack([tokens, np.roll(tokens, 1, axis=1)], axis=-1)
    best, top, lse, picked = reference.serve_readings(
        hf, get_leaf, tokens, at, toks, blocks=1, vocab_blocks=4
    )
    np.testing.assert_allclose(best, jnp.max(want, -1), atol=1e-6)
    np.testing.assert_array_equal(top, jnp.argmax(want, -1))
    np.testing.assert_allclose(lse, jax.nn.logsumexp(want, -1), atol=1e-5)
    np.testing.assert_allclose(
        picked, jnp.take_along_axis(want, jnp.asarray(toks), -1), atol=1e-6
    )


def test_prefill_then_cached_decode_is_the_full_forward(tiny):
    """Rows of different lengths in one right-padded batch: prefill under
    the validity mask, then one position at a time through the cache, the
    rows at their own positions."""
    cfg, _, params, _ = tiny
    model = FalconH1(cfg)
    rng = np.random.default_rng(1)
    lengths, width, steps = np.asarray([13, 7, 20]), 20, 6
    total = width + steps
    tokens = rng.integers(0, cfg.vocab_size, (3, total)).astype(np.int32)
    full = [
        model.apply({"params": params}, jnp.asarray(tokens[i : i + 1, : n + steps]))[0]
        for i, n in enumerate(lengths)
    ]
    prompt = np.zeros((3, width), np.int32)
    for i, n in enumerate(lengths):
        prompt[i, :n] = tokens[i, :n]
    positions = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), (3, width))
    valid = positions < jnp.asarray(lengths)[:, None]
    logits, state = model.apply(
        {"params": params}, jnp.asarray(prompt), positions=positions,
        decode=True, padded=True, valid=valid, mutable=["cache"],
    )
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(logits[i, :n], full[i][:n], atol=1e-4)
    cache = state["cache"]
    step = jax.jit(
        lambda c, t, p: model.apply(
            {"params": params, "cache": c}, t, positions=p, decode=True,
            padded=True, mutable=["cache"],
        )
    )
    for s in range(steps):
        pos = jnp.asarray(lengths + s)[:, None]
        tok = jnp.asarray(
            [[tokens[i, n + s]] for i, n in enumerate(lengths)], jnp.int32
        )
        lg, state = step(cache, tok, pos)
        cache = state["cache"]
        for i, n in enumerate(lengths):
            np.testing.assert_allclose(lg[i, 0], full[i][n + s], atol=1e-4)
    # the recurrent leaves are there, float32 state beside the window
    leaves = {
        jax.tree_util.keystr(p): v
        for p, v in jax.tree_util.tree_leaves_with_path(cache)
    }
    ssm = [v for k, v in leaves.items() if k.endswith("['ssm']")]
    conv = [v for k, v in leaves.items() if k.endswith("['conv']")]
    assert len(ssm) == len(conv) == cfg.num_layers
    assert ssm[0].dtype == jnp.float32 and ssm[0].shape == (
        3, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    )
    assert conv[0].shape == (3, cfg.mamba_d_conv - 1, cfg.conv_dim)


def test_padding_run_through_the_scan_is_wrong(tiny):
    """Why the engine passes ``valid``: without it the state after a
    padded prefill is not the prompt's."""
    cfg, _, params, _ = tiny
    model = FalconH1(cfg)
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :9] = np.random.default_rng(2).integers(1, cfg.vocab_size, 9)
    positions = jnp.arange(16, dtype=jnp.int32)[None]
    states = []
    for valid in (positions < 9, None):
        _, st = model.apply(
            {"params": params}, jnp.asarray(prompt), positions=positions,
            decode=True, padded=True, valid=valid, mutable=["cache"],
        )
        states.append(st["cache"]["layer0"]["mixer"]["ssm"])
    assert float(jnp.abs(states[0] - states[1]).max()) > 1e-3


def test_published_config_counts_430_million_a_layer():
    with open(CATALOG_ROW) as f:
        row = next(
            json.loads(l) for l in f if '"Falcon-H1-34B-Instruct"' in l
        )
    cfg = from_hf_config(row["config"])
    assert cfg.num_layers == 72 and cfg.conv_dim == 5120
    # the class's defaults are this file, the position limit apart
    assert dataclasses.replace(cfg, max_seq_len=4096) == FalconH1Config()
    one = dataclasses.replace(cfg, num_layers=1, max_seq_len=8)
    shapes = jax.eval_shape(
        lambda: FalconH1(one).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
    )["params"]
    layer = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["layer0"]))
    assert layer == 430_120_032
    rest = sum(
        int(np.prod(s.shape))
        for k, v in shapes.items() if k != "layer0"
        for s in jax.tree.leaves(v)
    )
    assert rest == 2 * 261_120 * 5120 + 5120
    with pytest.raises(ValueError, match="not supported"):
        from_hf_config({**row["config"], "mamba_norm_before_gate": True})
