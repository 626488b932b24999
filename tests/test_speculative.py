"""Speculative decoding: greedy output must be TOKEN-IDENTICAL to the
target model's plain greedy decode, for any draft model — the draft
changes speed, never output (models/speculative.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig, generate
from tensorflowonspark_tpu.models.speculative import speculative_generate


@pytest.fixture(scope="module")
def target_and_draft():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    target = Llama(cfg)
    t_params = target.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32)
    )["params"]
    # a genuinely different (smaller) draft — random weights, so it
    # disagrees with the target often: exercises low-acceptance paths
    dcfg = LlamaConfig.tiny(
        dtype=jnp.float32,
        remat=False,
        hidden_size=64,
        intermediate_size=128,
        num_layers=1,
        num_heads=2,
        num_kv_heads=1,
    )
    draft = Llama(dcfg)
    d_params = draft.init(
        jax.random.PRNGKey(1), jnp.zeros((2, 16), jnp.int32)
    )["params"]
    return target, t_params, draft, d_params


@pytest.mark.parametrize("k", [1, 3, 4])
def test_speculative_matches_plain_greedy(target_and_draft, k):
    target, t_params, draft, d_params = target_and_draft
    prompt = jax.random.randint(
        jax.random.PRNGKey(7), (3, 10), 0, target.cfg.vocab_size
    ).astype(jnp.int32)
    plain = generate(target, t_params, prompt, max_new_tokens=12)
    spec = speculative_generate(
        target, t_params, draft, d_params, prompt, max_new_tokens=12, k=k
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))


def test_speculative_self_draft_all_accepted(target_and_draft):
    """Draft == target: every proposal accepted (the upper-bound path,
    and the one that exercises the draft-cache final-slot feed)."""
    target, t_params, _, _ = target_and_draft
    prompt = jax.random.randint(
        jax.random.PRNGKey(9), (2, 8), 0, target.cfg.vocab_size
    ).astype(jnp.int32)
    plain = generate(target, t_params, prompt, max_new_tokens=15)
    spec = speculative_generate(
        target, t_params, target, t_params, prompt, max_new_tokens=15, k=4
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))


def test_speculative_eos_semantics(target_and_draft):
    """EOS contract identical to generate(): identical tokens through
    each row's first EOS, eos-filled afterwards, early exit."""
    target, t_params, draft, d_params = target_and_draft
    prompt = jax.random.randint(
        jax.random.PRNGKey(11), (2, 6), 0, target.cfg.vocab_size
    ).astype(jnp.int32)
    ref = np.asarray(generate(target, t_params, prompt, max_new_tokens=10))
    eos = int(ref[0, 3])  # a token the plain decode actually emits
    plain = generate(target, t_params, prompt, max_new_tokens=10, eos_id=eos)
    spec = speculative_generate(
        target, t_params, draft, d_params, prompt, max_new_tokens=10, k=3,
        eos_id=eos,
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))


def test_speculative_mixed_length_prompts(target_and_draft):
    """Right-padded prompts + prompt_lengths: rows decode from their own
    true lengths, exactly like generate's padded path."""
    target, t_params, draft, d_params = target_and_draft
    prompt = jax.random.randint(
        jax.random.PRNGKey(13), (3, 9), 0, target.cfg.vocab_size
    ).astype(jnp.int32)
    lengths = jnp.asarray([4, 9, 6], jnp.int32)
    plain = generate(
        target, t_params, prompt, max_new_tokens=11, prompt_lengths=lengths
    )
    spec = speculative_generate(
        target, t_params, draft, d_params, prompt, max_new_tokens=11, k=3,
        prompt_lengths=lengths,
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))


def test_speculative_mesh_sharded_matches_single_device(target_and_draft):
    """Speculative + mesh: TP/DP-sharded target with a replicated draft
    must still be token-identical to single-device speculative (and so
    to plain greedy) — serving at scale keeps the exactness contract."""
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    target, t_params, draft, d_params = target_and_draft
    mesh = make_mesh({"data": 4, "model": 2})
    prompt = jax.random.randint(
        jax.random.PRNGKey(17), (4, 10), 0, target.cfg.vocab_size
    ).astype(jnp.int32)
    plain = generate(target, t_params, prompt, max_new_tokens=9)
    spec = speculative_generate(
        target, t_params, draft, d_params, prompt, max_new_tokens=9, k=3,
        mesh=mesh,
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))

    # mixed-length + EOS under the mesh: per-row lengths shard on
    # 'data' and per-row early exit must survive the sharded caches
    lengths = jnp.asarray([4, 10, 7, 5], jnp.int32)
    eos = int(np.asarray(plain)[0, 2])
    plain_me = generate(
        target, t_params, prompt, max_new_tokens=9,
        prompt_lengths=lengths, eos_id=eos,
    )
    spec_me = speculative_generate(
        target, t_params, draft, d_params, prompt, max_new_tokens=9, k=3,
        prompt_lengths=lengths, eos_id=eos, mesh=mesh,
    )
    np.testing.assert_array_equal(np.asarray(plain_me), np.asarray(spec_me))

    with pytest.raises(ValueError, match="data"):
        speculative_generate(
            target, t_params, draft, d_params, prompt[:3],
            max_new_tokens=4, k=2, mesh=mesh,
        )


def test_speculative_accept_preserves_target_distribution():
    """Monte-Carlo check of the rejection rule (speculative_accept):
    whatever the draft distribution, each emitted position must be
    distributed as the TARGET distribution. Fixed seed — deterministic,
    not a flaky statistical test."""
    from tensorflowonspark_tpu.models.speculative import speculative_accept

    v, k, n = 12, 3, 4000
    rng = np.random.default_rng(0)
    # deliberately mismatched target/draft distributions
    t_probs = rng.dirichlet(np.ones(v) * 0.7, size=(1, k + 1)).astype(
        np.float32
    )
    d_probs = rng.dirichlet(np.ones(v) * 0.7, size=(1, k)).astype(np.float32)

    @jax.jit
    def one(key):
        kd, kv = jax.random.split(key)
        # drafts sampled FROM the draft distribution, as in the decoder
        drafts = jax.random.categorical(
            kd, jnp.log(jnp.asarray(d_probs)), axis=-1
        ).astype(jnp.int32)
        emit, accepted = speculative_accept(
            kv, jnp.asarray(t_probs), jnp.asarray(d_probs), drafts
        )
        return emit, accepted

    keys = jax.random.split(jax.random.PRNGKey(42), n)
    emits, accepts = jax.vmap(one)(keys)
    emits = np.asarray(emits)[:, 0]  # (n, k+1)
    accepts = np.asarray(accepts)[:, 0]  # (n,)

    # position 0 is ALWAYS emitted (either an accepted draft or the
    # j=0 residual), so its empirical distribution must match the
    # target's position-0 distribution
    counts = np.bincount(emits[:, 0], minlength=v) / n
    tv = 0.5 * np.abs(counts - t_probs[0, 0]).sum()
    assert tv < 0.05, f"total variation {tv:.3f} vs target at position 0"

    # position 1, conditioned on draft 0 accepted, must match the
    # target's position-1 distribution
    sel = emits[accepts >= 1, 1]
    counts1 = np.bincount(sel, minlength=v) / len(sel)
    tv1 = 0.5 * np.abs(counts1 - t_probs[0, 1]).sum()
    assert tv1 < 0.07, f"total variation {tv1:.3f} at position 1"

    # sanity: both accept and reject paths actually exercised
    assert 0 < (accepts == 0).sum() < n
    assert (accepts >= 1).sum() > n // 10


def test_speculative_accept_self_draft_always_accepts():
    """q == p: acceptance probability is 1 for every draft, and the
    bonus token is sampled from the target's k-th distribution."""
    from tensorflowonspark_tpu.models.speculative import speculative_accept

    v, k = 8, 2
    rng = np.random.default_rng(1)
    p = rng.dirichlet(np.ones(v), size=(1, k + 1)).astype(np.float32)
    q = p[:, :k]
    keys = jax.random.split(jax.random.PRNGKey(7), 500)

    @jax.jit
    def one(key):
        kd, kv = jax.random.split(key)
        drafts = jax.random.categorical(
            kd, jnp.log(jnp.asarray(q)), axis=-1
        ).astype(jnp.int32)
        return speculative_accept(
            kv, jnp.asarray(p), jnp.asarray(q), drafts
        )[1]

    accepts = np.asarray(jax.vmap(one)(keys))[:, 0]
    np.testing.assert_array_equal(accepts, k)


def test_speculative_sampling_end_to_end(target_and_draft):
    """temperature > 0 runs the sampled path end to end: the first
    emitted token's empirical distribution matches the target's
    softmax at the prompt's last position (fixed seed, deterministic)."""
    target, t_params, draft, d_params = target_and_draft
    prompt = jax.random.randint(
        jax.random.PRNGKey(21), (1, 6), 0, target.cfg.vocab_size
    ).astype(jnp.int32)
    temp = 1.5
    logits = target.apply({"params": t_params}, prompt)[0, -1]
    p_ref = np.asarray(jax.nn.softmax(logits / temp))

    # one device call: 300 identical rows sample independently
    # (categorical noise is per-row), giving 300 first-token draws
    n = 300
    tiled = jnp.tile(prompt, (n, 1))
    toks = speculative_generate(
        target, t_params, draft, d_params, tiled,
        max_new_tokens=2, k=2, temperature=temp,
        rng=jax.random.PRNGKey(1000),
    )
    firsts = np.asarray(toks)[:, 0].tolist()
    counts = np.bincount(firsts, minlength=target.cfg.vocab_size) / n
    # coarse TV bound: 256-vocab with n=300 draws concentrates on the
    # high-probability tokens; compare only where p_ref has real mass
    mask = p_ref > 0.01
    tv = 0.5 * np.abs(counts[mask] - p_ref[mask]).sum()
    assert tv < 0.15, f"total variation {tv:.3f}"
    assert len(set(firsts)) > 3  # actually sampling, not argmaxing


def test_speculative_int8_target_composes(target_and_draft):
    """The serving-stack combination: an int8
    weight-only target verified against an fp draft still emits exactly
    the int8 target's own greedy tokens (exactness is relative to
    whatever model the target IS — quantized here)."""
    from tensorflowonspark_tpu.ops.quant import quantize_tree

    target, t_params, draft, d_params = target_and_draft
    q_params = quantize_tree(t_params, min_size=1024)
    prompt = jax.random.randint(
        jax.random.PRNGKey(23), (2, 8), 0, target.cfg.vocab_size
    ).astype(jnp.int32)
    plain = generate(target, q_params, prompt, max_new_tokens=8)
    spec = speculative_generate(
        target, q_params, draft, d_params, prompt, max_new_tokens=8, k=3
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))


def test_speculative_validations(target_and_draft):
    target, t_params, draft, d_params = target_and_draft
    prompt = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="k must be"):
        speculative_generate(
            target, t_params, draft, d_params, prompt, 4, k=0
        )
    with pytest.raises(ValueError, match="max_seq_len"):
        speculative_generate(
            target, t_params, draft, d_params, prompt,
            target.cfg.max_seq_len, k=4,
        )


def test_speculative_composes_with_window_and_int8_kv():
    """Speculative decode under a sliding-window target with an int8 KV
    cache must still be token-identical to that target's plain greedy
    decode (the draft changes speed, never output — including through
    the round-4 cache features)."""
    cfg = LlamaConfig.tiny(
        dtype=jnp.float32,
        remat=False,
        sliding_window=5,
        kv_cache_dtype="int8",
    )
    target = Llama(cfg)
    t_params = target.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32)
    )["params"]
    dcfg = LlamaConfig.tiny(
        dtype=jnp.float32,
        remat=False,
        hidden_size=64,
        intermediate_size=128,
        num_layers=1,
        num_heads=2,
        num_kv_heads=1,
        sliding_window=5,
    )
    draft = Llama(dcfg)
    d_params = draft.init(
        jax.random.PRNGKey(1), jnp.zeros((2, 16), jnp.int32)
    )["params"]
    prompt = jnp.asarray([[1, 2, 3, 4], [9, 8, 7, 6]], jnp.int32)
    want = np.asarray(generate(target, t_params, prompt, 12))
    got = np.asarray(
        speculative_generate(
            target, t_params, draft, d_params, prompt, 12, k=3
        )
    )
    np.testing.assert_array_equal(got, want)
