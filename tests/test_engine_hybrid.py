"""The continuous-batching engine over a model that carries recurrent
state beside K/V (tiny Falcon-H1, float32): each request's tokens and
log-probabilities are those of the request alone, whatever the batch did
around it; what the engine cannot do with such state, it refuses."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models.falcon_h1 import FalconH1, FalconH1Config
from tensorflowonspark_tpu.models.llama import generate
from tensorflowonspark_tpu.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def tiny():
    model = FalconH1(FalconH1Config.tiny(dtype=jnp.float32))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    # flax's init leaves dt_bias and A_log at zero: a state that forgets
    # in a few tokens. Give the recurrence a memory of tens of tokens, so
    # that a state carried wrongly shows in the tokens.
    # ... and logits that are not flat (flax's 0.02 makes them 0.08 wide)
    params["lm_head"] = params["lm_head"] * 25.0
    rng = np.random.default_rng(0)
    for n in range(model.cfg.num_layers):
        mixer = params[f"layer{n}"]["mixer"]
        h = mixer["dt_bias"].shape
        mixer["dt_bias"] = jnp.asarray(rng.uniform(-4.0, -2.0, h), jnp.float32)
        mixer["A_log"] = jnp.asarray(rng.uniform(0.0, 1.0, h), jnp.float32)
    return model, params


def _alone(model, params, prompt, n):
    """Tokens of a lone greedy ``generate`` and their log-probabilities
    from one full forward pass (no cache) over prompt and tokens."""
    toks = np.asarray(
        generate(model, params, jnp.asarray([prompt], jnp.int32), n)
    )[0].tolist()
    logits = model.apply(
        {"params": params}, jnp.asarray([prompt + toks[:-1]], jnp.int32)
    )[0]
    logp = jax.nn.log_softmax(logits[len(prompt) - 1 :], axis=-1)
    return toks, [float(logp[i, t]) for i, t in enumerate(toks)]


def _requests(vocab, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(1, vocab, size=p).tolist(), n) for p, n in sizes
    ]


def _serve_all(eng, requests, clients=3):
    """``clients`` threads drawing requests in order, each sending its
    next the moment its last ends: more requests than slots, so rows are
    reused and admissions land between live blocks."""
    out, errors, lock = {}, [], threading.Lock()
    todo = list(enumerate(requests))

    def client():
        try:
            while True:
                with lock:
                    if not todo:
                        return
                    i, (prompt, n) = todo.pop(0)
                out[i] = eng.submit(prompt, n, eos_id=-1, return_logprobs=True)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
        assert not t.is_alive(), "client wedged"
    if errors:
        raise errors[0]
    return [out[i] for i in range(len(requests))]


def _check(model, params, requests, served):
    for (prompt, n), (toks, lps) in zip(requests, served):
        want_toks, want_lps = _alone(model, params, prompt, n)
        assert toks == want_toks, (len(prompt), n)
        np.testing.assert_allclose(lps, want_lps, atol=2e-4)


# prompt and output lengths: several to a prefill width, ends staggered
_SIZES = [(3, 9), (8, 5), (13, 12), (5, 17), (16, 4), (9, 10), (2, 21), (11, 6)]


@pytest.mark.parametrize("decode_block", [1, 8])
def test_churn_serves_each_request_as_alone(tiny, decode_block):
    model, params = tiny
    requests = _requests(model.cfg.vocab_size, _SIZES)
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8, 16),
        decode_block=decode_block,
    )
    try:
        served = _serve_all(eng, requests)
        assert eng.admitted == len(requests) > eng.stats()["slots"]
    finally:
        eng.close()
    _check(model, params, requests, served)


def test_chunked_prefill_with_the_last_chunk_shifted_back(tiny):
    """prefill_chunk 48 under max_seq_len 128: a prompt of 100 tokens
    takes chunks at 0, 48 and, shifted back, 80: positions 80..95 are in
    the cache already and must not reach the recurrent state again."""
    model, params = tiny
    assert model.cfg.max_seq_len == 128
    requests = _requests(
        model.cfg.vocab_size, [(100, 8), (7, 12), (60, 9), (100, 5)], seed=1
    )
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(128,), prefill_chunk=48,
    )
    try:
        served = _serve_all(eng, requests, clients=2)
    finally:
        eng.close()
    _check(model, params, requests, served)


def test_int8_kv_touches_the_planes_only(tiny):
    model, params = tiny
    q_model = FalconH1(
        FalconH1Config.tiny(dtype=jnp.float32, kv_cache_dtype="int8")
    )
    eng = ContinuousBatcher(q_model, params, slots=2, prompt_widths=(8,))
    try:
        prompt = [5, 9, 2, 7]
        toks = eng.submit(prompt, 6, eos_id=-1)
        assert len(toks) == 6
        kinds = eng.stats()["cache_bytes"]
        full = ContinuousBatcher(model, params, slots=2, prompt_widths=(8,))
        try:
            ref = full.stats()["cache_bytes"]
        finally:
            full.close()
        assert kinds["recurrent"] == ref["recurrent"] > 0
        assert kinds["kv"] < ref["kv"]
    finally:
        eng.close()


@pytest.mark.parametrize("what", ["prefix_cache", "prefix_l2", "model_mesh"])
def test_what_recurrent_state_cannot_do_is_refused(tiny, what):
    model, params = tiny
    options = dict(slots=2, prompt_widths=(16,))
    if what == "prefix_cache":
        options.update(prefill_chunk=4, prefix_cache=8)
        match = "state snapshot at the resume position"
    elif what == "prefix_l2":
        options.update(prefill_chunk=4, prefix_l2=object())
        match = "state snapshot at the resume position"
    else:
        from tensorflowonspark_tpu.compute.mesh import make_mesh

        options.update(mesh=make_mesh({"data": 4, "model": 2}))
        match = "no cache sharding for its recurrent leaves"
    with pytest.raises(ValueError, match=match):
        ContinuousBatcher(model, params, **options)


def test_cache_bytes_gauge_sums_to_the_batch_cache(tiny):
    model, params = tiny
    eng = ContinuousBatcher(model, params, slots=3, prompt_widths=(8,))
    try:
        cache = eng._empty_state()[0]
        by_kind = eng.stats()["cache_bytes"]
        assert sum(by_kind.values()) == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(cache)
        )
        cfg = model.cfg
        assert by_kind["kv"] == cfg.num_layers * 2 * 3 * cfg.max_seq_len * (
            cfg.num_kv_heads * cfg.head_dim * 4
        )
        assert by_kind["recurrent"] == cfg.num_layers * 3 * (
            cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state * 4
            + (cfg.mamba_d_conv - 1) * cfg.conv_dim * 4
        )
        series = eng.metrics.window()["engine_cache_bytes"]["series"]
        assert {
            k: v["value"] for k, v in series.items()
        } == {'{kind="%s"}' % k: float(v) for k, v in by_kind.items()}
    finally:
        eng.close()
