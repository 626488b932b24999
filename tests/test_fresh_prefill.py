"""A ``decode=True`` call that was handed no cache starts its sequence: it
writes the cache and attends among its own positions
(``dot_product_attention`` over the K/V in hand), where a call that was
handed one runs the einsum over the cache. Held here: the two give the
same hidden states and leave the same cache; the engine's tokens do not
change; the prefill program holds no (width, cache length) array; on a TPU
the fresh call is the flash kernel at the kernel's shapes; and the
engine's two counters say what each dispatched prefill scored."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import decode_cache
from tensorflowonspark_tpu.models.falcon_h1 import FalconH1, FalconH1Config
from tensorflowonspark_tpu.models.llama import Attention, Llama, LlamaConfig
from tensorflowonspark_tpu.models.pangu_moe import PanguMoE, PanguMoEConfig
from tensorflowonspark_tpu.ops import attention as attn_mod
from tensorflowonspark_tpu.serving import ContinuousBatcher
from tests.test_decode_attention import CachedAttention

WIDTH, CHUNK, SEQ = 16, 8, 56  # 56: no model dimension of the tiny configs


def _attention_inputs(cfg, lengths):
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.normal(size=(len(lengths), WIDTH, cfg.hidden_size)), jnp.float32
    )
    positions = jnp.broadcast_to(
        jnp.arange(WIDTH, dtype=jnp.int32), (len(lengths), WIDTH)
    )
    return x, positions, jnp.asarray(lengths, jnp.int32)[:, None]


# Falcon-H1's use of the module: padding marked invalid (it then writes
# nothing) and the keys scaled before RoPE
@pytest.mark.parametrize("falcon", [False, True], ids=["llama", "falcon_h1"])
@pytest.mark.parametrize(
    "cache,window",
    # a window smaller than the width, or none; a rolling cache needs one
    [("model", None), ("model", 5), ("int8", None), ("int8", 5), ("rolling", 5)],
    ids=lambda v: f"window{v}" if isinstance(v, int) else v or "full",
)
def test_whole_prompt_equals_chunks_against_a_cache(falcon, cache, window):
    """Right-padded rows of 16, 9 and 3 real tokens under GQA: the
    whole-prompt call (no cache: among its own positions) against the
    same prompt fed as two chunks into a handed-in cache (the einsum)."""
    over = {
        "model": {},
        "int8": dict(kv_cache_dtype="int8"),
        # >= window + write width - 1, < max_seq_len
        "rolling": dict(kv_cache_len=24),
    }[cache]
    cfg = LlamaConfig.tiny(
        dtype=jnp.float32, remat=False, max_seq_len=SEQ, sliding_window=window,
        key_multiplier=0.5 if falcon else 1.0, **over,
    )
    assert cfg.num_heads > cfg.num_kv_heads > 1
    lengths = [16, 9, 3]
    x, positions, length = _attention_inputs(cfg, lengths)
    valid = positions < length if falcon else None
    mod = Attention(cfg)
    params = mod.init(jax.random.PRNGKey(0), x, positions)["params"]

    def call(variables, lo, hi):
        return mod.apply(
            variables, x[:, lo:hi], positions[:, lo:hi], None, True, True,
            None, None if valid is None else valid[:, lo:hi],
            mutable=["cache"],
        )

    whole, fresh = call({"params": params}, 0, WIDTH)
    shapes = jax.eval_shape(lambda: call({"params": params}, 0, 1)[1]["cache"])
    state = {"cache": decode_cache.init_cache(shapes)}
    pieces = []
    for lo in range(0, WIDTH, CHUNK):
        out, state = call({"params": params, **state}, lo, lo + CHUNK)
        pieces.append(out)
    chunked = jnp.concatenate(pieces, axis=1)
    real = np.asarray(positions < length)
    # int8: the chunks read back what they rounded, the whole-prompt call
    # the K/V in hand, as the stated precision asks
    tol = 3e-2 if cache == "int8" else 2e-5
    np.testing.assert_allclose(
        np.asarray(whole)[real], np.asarray(chunked)[real], atol=tol
    )
    for name, leaf in fresh["cache"].items():
        other = state["cache"][name]
        if leaf.ndim == 0:
            assert int(leaf) == int(other) == WIDTH
            continue
        # written slots: a row's real positions (its padding too, where
        # padding is written, but nothing reads that)
        np.testing.assert_array_equal(
            np.asarray(leaf)[:, :WIDTH][real], np.asarray(other)[:, :WIDTH][real],
            err_msg=name,
        )


MODELS = {
    "tiny": lambda: Llama(
        LlamaConfig.tiny(dtype=jnp.float32, remat=False, max_seq_len=SEQ)
    ),
    "tiny-hybrid": lambda: FalconH1(
        FalconH1Config.tiny(dtype=jnp.float32, max_seq_len=SEQ)
    ),
    "tiny-latent-moe": lambda: PanguMoE(
        PanguMoEConfig.tiny(dtype=jnp.float32, max_seq_len=SEQ)
    ),
}


def _params(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _counter(eng, name):
    return eng.metrics.window()[name]["series"][""]["value"]


def _spans(eng, name):
    return [s for s in eng._tracer.spans() if s.name == name]


@pytest.mark.parametrize("name", ["tiny", "tiny-hybrid"])
def test_engine_tokens_after_a_fresh_prefill(name):
    """Greedy tokens and log-probabilities of an engine whose prefill
    program starts the sequence (bucket widths) against one whose every
    chunk is handed a cache (the einsum, as every prefill ran before)."""
    model = MODELS[name]()
    params = _params(model)
    rng = np.random.default_rng(2)
    requests = [
        (rng.integers(1, model.cfg.vocab_size, size=p).tolist(), n)
        for p, n in [(3, 7), (8, 5), (13, 9), (16, 4), (6, 11)]
    ]
    served = {}
    for kind, kw in (
        ("fresh", dict(prompt_widths=(8, 16))),
        ("chunks", dict(prompt_widths=(16,), prefill_chunk=8)),
    ):
        eng = ContinuousBatcher(model, params, slots=2, decode_block=4, **kw)
        try:
            served[kind] = [
                eng.submit(p, n, eos_id=-1, return_logprobs=True)
                for p, n in requests
            ]
        finally:
            eng.close()
    for (toks_f, lps_f), (toks_c, lps_c) in zip(served["fresh"], served["chunks"]):
        assert toks_f == toks_c
        np.testing.assert_allclose(lps_f, lps_c, atol=2e-4)


def _trailing(text: str, a: int, b: int) -> list:
    """Array types in lowered text whose last two dimensions are a x b."""
    return re.findall(rf"tensor<(?:\d+x)*{a}x{b}x[a-z]\w*>", text)


def test_prefill_program_holds_no_width_by_cache_array():
    """``jit_prefill`` as the engine dispatches it; the chunk program,
    which must score against the cache, is the control."""
    model = MODELS["tiny"]()
    eng = ContinuousBatcher(
        model, _params(model), slots=2, prompt_widths=(WIDTH,), decode_block=4
    )
    seen = []
    real = eng._prefill_fn(WIDTH)
    eng._prefill_cache[WIDTH] = lambda *a: (seen.append(a), real(*a))[1]
    try:
        eng.submit(list(range(1, 12)), 3, eos_id=-1)
        chunk_text = eng._chunk_fn.lower(
            eng._params, eng._single_row_cache(),
            jnp.zeros((1, WIDTH), jnp.int32),
            jnp.arange(WIDTH, dtype=jnp.int32)[None], jnp.zeros((1,), jnp.int32),
            jnp.int32(0), jnp.int32(WIDTH),
        ).as_text()
    finally:
        eng.close()
    text = real.lower(*seen[0]).as_text()
    assert _trailing(text, WIDTH, WIDTH)
    assert not _trailing(text, WIDTH, SEQ)
    assert _trailing(chunk_text, WIDTH, SEQ)


def _resolved_impls(monkeypatch) -> list:
    calls = []
    real = attn_mod._jitted_attention

    def spy(q, k, v, **kw):
        calls.append(kw["impl"])
        return real(q, k, v, **dict(kw, impl="xla"))  # nothing runs a kernel

    monkeypatch.setattr(attn_mod, "_jitted_attention", spy)
    return calls


@pytest.mark.parametrize(
    "width,want", [(64, "xla"), (128, "flash"), (256, "flash"), (512, "flash"),
                   (1024, "flash"), (2048, "flash")],
)
def test_fresh_call_resolves_to_flash_on_a_tpu(width, want, monkeypatch):
    """At the serve cells' head width (128) and GQA, told it is on one
    TPU: the fresh call takes the flash kernel from 128 positions up and
    the plain einsum among its own 64 below; a call that was handed a
    cache reaches ``dot_product_attention`` not at all."""
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    one = jax.devices()[:1]  # the suite's CPU shows eight
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    cfg = LlamaConfig.tiny(
        hidden_size=512, dtype=jnp.bfloat16, max_seq_len=2560, sliding_window=4096
    )
    assert cfg.head_dim == 128
    mod = CachedAttention(cfg)
    q = jax.ShapeDtypeStruct((1, width, cfg.num_heads, 128), cfg.dtype)
    kv = jax.ShapeDtypeStruct((1, width, cfg.num_kv_heads, 128), cfg.dtype)
    positions = jax.ShapeDtypeStruct((1, width), jnp.int32)
    calls = _resolved_impls(monkeypatch)

    def fresh(q, k, v, positions):
        return mod.apply({}, q, k, v, positions, True, mutable=["cache"])

    out, state = jax.eval_shape(fresh, q, kv, kv, positions)
    assert calls == [want]
    assert out.shape == q.shape
    del calls[:]
    jax.eval_shape(
        lambda c, *a: mod.apply({"cache": c}, *a, True, mutable=["cache"]),
        state["cache"], q, kv, kv, positions,
    )
    assert calls == []


def test_counters_of_a_fresh_prefill_and_of_a_chunk():
    """A fresh prefill of width w adds w x w scored and w x C spanned; a
    chunk of width c adds c x C to both; the ``engine.prefill`` spans
    carry the same numbers."""
    model = MODELS["tiny"]()
    params = _params(model)
    scored = "engine_prefill_kv_positions_scored_total"
    span = "engine_prefill_kv_positions_span_total"
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8, 16))
    try:
        assert _counter(eng, scored) == _counter(eng, span) == 0
        eng.submit([1, 2, 3], 2, eos_id=-1)  # width 8
        eng.submit(list(range(1, 12)), 2, eos_id=-1)  # width 16
        assert _counter(eng, scored) == 8 * 8 + 16 * 16
        assert _counter(eng, span) == (8 + 16) * SEQ
        got = [(s.args["kv_scored"], s.args["kv_span"])
               for s in _spans(eng, "engine.prefill")]
        assert got == [(64, 8 * SEQ), (256, 16 * SEQ)]
    finally:
        eng.close()
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(16,), prefill_chunk=8
    )
    try:
        eng.submit(list(range(1, 12)), 2, eos_id=-1)  # two chunks of 8
        assert _counter(eng, scored) == _counter(eng, span) == 2 * 8 * SEQ
        assert all(
            s.args["kv_scored"] == s.args["kv_span"] == 8 * SEQ
            for s in _spans(eng, "engine.prefill")
        )
    finally:
        eng.close()


def _score_shapes(model, params, handed_cache: bool) -> set:
    """Trailing (queries, keys) of every float array a ``decode=True``
    call of WIDTH positions makes, with or without a cache in hand."""
    toks = jnp.zeros((1, WIDTH), jnp.int32)
    positions = jnp.arange(WIDTH, dtype=jnp.int32)[None]

    def call(variables):
        return model.apply(
            variables, toks, positions=positions, decode=True, padded=True,
            mutable=["cache"],
        )

    variables = {"params": params}
    if handed_cache:
        variables["cache"] = decode_cache.init_cache(
            jax.eval_shape(lambda: call({"params": params})[1]["cache"])
        )
    shapes = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                if len(v.aval.shape) >= 2:
                    shapes.add(tuple(v.aval.shape[-2:]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(call)(variables).jaxpr)
    return shapes


@pytest.mark.parametrize("handed_cache", [False, True], ids=["fresh", "handed"])
@pytest.mark.parametrize("name", MODELS)
def test_keys_scored_is_what_the_models_do(name, handed_cache):
    """``decode_cache.keys_scored``, which the engine counts by, against
    the arrays each model's call really makes: scores WIDTH wide where
    the call starts its sequence, cache-long where it was handed one."""
    model = MODELS[name]()
    shapes = _score_shapes(model, _params(model), handed_cache)
    keys = decode_cache.keys_scored(WIDTH, SEQ, handed_cache)
    assert keys == (SEQ if handed_cache else WIDTH)
    assert (WIDTH, keys) in shapes
    assert ((WIDTH, SEQ) in shapes) == handed_cache
