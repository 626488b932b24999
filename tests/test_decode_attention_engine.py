"""The decode step's branch into the decode-attention kernel, through the
engine: the same requests served with the branch taken (told it is on a
TPU, the kernel in the Pallas interpreter) and not taken give the same
tokens and log-probabilities; the two counters say what was read; and each
of the branch's conditions, alone, keeps the einsum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.compute.mesh import make_mesh
from tensorflowonspark_tpu.models.falcon_h1 import FalconH1, FalconH1Config
from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig
from tensorflowonspark_tpu.ops import attention as attn_mod
from tensorflowonspark_tpu.ops import decode_attention as da
from tensorflowonspark_tpu.parallel import use_mesh
from tensorflowonspark_tpu.serving import ContinuousBatcher
from tests.test_decode_attention import CachedAttention, spy_on_the_kernel

# two blocks of 512 a row: the short requests leave the second one dead,
# the long one crosses into it in mid-decode
SEQ = 1024
MODELS = {
    "tiny": lambda: Llama(
        LlamaConfig.tiny(dtype=jnp.float32, remat=False, max_seq_len=SEQ)
    ),
    "tiny-hybrid": lambda: FalconH1(
        FalconH1Config.tiny(dtype=jnp.float32, max_seq_len=SEQ)
    ),
}


def _on_tpu(monkeypatch, on: bool):
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", on)
    monkeypatch.setattr(da, "INTERPRET", on)


def _counter(eng, name):
    return eng.metrics.window()[name]["series"][""]["value"]


@pytest.mark.parametrize("name", MODELS)
def test_engine_serves_the_same_with_and_without_the_kernel(name, monkeypatch):
    model = MODELS[name]()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(1)
    vocab = model.cfg.vocab_size
    requests = [
        (rng.integers(1, vocab, size=p).tolist(), n)
        for p, n in [(3, 9), (7, 12), (5, 6), (506, 12), (2, 10)]
    ]
    calls = spy_on_the_kernel(monkeypatch)
    served, read, span = {}, {}, {}
    for kernel in (False, True):
        _on_tpu(monkeypatch, kernel)
        del calls[:]
        eng = ContinuousBatcher(
            model, params, slots=2, prompt_widths=(8, 512), decode_block=4
        )
        try:
            served[kernel] = [
                eng.submit(p, n, eos_id=-1, return_logprobs=True)
                for p, n in requests
            ]
            read[kernel] = _counter(eng, "engine_decode_kv_positions_read_total")
            span[kernel] = _counter(eng, "engine_decode_kv_positions_span_total")
        finally:
            eng.close()
        assert bool(calls) == kernel, "the branch was not the one asked for"
    for (toks_e, lps_e), (toks_k, lps_k) in zip(served[False], served[True]):
        assert toks_k == toks_e
        np.testing.assert_allclose(lps_k, lps_e, atol=1e-3)
    # the einsum reads every position it spans; the kernel the blocks up
    # to each slot's position: one of two for all but the long request
    assert read[False] == span[False] > 0
    assert span[True] == span[False]
    assert 0.5 * span[True] <= read[True] < 0.75 * span[True]


def _step(cfg, s=1, padded=True):
    """One cached step of width ``s`` on a fresh cache of two rows."""
    shape = (2, s, cfg.num_kv_heads, cfg.head_dim)
    q = jnp.ones((2, s, cfg.num_heads, cfg.head_dim), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (2, s))
    kv = jnp.ones(shape, cfg.dtype)
    mod = CachedAttention(cfg)
    cache = mod.init(jax.random.PRNGKey(0), q, kv, kv, positions, padded)["cache"]
    return mod.apply(
        {"cache": cache}, q, kv, kv, positions, padded, mutable=["cache"]
    )[0]


@pytest.mark.parametrize(
    "case",
    ["kernel", "wide_step", "uniform_rows", "rolling", "int8", "mesh", "cpu"],
)
def test_each_condition_of_the_branch(case, monkeypatch):
    """A padded step of one position on the dense model-dtype cache of a
    single TPU takes the kernel; change any one of those and the einsum
    stays."""
    calls = spy_on_the_kernel(monkeypatch)
    _on_tpu(monkeypatch, case != "cpu")
    if case == "cpu":
        monkeypatch.setattr(da, "INTERPRET", True)
    over = {
        "rolling": dict(sliding_window=16, kv_cache_len=32),
        "int8": dict(kv_cache_dtype="int8"),
    }.get(case, {})
    cfg = LlamaConfig.tiny(dtype=jnp.float32, max_seq_len=64, **over)
    if case == "mesh":
        with use_mesh(make_mesh({"data": 8})):
            out = _step(cfg)
    else:
        out = _step(
            cfg, s=4 if case == "wide_step" else 1,
            padded=case != "uniform_rows",
        )
    assert np.isfinite(np.asarray(out)).all()
    assert bool(calls) == (case == "kernel")


def test_engine_under_a_mesh_keeps_the_einsum(monkeypatch):
    """The engine publishes its mesh while the decode step is traced, so
    a tensor-parallel engine on TPUs keeps the einsum that GSPMD can
    partition, and counts whole rows."""
    model = MODELS["tiny"]()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    calls = spy_on_the_kernel(monkeypatch)
    _on_tpu(monkeypatch, True)
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,),
        mesh=make_mesh({"data": 4, "model": 2}),
    )
    del calls[:]  # the constructor's shape-only trace of a step has no mesh
    try:
        assert len(eng.submit([1, 2, 3], 5)) == 5
        assert _counter(eng, "engine_decode_kv_positions_read_total") == _counter(
            eng, "engine_decode_kv_positions_span_total"
        )
    finally:
        eng.close()
    assert not calls
