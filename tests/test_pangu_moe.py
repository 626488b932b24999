"""openPangu-Ultra-MoE (``models/pangu_moe.py``) at a tiny size: the
model against the benchmark's plain reference on seeded weights, through
the serving engine as alone, what the engine refuses with a latent cache,
and the routed counters."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, reference_pangu_moe, weights_pangu_moe
from perfbench.drivers import serve_pangu_moe
from tensorflowonspark_tpu.models import zoo
from tensorflowonspark_tpu.models.llama import generate
from tensorflowonspark_tpu.models.pangu_moe import (
    PanguMoE,
    PanguMoEConfig,
    from_hf_config,
)
from tensorflowonspark_tpu.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def seeded():
    """The rehearsal configuration with the benchmark's seeded weights:
    experts 4-7 of 16 held, a dense layer and two expert layers."""
    config = harness.load_json("configs", "tiny-latent-moe.json")
    cfg = serve_pangu_moe.model_keys(config)
    model = PanguMoE(from_hf_config(
        config, n_routed_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"], first_expert=cfg["first_expert"],
        max_seq_len=64, dtype=jnp.float32,
    ))
    key = weights_pangu_moe.seed_key(7)
    params = weights_pangu_moe.make_params(cfg, key, jnp.float32)
    return cfg, model, params, key


def test_defaults_are_the_published_config():
    cfg = PanguMoEConfig()
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_layers) == (7680, 128, 61)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.held) == (256, 8, 256)
    assert cfg.first_k_dense_replace == 3 and cfg.routed_scaling_factor == 2.5
    config = harness.load_json("configs", "pangu-ultra-718b-ep16-d5-serve.json")
    published = {**config, **config["published"]}
    assert from_hf_config(published, max_seq_len=4096) == cfg
    with pytest.raises(ValueError, match="sandwich_norm"):
        from_hf_config({**published, "sandwich_norm": False})
    with pytest.raises(ValueError, match="scoring_func"):
        from_hf_config({**published, "scoring_func": "softmax"})
    with pytest.raises(ValueError, match="n_group"):
        from_hf_config({**published, "n_group": 8})


def test_zoo_builds_the_tiny_model():
    entry = zoo.build("pangu_ultra_moe_718b", tiny=True)
    assert "pangu_ultra_moe_718b" in zoo.names()
    batch = entry.make_input(2)
    params = entry.model.init(jax.random.PRNGKey(0), batch["tokens"][:, :-1])["params"]
    loss = entry.make_loss()(params, batch)
    assert np.isfinite(float(loss))
    shardings = entry.param_shardings(params, jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1), ("data",)))
    assert jax.tree.structure(shardings) == jax.tree.structure(params)


def test_model_equals_the_plain_reference(seeded):
    """Logits of one full pass against ``perfbench/reference_pangu_moe.py``
    (the expanded attention, a loop over the held experts), which shares
    no code with the program; and through the cache, the absorbed form."""
    cfg, model, params, key = seeded
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, cfg["vocab_size"], size=(2, 40)).astype(np.int32)
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(seqs)))
    at = np.broadcast_to(np.arange(40, dtype=np.int32), (2, 40))
    toks = np.argmax(logits, axis=-1).astype(np.int32)[..., None]
    get_leaf = serve_pangu_moe.reference_leaves(cfg, key, jnp.float32)
    best, top, lse, got = (np.asarray(x) for x in reference_pangu_moe.serve_readings(
        cfg, get_leaf, seqs, at, toks, blocks=2, vocab_blocks=4))
    assert (top == toks[..., 0]).mean() > 0.97  # ties aside, the same best token
    np.testing.assert_allclose(got[..., 0], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(
        got[..., 0] - lse,
        np.asarray(jax.nn.log_softmax(logits, -1)).max(-1), atol=2e-4,
    )
    # prefill of 9, then one position at a time through the latent cache
    pos = jnp.asarray(at)
    out, state = model.apply(
        {"params": params}, jnp.asarray(seqs[:, :9]), positions=pos[:, :9],
        decode=True, padded=True, mutable=["cache"],
    )
    np.testing.assert_allclose(np.asarray(out), logits[:, :9], atol=2e-4)
    cache = state["cache"]
    for i in range(9, 16):
        out, state = model.apply(
            {"params": params, "cache": cache}, jnp.asarray(seqs[:, i : i + 1]),
            positions=pos[:, i : i + 1], decode=True, padded=True,
            mutable=["cache"],
        )
        cache = state["cache"]
        np.testing.assert_allclose(np.asarray(out[:, 0]), logits[:, i], atol=2e-4)


def _alone(model, params, requests):
    """Per request, the tokens of a lone greedy ``generate`` and their
    log-probabilities from one full forward pass (no cache) over prompt
    and tokens: all requests right-padded into one batch of each (greedy
    tokens are a prefix of a longer run's; padding lies after what a
    causal pass reads)."""
    width = max(len(p) for p, _ in requests)
    most = max(n for _, n in requests)
    prompts = np.zeros((len(requests), width), np.int32)
    for i, (p, _) in enumerate(requests):
        prompts[i, : len(p)] = p
    lengths = np.asarray([len(p) for p, _ in requests], np.int32)
    toks = np.asarray(generate(
        model, params, jnp.asarray(prompts), most, prompt_lengths=lengths
    ))
    rows = np.zeros((len(requests), width + most), np.int32)
    for i, (p, n) in enumerate(requests):
        rows[i, : len(p) + n] = p + toks[i, :n].tolist()
    logp = np.asarray(jax.nn.log_softmax(
        model.apply({"params": params}, jnp.asarray(rows)), axis=-1
    ))
    out = []
    for i, (p, n) in enumerate(requests):
        t = toks[i, :n].tolist()
        out.append((t, [float(logp[i, len(p) - 1 + j, t[j]]) for j in range(n)]))
    return out


_SIZES = [(3, 9), (8, 5), (13, 12), (5, 17), (16, 4), (9, 10), (2, 21), (11, 6)]


def _serve_all(eng, requests, clients=3):
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


@pytest.mark.parametrize("options", [
    dict(decode_block=1), dict(decode_block=8),
    dict(decode_block=4, prefill_chunk=8, prefix_cache=4),
], ids=["single-steps", "blocks-of-8", "chunked-with-prefix-store"])
def test_engine_serves_each_request_as_alone(seeded, options):
    """More requests than slots, so rows are reused and admissions land
    between live blocks: the same tokens as ``generate()`` and
    log-probabilities within 1e-3 of one full pass. Chunked prefill and
    the prefix store work by shape on a latent plane as on K/V."""
    cfg, model, params, _ = seeded
    rng = np.random.default_rng(1)
    requests = [(rng.integers(1, cfg["vocab_size"], size=p).tolist(), n)
                for p, n in _SIZES]
    requests.append((requests[2][0][:10] + [5, 6], 7))  # shares a prefix
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8, 16), **options
    )
    try:
        served = _serve_all(eng, requests)
        assert eng.admitted == len(requests) > eng.stats()["slots"]
    finally:
        eng.close()
    alone = _alone(model, params, requests)
    for (prompt, n), (toks, lps), (want_toks, want_lps) in zip(
        requests, served, alone
    ):
        assert toks == want_toks, (len(prompt), n)
        np.testing.assert_allclose(lps, want_lps, atol=1e-3)


def test_cache_bytes_and_the_routed_counters(seeded):
    cfg, model, params, _ = seeded
    eng = ContinuousBatcher(model, params, slots=3, prompt_widths=(8,), decode_block=4)
    try:
        c = model.cfg
        by_kind = eng.stats()["cache_bytes"]
        assert by_kind["latent"] == c.num_layers * 3 * c.max_seq_len * 128 * 4
        assert by_kind["kv"] == by_kind["recurrent"] == 0
        assert by_kind["other"] == 2 * (c.held + 2) * 4  # the counters
        series = eng.metrics.window()["engine_cache_bytes"]["series"]
        assert series['{kind="latent"}']["value"] == by_kind["latent"]
        eng.submit([1, 2, 3, 4, 5], 13, eos_id=-1)
        eng.submit([9, 8, 7], 6, eos_id=-1)
        reg = eng.metrics.window()
        pairs = reg["engine_moe_assignments_total"]["series"][""]["delta"]
        local = reg["engine_moe_local_assignments_total"]["series"][""]["delta"]
        reached = reg["engine_moe_experts_reached_total"]["series"][""]["delta"]
        experts = reg["engine_moe_expert_tokens_total"]["series"]
        assert sorted(experts) == ['{expert="%d"}' % e for e in (4, 5, 6, 7)]
        # every slot steps, live or not: slots x top-k x expert layers a step
        assert pairs > 0 and pairs % (3 * c.num_experts_per_tok * 2) == 0
        steps = pairs / (3 * c.num_experts_per_tok * 2)
        assert 12 <= steps <= eng.steps
        assert sum(s["delta"] for s in experts.values()) == local
        assert 0 < local < pairs and 0 < reached <= steps * 2 * c.held
    finally:
        eng.close()


def test_the_model_names_what_its_counters_count(seeded):
    """The engine adds the counter leaves up and takes differences; which
    registry counter an entry feeds, and the held experts' labels, are
    the model's to say (``decode_cache.moe_count_entries``)."""
    import inspect

    from tensorflowonspark_tpu.models import decode_cache
    from tensorflowonspark_tpu.serving import engine

    _, model, _, _ = seeded
    entries = model.counter_entries()
    assert len(entries) == model.cfg.held + 2  # as wide as the leaf
    assert {name for feeds in entries for name, _ in feeds} == {
        "engine_moe_assignments_total", "engine_moe_local_assignments_total",
        "engine_moe_expert_tokens_total", "engine_moe_experts_reached_total",
    }
    assert entries == decode_cache.moe_count_entries(4, 4)
    assert [feeds[0][1] for feeds in entries[:-2]] == [
        {"expert": str(e)} for e in range(4, 8)
    ]
    assert entries[-2:] == (
        (("engine_moe_assignments_total", {}),),
        (("engine_moe_experts_reached_total", {}),),
    )
    assert "first_expert" not in inspect.getsource(engine)


def test_counters_survive_int32_wraparound(seeded):
    """The device's sums wrap as int32 does; the registry takes
    differences modulo 2**32."""
    _, model, params, _ = seeded
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8,))
    try:
        eng._routed_seen[:] = np.uint32(2**32 - 5)
        eng._count_routed(np.asarray([3, 0, 1, 2, 10, 4], np.int32) - 5)
        reg = eng.metrics.window()
        assert reg["engine_moe_assignments_total"]["series"][""]["value"] == 10
        assert reg["engine_moe_local_assignments_total"]["series"][""]["value"] == 6
        assert reg["engine_moe_expert_tokens_total"]["series"]['{expert="4"}']["value"] == 3
    finally:
        eng.close()


class _Mesh:
    shape = {"data": 1, "model": 2}


@pytest.mark.parametrize("what,match", [
    (dict(mesh=_Mesh()), "latent"),
    (dict(cfg=dict(kv_cache_dtype="int8")), "int8"),
    (dict(cfg=dict(kv_cache_len=32)), "rolling"),
], ids=["model-mesh", "int8-cache", "rolling-cache"])
def test_what_a_latent_cache_cannot_do_is_refused_at_construction(
    seeded, what, match
):
    import dataclasses

    _, model, params, _ = seeded
    if "cfg" in what:
        model = PanguMoE(dataclasses.replace(model.cfg, **what["cfg"]))
    with pytest.raises(ValueError, match=match):
        ContinuousBatcher(model, params, slots=2, prompt_widths=(8,),
                          mesh=what.get("mesh"))
