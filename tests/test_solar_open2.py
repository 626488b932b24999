"""Solar-Open2 (``models/solar_open2.py``) at a tiny size: the model
against the benchmark's plain reference on seeded weights (one full pass;
prefill then decode through the cache; a padded prefill), the eight
shares of an expert layer against the uncut reference, through the
serving engine as alone, what the engine counts and refuses with layers
that hold either K/V or state, and ``llama.Attention`` at its defaults."""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, reference_solar_open2, weights_solar_open2
from perfbench.drivers import serve_solar_open2
from perfbench.reference import mm_highest
from tensorflowonspark_tpu.models import zoo
from tensorflowonspark_tpu.models.decode_cache import leaf_kind
from tensorflowonspark_tpu.models.falcon_h1 import FalconH1Config
from tensorflowonspark_tpu.models.llama import Attention, LlamaConfig, generate
from tensorflowonspark_tpu.models.solar_open2 import (
    SolarOpen2,
    SolarOpen2Config,
    from_hf_config,
)
from tensorflowonspark_tpu.parallel.moe import DroplessMoE
from tensorflowonspark_tpu.serving import ContinuousBatcher
from tests.test_engine_hybrid import _serve_all

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def seeded():
    """The rehearsal configuration with the benchmark's seeded weights:
    GQA, three linear layers, GQA; experts 4-7 of 16 held."""
    config = harness.load_json("configs", "tiny-kda-moe.json")
    cfg = serve_solar_open2.model_keys(config)
    model = serve_solar_open2.build_model(
        {**config, "run": {**config["run"], "max_seq_len": 64}}, cfg)
    key = weights_solar_open2.seed_key(7)
    params = weights_solar_open2.make_params(cfg, key, jnp.float32)
    return cfg, model, params, key


def published():
    config = harness.load_json("configs", "solar-open2-250b-ep8-d4-serve.json")
    return {**config, **config["published"]}


def test_defaults_are_the_published_config():
    cfg = SolarOpen2Config()
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size) == (4096, 48, 196608)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel) == (64, 128, 4)
    assert cfg.gqa_layers == tuple(range(0, 48, 4)) and not cfg.use_rope
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.held) == (320, 8, 320)
    assert cfg.moe_intermediate_size == 1280 and cfg.routed_scaling_factor == 1
    assert from_hf_config(published(), max_seq_len=4096) == cfg
    with open(CATALOG) as f:
        row = next(json.loads(l) for l in f if '"Solar-Open2-250B"' in l)
    assert from_hf_config(row["config"], max_seq_len=4096) == cfg


@pytest.mark.parametrize("change,match", [
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_group": 8}, "n_group"),
    ({"gqa_layers": [0, 48]}, "gqa_layers"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                             "num_heads": 64, "num_kv_heads": 8}}, "num_kv_heads"),
])
def test_what_is_not_computed_is_refused_not_dropped(change, match):
    with pytest.raises(ValueError, match=match):
        from_hf_config({**published(), **change})


def test_use_rope_true_is_built_not_dropped():
    """``use_rope`` with ``gqa_layers``: the attention layers rotate, as
    ``llama.Attention`` does for every other model."""
    cfg = from_hf_config({**published(), "use_rope": True}, max_seq_len=64)
    assert cfg.use_rope and cfg.rope_theta == 10000.0


def test_one_layer_of_each_kind_counts_as_published():
    """126,099,456 outside the experts in a GQA layer, 154,788,032 in a
    linear one, 15,728,640 an expert (ISSUE 33's reckoning)."""
    one = dataclasses.replace(
        SolarOpen2Config(), num_layers=2, gqa_layers=(0,), experts_held=1,
        vocab_size=8, max_seq_len=8)
    shapes = jax.eval_shape(lambda: SolarOpen2(one).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    count = lambda t: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["layer0"]) == 126_099_456 + 15_728_640
    assert count(shapes["layer1"]) == 154_788_032 + 15_728_640
    assert count(shapes["layer1"]["mixer"]) == 137_740_480
    assert "attn" in shapes["layer0"] and "mixer" not in shapes["layer0"]
    assert "g_proj" in shapes["layer0"]["attn"]


def test_zoo_builds_the_tiny_model():
    entry = zoo.build("solar_open2_250b", tiny=True)
    assert "solar_open2_250b" in zoo.names()
    batch = entry.make_input(2)
    params = entry.model.init(jax.random.PRNGKey(0), batch["tokens"][:, :-1])["params"]
    loss, grads = jax.value_and_grad(entry.make_loss())(params, batch)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    shardings = entry.param_shardings(params, jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1), ("data",)))
    assert jax.tree.structure(shardings) == jax.tree.structure(params)


def _reference(cfg, key, seqs, toks):
    at = np.broadcast_to(np.arange(seqs.shape[1], dtype=np.int32), seqs.shape)
    get_leaf = serve_solar_open2.reference_leaves(cfg, key, jnp.float32)
    return [np.asarray(x) for x in reference_solar_open2.serve_readings(
        cfg, get_leaf, seqs, at, toks, blocks=2, vocab_blocks=4)]


def test_model_equals_the_plain_reference(seeded):
    """Logits of one full pass against ``perfbench/reference_solar_open2
    .py`` (the delta rule a position at a time, the full softmax, a loop
    over the held experts), which shares no code with the program; then a
    prefill and one position at a time through the cache."""
    cfg, model, params, key = seeded
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, cfg["vocab_size"], size=(2, 40)).astype(np.int32)
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(seqs)))
    toks = np.argmax(logits, axis=-1).astype(np.int32)[..., None]
    best, top, lse, got = _reference(cfg, key, seqs, toks)
    assert (top == toks[..., 0]).mean() > 0.97  # ties aside, the same best token
    np.testing.assert_allclose(got[..., 0], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(
        got[..., 0] - lse,
        np.asarray(jax.nn.log_softmax(logits, -1)).max(-1), atol=2e-4,
    )
    # prefill of 11 (across the chunk of 8), then through the cache
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    out, state = model.apply(
        {"params": params}, jnp.asarray(seqs[:, :11]), positions=pos[:, :11],
        decode=True, padded=True, mutable=["cache"],
    )
    np.testing.assert_allclose(np.asarray(out), logits[:, :11], atol=2e-4)
    cache = state["cache"]
    for i in range(11, 20):
        out, state = model.apply(
            {"params": params, "cache": cache}, jnp.asarray(seqs[:, i : i + 1]),
            positions=pos[:, i : i + 1], decode=True, padded=True,
            mutable=["cache"],
        )
        cache = state["cache"]
        np.testing.assert_allclose(np.asarray(out[:, 0]), logits[:, i], atol=2e-4)


def test_padded_prefill_then_decode_equals_the_reference(seeded):
    """Rows of different lengths in one right-padded batch: prefill under
    the validity mask, then one position at a time, the rows at their own
    positions; every logit against the reference's full pass of that row."""
    cfg, model, params, key = seeded
    rng = np.random.default_rng(1)
    lengths, width, steps = np.asarray([13, 7, 20]), 20, 5
    tokens = rng.integers(0, cfg["vocab_size"], (3, width + steps)).astype(np.int32)
    prompt = np.zeros((3, width), np.int32)
    for i, n in enumerate(lengths):
        prompt[i, :n] = tokens[i, :n]
    positions = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), (3, width))
    valid = positions < jnp.asarray(lengths)[:, None]
    logits, state = model.apply(
        {"params": params}, jnp.asarray(prompt), positions=positions,
        decode=True, padded=True, valid=valid, mutable=["cache"],
    )
    got = [[np.asarray(logits[i, :n])] for i, n in enumerate(lengths)]
    cache = state["cache"]
    for s in range(steps):
        tok = jnp.asarray([[tokens[i, n + s]] for i, n in enumerate(lengths)], jnp.int32)
        lg, state = model.apply(
            {"params": params, "cache": cache}, tok,
            positions=jnp.asarray(lengths + s)[:, None], decode=True,
            padded=True, mutable=["cache"],
        )
        cache = state["cache"]
        for i in range(3):
            got[i].append(np.asarray(lg[i]))
    for i, n in enumerate(lengths):
        mine = np.concatenate(got[i])  # (n + steps, vocab)
        row = tokens[i : i + 1, : n + steps]
        toks = np.argmax(mine, -1).astype(np.int32)[None, :, None]
        _, _, lse, ref = _reference(cfg, key, row, toks)
        np.testing.assert_allclose(ref[0, :, 0], mine.max(-1), atol=2e-4)
        np.testing.assert_allclose(
            lse[0], np.asarray(jax.nn.logsumexp(mine, -1)), atol=2e-4)
    # a layer holds either K/V or the state and its window
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        kinds.setdefault(path[0].key, set()).add(leaf_kind(path))
    assert kinds["layer0"] == kinds["layer4"] == {"kv", "other", "counter"}
    assert kinds["layer1"] == kinds["layer2"] == kinds["layer3"] == {"recurrent", "counter"}
    mixer = cache["layer1"]["mixer"]
    assert mixer["kda"].dtype == jnp.float32 and mixer["kda"].shape == (3, 4, 8, 8)
    assert mixer["conv"].shape == (3, 3, 3 * 32)


def test_padding_run_through_the_recurrence_is_wrong(seeded):
    """Why the engine passes ``valid``: without it the state after a
    padded prefill is not the prompt's."""
    cfg, model, params, _ = seeded
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :9] = np.random.default_rng(2).integers(1, cfg["vocab_size"], 9)
    positions = jnp.arange(16, dtype=jnp.int32)[None]
    states = []
    for valid in (positions < 9, None):
        _, st = model.apply(
            {"params": params}, jnp.asarray(prompt), positions=positions,
            decode=True, padded=True, valid=valid, mutable=["cache"],
        )
        states.append(st["cache"]["layer1"]["mixer"]["kda"])
    assert float(jnp.abs(states[0] - states[1]).max()) > 1e-3


def test_the_eight_shares_add_up_to_the_uncut_reference_layer(seeded):
    """One chip of eight holds 2 of this layer's 16 experts: each share
    routes over all 16 and computes its own experts' part; the eight
    parts, and the shared expert counted once, are what the plain
    reference gives for the layer with every expert held."""
    cfg, _, _, key = seeded
    uncut = dict(cfg, n_routed_experts=16, router_experts=16, first_expert=0)
    names = {"/".join(p[1:]): (n, s, k) for n, (p, s, k) in enumerate(
        weights_solar_open2.leaf_specs(uncut)) if p[0] == "layer1"}
    w = {name: weights_solar_open2.make_leaf(key, n, s, k, jnp.float32)
         for name, (n, s, k) in names.items() if name.startswith("moe/")}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, cfg["hidden_size"]))
    want = reference_solar_open2.experts(
        uncut, w, x.reshape(-1, cfg["hidden_size"]), mm_highest)

    def share(first, held):
        layer = DroplessMoE(
            num_experts=16, top_k=cfg["num_experts_per_tok"],
            intermediate_size=cfg["moe_intermediate_size"],
            shared_size=cfg["moe_intermediate_size"], first_held=first, held=held,
            scaling=cfg["routed_scaling_factor"], dtype=jnp.float32)
        params = {
            "router": w["moe/router"],
            **{n: w[f"moe/{n}"][first : first + held] for n in ("w_gate", "w_up", "w_down")},
            **{n: {"kernel": w[f"moe/{n}/kernel"]}
               for n in ("shared_gate", "shared_up", "shared_down")},
        }
        return layer.apply({"params": params}, x)

    shared_only, _ = share(0, 0)
    total, pairs = np.asarray(shared_only, np.float64), 0
    for first in range(0, 16, 2):
        part, sizes = share(first, 2)
        total += np.asarray(part, np.float64) - np.asarray(shared_only, np.float64)
        pairs += int(sizes.sum())
    np.testing.assert_allclose(total.reshape(want.shape), np.asarray(want), atol=2e-5)
    assert pairs == 2 * 9 * cfg["num_experts_per_tok"]


def _alone(model, params, requests):
    """Per request, the tokens of a lone greedy ``generate`` and their
    log-probabilities from one full forward pass (no cache) over prompt
    and tokens, a request at a time (a recurrence would run over another
    row's padding)."""
    out = []
    for prompt, n in requests:
        toks = np.asarray(
            generate(model, params, jnp.asarray([prompt], jnp.int32), n)
        )[0].tolist()
        logits = model.apply(
            {"params": params}, jnp.asarray([prompt + toks[:-1]], jnp.int32))[0]
        logp = jax.nn.log_softmax(logits[len(prompt) - 1 :], axis=-1)
        out.append((toks, [float(logp[i, t]) for i, t in enumerate(toks)]))
    return out


_SIZES = [(3, 9), (8, 5), (13, 12), (5, 17), (16, 4), (9, 10), (2, 21), (11, 6)]


@pytest.mark.parametrize("options", [
    dict(decode_block=1), dict(decode_block=8), dict(decode_block=4, prefill_chunk=8),
], ids=["single-steps", "blocks-of-8", "chunked-prefill"])
def test_engine_serves_each_request_as_alone(seeded, options):
    """More requests than slots, so rows are reused and admissions land
    between live blocks: the same tokens as ``generate()`` and
    log-probabilities within 1e-3 of one full pass, through the same
    scheduler, admission scatter, donation and packed fetch as every
    other model, the layers holding K/V in some and state in others."""
    cfg, model, params, _ = seeded
    rng = np.random.default_rng(1)
    requests = [(rng.integers(1, cfg["vocab_size"], size=p).tolist(), n)
                for p, n in _SIZES]
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8, 16), **options)
    try:
        served = _serve_all(eng, requests)
        assert eng.admitted == len(requests) > eng.stats()["slots"]
    finally:
        eng.close()
    for (prompt, n), (toks, lps), (want_toks, want_lps) in zip(
        requests, served, _alone(model, params, requests)
    ):
        assert toks == want_toks, (len(prompt), n)
        np.testing.assert_allclose(lps, want_lps, atol=1e-3)


def test_cache_bytes_and_counters_by_what_the_layers_hold(seeded):
    """``engine_cache_bytes`` says two layers' K/V and three layers'
    state; the K/V position counters count a layer (the readers multiply
    by the layers that hold K/V); the recurrent bytes follow the live
    slots; the routed counters are the expert model's."""
    cfg, model, params, _ = seeded
    slots = 3
    eng = ContinuousBatcher(model, params, slots=slots, prompt_widths=(8,), decode_block=4)
    try:
        c = model.cfg
        by_kind = eng.stats()["cache_bytes"]
        cache = eng._empty_state()[0]
        assert sum(by_kind.values()) == sum(x.nbytes for x in jax.tree_util.tree_leaves(cache))
        assert by_kind["kv"] == 2 * 2 * slots * c.max_seq_len * c.num_kv_heads * c.head_dim * 4
        row = c.linear_num_heads * c.linear_head_dim**2 * 4 + 3 * 3 * c.linear_dim * 4
        assert by_kind["recurrent"] == 3 * slots * row
        assert by_kind["latent"] == 0
        eng.submit([1, 2, 3, 4, 5], 13, eos_id=-1)
        eng.submit([9, 8, 7], 6, eos_id=-1)
        reg = eng.metrics.window()
        delta = lambda name: reg[name]["series"][""]["delta"]  # noqa: E731
        steps, live = delta("engine_decode_steps_total"), delta("engine_slot_steps_live_total")
        assert delta("engine_recurrent_state_bytes_total") == live * 3 * row
        assert 0 < live < steps * slots
        assert delta("engine_decode_kv_positions_span_total") == steps * slots * c.max_seq_len
        assert 0 < delta("engine_decode_kv_positions_read_total") <= steps * slots * c.max_seq_len
        pairs = delta("engine_moe_assignments_total")
        # every slot steps, live or not, in all five layers; the counts
        # ride the packed fetch, so the last blocks' may be yet to come
        a_step = slots * c.num_experts_per_tok * c.num_layers
        assert pairs % a_step == 0 and 12 <= pairs / a_step <= steps
        experts = reg["engine_moe_expert_tokens_total"]["series"]
        assert sorted(experts) == ['{expert="%d"}' % e for e in (4, 5, 6, 7)]
        assert sum(s["delta"] for s in experts.values()) == delta(
            "engine_moe_local_assignments_total")
    finally:
        eng.close()


@pytest.mark.parametrize("what", ["prefix_cache", "prefix_l2", "model_mesh"])
def test_what_recurrent_state_cannot_do_is_refused(seeded, what):
    """The refusals that hold for recurrent state hold for a model that
    has it in some layers only, through the same checks."""
    _, model, params, _ = seeded
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


def test_no_model_is_named_in_the_engine_or_the_cache_module():
    import inspect

    from tensorflowonspark_tpu.models import decode_cache
    from tensorflowonspark_tpu.serving import engine

    # the engine asks of the cache tree, never which model or which kind
    # of layer made a leaf
    for word in ("kda", "gqa_layers", "SolarOpen2("):
        assert word not in inspect.getsource(engine)
    assert "solar" not in inspect.getsource(decode_cache.leaf_kind)


@pytest.mark.parametrize("cfg", [
    LlamaConfig.tiny(sliding_window=16, dtype=jnp.float32),
    FalconH1Config.tiny(dtype=jnp.float32),
], ids=["mistral-shaped", "falcon-h1-shaped"])
def test_attention_at_its_defaults_gains_no_parameter_and_no_operation(cfg):
    """``use_rope`` true and ``attention_output_gate`` false: Mistral's
    and Falcon-H1's attention has no ``g_proj``, no sigmoid on its
    output, and both rotations; switched the other way the gate and its
    sigmoid appear and the rotations (their sines and cosines) go."""
    x = jnp.zeros((2, 8, cfg.hidden_size), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))

    def lowered(c):
        attn = Attention(c)
        params = jax.eval_shape(lambda: attn.init(jax.random.PRNGKey(0), x, pos))["params"]
        text = str(jax.make_jaxpr(lambda p: attn.apply({"params": p}, x, pos))(params))
        count = lambda prim: len(re.findall(rf"= {prim}\b", text))  # noqa: E731
        return set(params), count

    names, count = lowered(cfg)
    assert names == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert count("logistic") == 0
    assert count("sin") == count("cos") == 2
    names, count = lowered(dataclasses.replace(
        cfg, use_rope=False, attention_output_gate=True))
    assert names == {"q_proj", "k_proj", "v_proj", "o_proj", "g_proj"}
    assert count("logistic") == 1 and count("sin") == count("cos") == 0


def test_gated_nope_attention_is_what_its_equation_says():
    """``o = (softmax(q k^T / sqrt(d)) v * sigmoid(x W_g)) W_o`` with no
    rotation, against the same written out."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(dtype=jnp.float32), use_rope=False, attention_output_gate=True)
    attn = Attention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, cfg.hidden_size))
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    params = attn.init(jax.random.PRNGKey(1), x, pos)["params"]
    params = jax.tree.map(lambda a: a * 10.0, params)
    got = attn.apply({"params": params}, x, pos)
    w = {k: np.asarray(v["kernel"], np.float64) for k, v in params.items()}
    xs = np.asarray(x[0], np.float64)
    d, rep = cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
    q = (xs @ w["q_proj"]).reshape(6, cfg.num_heads, d)
    k = np.repeat((xs @ w["k_proj"]).reshape(6, cfg.num_kv_heads, d), rep, 1)
    v = np.repeat((xs @ w["v_proj"]).reshape(6, cfg.num_kv_heads, d), rep, 1)
    sc = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    sc = np.where(np.tril(np.ones((6, 6), bool)), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    a = np.einsum("hqk,khd->qhd", p, v).reshape(6, -1)
    want = (a / (1.0 + np.exp(-(xs @ w["g_proj"])))) @ w["o_proj"]
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-4)
