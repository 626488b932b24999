"""Phi-4-mini-flash (``models/phi4_flash.py``, SambaY) at a tiny size: the
model against the benchmark's plain reference on seeded weights (one full
pass; a prefill wider than the windowed layers' ring, then decode through
the cache as the ring wraps; a right-padded prefill whose cross-decoder
runs at the last position only), differential attention against both maps
written out, what the cross layers and the GMUs read, through the serving
engine as alone, what the engine counts and refuses, and the traced
programs of the accepted models at ``llama.Attention``'s new field's
default."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, reference_phi4_flash, weights_phi4_flash
from perfbench.drivers import _llama, serve_phi4_flash
from tensorflowonspark_tpu.models import llama, phi4_flash, zoo
from tensorflowonspark_tpu.models.decode_cache import leaf_kind
from tensorflowonspark_tpu.models.falcon_h1 import FalconH1, FalconH1Config
from tensorflowonspark_tpu.models.llama import Attention, Llama, LlamaConfig
from tensorflowonspark_tpu.models.phi4_flash import (
    Phi4Flash,
    Phi4FlashConfig,
    from_hf_config,
    layer_types,
)
from tensorflowonspark_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config
from tensorflowonspark_tpu.ops import attention as attn_mod
from tensorflowonspark_tpu.ops import decode_attention as da_mod
from tensorflowonspark_tpu.serving import ContinuousBatcher
from tests.test_engine_hybrid import _serve_all

# a model catalog (JSON lines) to hold the defaults against, where one is given
CATALOG = os.environ.get("MODEL_CATALOG", "")
ATOL = 2e-4


@pytest.fixture(scope="module")
def seeded():
    """The rehearsal configuration with the benchmark's seeded weights:
    mamba, swa, mamba, swa, mamba (the memory), full, gmu, cross; a
    window of 8."""
    config = harness.load_json("configs", "tiny-sambay.json")
    cfg = _llama.model_keys(config)
    model = serve_phi4_flash.build_model(config, cfg)
    key = weights_phi4_flash.seed_key(7)
    params = weights_phi4_flash.make_params(cfg, key, jnp.float32)
    return cfg, model, params, key


def published():
    config = harness.load_json("configs", "phi4-mini-flash-d32-serve.json")
    return _llama.model_keys(config)


def _reference(cfg, key, seqs, toks):
    at = np.broadcast_to(np.arange(seqs.shape[1], dtype=np.int32), seqs.shape)
    get_leaf = serve_phi4_flash.reference_leaves(cfg, key, jnp.float32)
    return [np.asarray(x) for x in reference_phi4_flash.serve_readings(
        cfg, get_leaf, seqs, at, toks, blocks=2, vocab_blocks=4)]


def test_defaults_are_the_published_config():
    cfg = Phi4FlashConfig()
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size) == (2560, 32, 200064)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (40, 20, 64)
    assert (cfg.sliding_window, cfg.intermediate_size, cfg.mamba_dt_rank) == (512, 10240, 160)
    assert cfg.layer_types == (
        ("mamba", "swa") * 8 + ("mamba", "full") + ("gmu", "cross") * 7)
    assert cfg.memory_layer == 16 and cfg.d_inner == 5120
    assert from_hf_config(published(), max_seq_len=4096) == cfg
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(l) for l in f if '"Phi-4-mini-flash-reasoning"' in l)
        assert from_hf_config(row["config"], max_seq_len=4096) == cfg


@pytest.mark.parametrize("change,match", [
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mb_per_layer": 3}, "no Mamba layer before the full"),
    ({"num_key_value_heads": 5, "num_attention_heads": 40}, "pair up"),
])
def test_what_is_not_computed_is_refused_not_dropped(change, match):
    with pytest.raises(ValueError, match=match):
        from_hf_config({**published(), **change})


def test_one_layer_of_each_kind_counts_as_published():
    """119,895,040 a Mamba layer, 98,314,624 a windowed or full attention
    layer, 104,867,840 a GMU, 91,761,024 a cross layer (reckoned
    by hand), at the published widths."""
    eight = dataclasses.replace(
        Phi4FlashConfig(), num_layers=8, layer_types=layer_types(8, 2),
        vocab_size=8, max_seq_len=8)
    shapes = jax.eval_shape(lambda: Phi4Flash(eight).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    count = lambda t: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))  # noqa: E731
    want = [119_895_040, 98_314_624] * 2 + [119_895_040, 98_314_624, 104_867_840, 91_761_024]
    assert [count(shapes[f"layer{i}"]) for i in range(8)] == want
    assert "k_proj" not in shapes["layer7"]["attn"] and "lambda_q1" in shapes["layer7"]["attn"]
    assert shapes["layer5"]["attn"]["q_proj"]["kernel"].shape == (2560, 2560)
    assert shapes["layer5"]["attn"]["k_proj"]["kernel"].shape == (2560, 1280)


def test_zoo_builds_the_tiny_model():
    entry = zoo.build("phi4_mini_flash", tiny=True)
    batch = entry.make_input(2)
    params = entry.model.init(jax.random.PRNGKey(0), batch["tokens"][:, :-1])["params"]
    loss, grads = jax.value_and_grad(entry.make_loss())(params, batch)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))


def test_model_equals_the_plain_reference_then_through_the_cache(seeded):
    """Logits of one full pass against ``perfbench/reference_phi4_flash
    .py`` (the scan a position at a time, both maps written out, every
    layer at every position), which shares no code with the program;
    then a prefill of 11 positions, wider than the windowed layers' ring
    of 8 (its last position's logits: the cross-decoder runs there
    only), and one position at a time through the cache while the ring
    wraps."""
    cfg, model, params, key = seeded
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, cfg["vocab_size"], size=(2, 40)).astype(np.int32)
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(seqs)))
    toks = np.argmax(logits, axis=-1).astype(np.int32)[..., None]
    best, top, lse, got = _reference(cfg, key, seqs, toks)
    assert (top == toks[..., 0]).mean() > 0.97  # ties aside, the same best token
    np.testing.assert_allclose(got[..., 0], logits.max(-1), atol=ATOL)
    np.testing.assert_allclose(
        got[..., 0] - lse, np.asarray(jax.nn.log_softmax(logits, -1)).max(-1), atol=ATOL)
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    out, state = model.apply(
        {"params": params}, jnp.asarray(seqs[:, :11]), positions=pos[:, :11],
        decode=True, padded=True, mutable=["cache"],
    )
    np.testing.assert_allclose(np.asarray(out[:, -1]), logits[:, 10], atol=ATOL)
    cache = state["cache"]
    ring = cache["layer1"]["attn"]
    # the ring holds the last 8 positions of the 11, each at position % 8
    assert ring["pos"].shape[1] == 8 and ring["k"].shape[1] == 8 * 2
    np.testing.assert_array_equal(np.asarray(ring["pos"][0]), [8, 9, 10, 3, 4, 5, 6, 7])
    for i in range(11, 30):
        out, state = model.apply(
            {"params": params, "cache": cache}, jnp.asarray(seqs[:, i : i + 1]),
            positions=pos[:, i : i + 1], decode=True, padded=True, mutable=["cache"],
        )
        cache = state["cache"]
        np.testing.assert_allclose(np.asarray(out[:, 0]), logits[:, i], atol=ATOL)


def test_padded_prefill_then_decode_equals_the_reference(seeded):
    """Rows of different lengths in one right-padded batch, one longer
    than the ring: prefill under the validity mask (each row's logits at
    its last real position), then one position at a time, the rows at
    their own positions; every logit against the reference's full pass of
    that row."""
    cfg, model, params, key = seeded
    rng = np.random.default_rng(1)
    lengths, width, steps = np.asarray([13, 5, 20]), 20, 6
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
    got = [[np.asarray(logits[i, n - 1 : n])] for i, n in enumerate(lengths)]
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
        mine = np.concatenate(got[i])  # (1 + steps, vocab): positions n-1 ..
        row = tokens[i : i + 1, : n + steps]
        toks = np.argmax(mine, -1).astype(np.int32)
        full = np.zeros((1, n + steps, 1), np.int32)
        full[0, n - 1 :, 0] = toks
        _, _, lse, ref = _reference(cfg, key, row, full)
        np.testing.assert_allclose(ref[0, n - 1 :, 0], mine.max(-1), atol=ATOL)
        np.testing.assert_allclose(
            lse[0, n - 1 :], np.asarray(jax.nn.logsumexp(mine, -1)), atol=ATOL)
    # what each kind of layer keeps: state and window, a ring, the dense
    # plane, nothing
    kinds = {k: {leaf_kind(p) for p, _ in jax.tree_util.tree_leaves_with_path(v)}
             for k, v in cache.items()}
    assert set(cache) == {f"layer{i}" for i in range(6)}  # the cross-decoder keeps nothing
    assert kinds["layer0"] == kinds["layer4"] == {"recurrent"}
    assert kinds["layer1"] == kinds["layer5"] == {"kv", "other"}
    assert cache["layer0"]["mamba"]["ssm"].shape == (3, 8, 128)  # (rows, N, c)
    assert cache["layer0"]["mamba"]["ssm"].dtype == jnp.float32
    assert cache["layer0"]["mamba"]["conv"].shape == (3, 3, 128)
    # a ring of 8 and the dense plane of 64, two pairs of 16 a position,
    # each position's pairs as rows (``kv_heads_in_rows``)
    assert cache["layer1"]["attn"]["k"].shape == (3, 8 * 2, 16)
    assert cache["layer5"]["attn"]["k"].shape == (3, 64 * 2, 16)
    assert "pos" not in cache["layer5"]["attn"]


def test_last_position_cross_decoder_equals_every_position(seeded):
    """A ``decode=True`` prefill runs the layers after the full one at each
    row's last valid position only: there its hidden state is the one a
    forward over every position gives."""
    cfg, model, params, _ = seeded
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg["vocab_size"], (2, 16)).astype(np.int32)
    lengths = np.asarray([16, 9])
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    (hidden, _), _ = model.apply(
        {"params": params}, jnp.asarray(tokens), positions=positions, decode=True,
        padded=True, valid=positions < jnp.asarray(lengths)[:, None],
        return_hidden=True, mutable=["cache"],
    )
    for i, n in enumerate(lengths):
        whole, _ = model.apply(
            {"params": params}, jnp.asarray(tokens[i : i + 1, :n]), return_hidden=True)
        np.testing.assert_allclose(
            np.asarray(hidden[i, n - 1]), np.asarray(whole[0, n - 1]), atol=1e-5)


def test_cross_layers_read_the_full_layers_plane_and_gmus_the_pre_gate_y(
    seeded, monkeypatch
):
    """The cross layer's decode step attends the full layer's planes as
    this step left them; and the reference that the model equals tells a
    GMU fed the gated output, or a cross layer fed the last windowed
    layer's K/V, from the published one."""
    cfg, model, params, key = seeded
    seen = []
    real = llama.attend_plane
    monkeypatch.setattr(
        llama, "attend_plane",
        lambda q, ck, cv, lengths, c: seen.append((ck, cv, lengths)) or real(q, ck, cv, lengths, c))
    rng = np.random.default_rng(3)
    seqs = rng.integers(0, cfg["vocab_size"], (1, 12)).astype(np.int32)
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    _, state = model.apply({"params": params}, jnp.asarray(seqs[:, :11]), positions=pos[:, :11],
                           decode=True, padded=True, mutable=["cache"])
    seen.clear()
    _, state = model.apply({"params": params, "cache": state["cache"]},
                           jnp.asarray(seqs[:, 11:]), positions=pos[:, 11:],
                           decode=True, padded=True, mutable=["cache"])
    plane = state["cache"]["layer5"]["attn"]
    (ck, cv, lengths), = seen  # one cross layer
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(plane["k"]))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(plane["v"]))
    assert np.asarray(lengths).tolist() == [12]
    # the reference's own readings, then with either reading changed; the
    # GMU's and the cross layer's outputs made 200 times louder, so that
    # at this size a wrong input to either shows in the logits
    loud = ("layer6/gmu_out/kernel", "layer7/attn/o_proj/kernel")
    edit = lambda name, leaf: leaf * 200.0 if name in loud else leaf  # noqa: E731
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a * 200.0 if "/".join(k.key for k in p) in loud else a, params)

    def readings():
        at = np.broadcast_to(np.arange(24, dtype=np.int32), seqs.shape)
        get_leaf = serve_phi4_flash.reference_leaves(cfg, key, jnp.float32, edit)
        return np.asarray(reference_phi4_flash.serve_readings(
            cfg, get_leaf, seqs, at, np.zeros((1, 24, 1), np.int32), blocks=2,
            vocab_blocks=4)[0])  # the best logit a position

    seqs = rng.integers(0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    base = readings()
    mine = np.asarray(model.apply({"params": params}, jnp.asarray(seqs)).max(-1))
    np.testing.assert_allclose(base, mine, atol=ATOL)
    mamba = reference_phi4_flash.mamba

    def gated(c, w, n, mm):
        out, y = mamba(c, w, n, mm)
        z = mm(n, w["mamba/in_proj/kernel"])[:, y.shape[-1]:]
        return out, y * jax.nn.silu(z)

    layer = reference_phi4_flash.layer

    def ring_read(c, w, x, kind, lam0, memory, kv, mm, blocks):
        out = layer(c, w, x, kind, lam0, memory, kv, mm, blocks)
        if kind == "swa":  # the last windowed layer's own K/V stand in
            n = reference_phi4_flash.layer_norm(
                x, w["in_norm/scale"], w["in_norm/bias"], c["layer_norm_eps"])
            _, own = reference_phi4_flash.attention_layer(c, w, n, None, lam0, 8, mm, blocks)
            return out[0], out[1], own
        if kind == "full":  # which then keeps what the cross layers read
            return out[0], out[1], kv
        return out

    for name, fake in (("mamba", gated), ("layer", ring_read)):
        with monkeypatch.context() as m:
            m.setattr(reference_phi4_flash, name, fake)
            reference_phi4_flash._programs.cache_clear()
            other = readings()
        reference_phi4_flash._programs.cache_clear()
        assert np.abs(other - base).max() > 10 * ATOL, name


def _diff_reference(x, w, lam0, window, d):
    """Differential attention of x (S, H) written out with both maps, in
    float64, before ``W_o``."""
    s = x.shape[0]
    q = (x @ w["q_proj"]).reshape(s, -1, 2, d)
    k = (x @ w["k_proj"]).reshape(s, -1, 2, d)
    v = (x @ w["v_proj"]).reshape(s, -1, 2 * d)
    lam = (np.exp(w["lambda_q1"] @ w["lambda_k1"]) - np.exp(w["lambda_q2"] @ w["lambda_k2"])
           + lam0)
    idx = np.arange(s)
    ok = idx[None, :] <= idx[:, None]
    if window:
        ok &= idx[:, None] - idx[None, :] < window
    out = []
    for i in range(q.shape[1]):
        maps = []
        for m in range(2):
            sc = np.where(ok, q[:, i, m] @ k[:, i // 2, m].T / np.sqrt(d), -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            maps.append((p / p.sum(-1, keepdims=True)) @ v[:, i // 2])
        a = maps[0] - lam * maps[1]
        a = a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * w["subln"] * (1 - lam0)
        out.append(a)
    return np.concatenate(out, -1) @ w["o_proj"]


@pytest.mark.parametrize("kind", ["swa", "full"])
@pytest.mark.parametrize("path", ["forward", "prefill", "decode", "decode-kernel"])
def test_differential_attention_is_both_maps_written_out(monkeypatch, kind, path):
    """Four query rows a K/V pair against the pairs stored 2d wide: the
    forward, a prefill that starts its sequence, and decode steps through
    the ring or the dense plane (on the plane also the decode-attention
    kernel in the interpreter), against the two maps of every
    differential head written out. On the chip both the ring, which its
    window fills, and the plane are read by the kernel."""
    cfg = Phi4FlashConfig.tiny(dtype=jnp.float32, sliding_window=4)
    view = cfg.attention(1 if kind == "swa" else 5)
    attn = Attention(view)
    s, d = 12, cfg.head_dim
    x = jax.random.normal(jax.random.PRNGKey(0), (1, s, cfg.hidden_size))
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    params = attn.init(jax.random.PRNGKey(1), x, pos)["params"]
    params = jax.tree.map(lambda a: a * 4.0, params)  # sharp enough maps
    w = {k: np.asarray(v["kernel"] if isinstance(v, dict) else v, np.float64)
         for k, v in params.items()}
    want = _diff_reference(np.asarray(x[0], np.float64), w, view.differential_lambda_init,
                           view.sliding_window, d)
    if path == "forward":
        got = attn.apply({"params": params}, x, pos)[0]
    else:
        if path == "decode-kernel":
            monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
            monkeypatch.setattr(da_mod, "INTERPRET", True)
        first = s if path == "prefill" else 5
        out, st = attn.apply({"params": params}, x[:, :first], pos[:, :first], None,
                             True, True, mutable=["cache"])
        got = [out[0]]
        for i in range(first, s):
            out, st = attn.apply({"params": params, "cache": st["cache"]}, x[:, i : i + 1],
                                 pos[:, i : i + 1], None, True, True, mutable=["cache"])
            got.append(out[0])
        got = jnp.concatenate(got)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


def test_engine_serves_each_request_as_alone(seeded):
    """More requests than slots, prompts longer than the ring, so rows are
    reused and rings wrap: the same tokens as ``generate()`` and
    log-probabilities within 1e-3 of one full pass, through the same
    scheduler, admission scatter, donation and packed fetch as every
    other model."""
    from tests.test_solar_open2 import _SIZES, _alone

    cfg, model, params, _ = seeded
    rng = np.random.default_rng(1)
    requests = [(rng.integers(1, cfg["vocab_size"], size=p).tolist(), n) for p, n in _SIZES]
    for options in (dict(decode_block=1), dict(decode_block=8)):
        eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8, 16), **options)
        try:
            served = _serve_all(eng, requests)
            assert eng.admitted == len(requests) > eng.stats()["slots"]
        finally:
            eng.close()
        for (prompt, n), (toks, lps), (want_toks, want_lps) in zip(
            requests, served, _alone(model, params, requests)
        ):
            assert toks == want_toks, (len(prompt), n, options)
            np.testing.assert_allclose(lps, want_lps, atol=1e-3)


@pytest.mark.parametrize("what", ["prefix_cache", "prefix_l2", "model_mesh", "prefill_chunk"])
def test_what_the_model_cannot_do_is_refused(seeded, what):
    """The refusals that hold for recurrent state hold here, through the
    same checks; a chunk of more than one position against a ring that
    its window fills is refused by the ring's own check."""
    _, model, params, _ = seeded
    options = dict(slots=2, prompt_widths=(16,))
    if what == "prefix_cache":
        options.update(prefill_chunk=4, prefix_cache=8)
        match = "state snapshot at the resume position"
    elif what == "prefix_l2":
        options.update(prefill_chunk=4, prefix_l2=object())
        match = "state snapshot at the resume position"
    elif what == "model_mesh":
        from tensorflowonspark_tpu.compute.mesh import make_mesh

        options.update(mesh=make_mesh({"data": 4, "model": 2}))
        match = "no cache sharding for its recurrent leaves"
    else:
        # what the engine's chunk program would trace
        _, st = model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                            decode=True, padded=True, mutable=["cache"])
        with pytest.raises(ValueError, match="kv_cache_len=8 must be >= sliding_window"):
            model.apply({"params": params, "cache": st["cache"]},
                        jnp.zeros((1, 4), jnp.int32),
                        positions=jnp.arange(4, 8, dtype=jnp.int32)[None],
                        decode=True, padded=True, mutable=["cache"])
        return
    with pytest.raises(ValueError, match=match):
        ContinuousBatcher(model, params, **options)


def _counter(eng, name):
    return eng.metrics.window()[name]["series"][""]["delta"]


def test_kv_counters_count_each_layer_at_its_own_length(seeded):
    """Two rings of 8 and one dense plane of 64: a decode step of a slot
    reads each ring whole and the plane whole (the einsum here), so the
    counters' mean a K/V layer is (2 x 8 + 64) / 3 a slot-step, over the
    same span; a fresh prefill of width 16 scores 8 keys a query in a
    ring and 16 in the plane."""
    _, model, params, _ = seeded
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(16,), decode_block=1)
    try:
        assert dict(eng._kv_groups) == {(8, 8, 8): 2, (64, None, 64): 1}
        eng.submit(list(range(1, 12)), 5, eos_id=-1)
        reg = eng.metrics.window()
        delta = lambda name: reg[name]["series"][""]["delta"]  # noqa: E731
        steps = delta("engine_decode_steps_total")
        assert delta("engine_decode_kv_positions_span_total") == pytest.approx(
            steps * 2 * (2 * 8 + 64) / 3)
        assert delta("engine_decode_kv_positions_read_total") == pytest.approx(
            steps * 2 * (2 * 8 + 64) / 3)
        assert delta("engine_prefill_kv_positions_scored_total") == pytest.approx(
            16 * (2 * 8 + 16) / 3)
        assert delta("engine_prefill_kv_positions_span_total") == pytest.approx(
            16 * (2 * 8 + 64) / 3)
    finally:
        eng.close()


@pytest.mark.parametrize("name", ["tiny", "tiny-hybrid"])
def test_one_length_trees_count_as_before(name):
    """Where every K/V layer has one plane, the counts are that plane's,
    integers as before: steps x slots x max_seq_len spanned."""
    model = {
        "tiny": lambda: Llama(LlamaConfig.tiny(dtype=jnp.float32, remat=False, max_seq_len=56)),
        "tiny-hybrid": lambda: FalconH1(FalconH1Config.tiny(dtype=jnp.float32, max_seq_len=56)),
    }[name]()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8, 16), decode_block=1)
    try:
        assert eng._kv_groups == [((56, None, 56), 2)]
        eng.submit([1, 2, 3], 4, eos_id=-1)
        reg = eng.metrics.window()
        delta = lambda n: reg[n]["series"][""]["delta"]  # noqa: E731
        steps = delta("engine_decode_steps_total")
        assert delta("engine_decode_kv_positions_span_total") == steps * 2 * 56
        assert delta("engine_decode_kv_positions_read_total") == steps * 2 * 56
        assert delta("engine_prefill_kv_positions_scored_total") == 8 * 8
        assert delta("engine_prefill_kv_positions_span_total") == 8 * 56
        assert isinstance(eng._per_kv_layer(lambda length, *_: 3 * length), int)
    finally:
        eng.close()


# sha256 of the jaxprs (first 16 hex digits) of each accepted model's
# forward, fresh prefill and decode step at a tiny size, as traced before
# ``differential_lambda_init``, ``shared_kv`` and ``return_kv`` existed.
# A change that means to alter these programs updates them here.
JAXPRS = {
    "mistral": ("ed0555961a3f6b32", "5d48b8d092d3536f", "93c52f538dd8e8e3"),
    "mistral-ring": ("ed0555961a3f6b32", "04e69b2046bcaeba", "a6c98bb5fe20aec3"),
    "falcon_h1": ("e329d010de0a65c6", "c88080fc7ccf5c52", "0b6d72859b319180"),
    "solar_open2": ("d227fa29436bfa97", "253426564809fdd5", "a07224c91398f848"),
}


@pytest.mark.parametrize("name", sorted(JAXPRS))
def test_the_accepted_models_trace_what_they_traced(name):
    model = {
        "mistral": lambda: Llama(LlamaConfig.tiny(sliding_window=16, dtype=jnp.float32)),
        "mistral-ring": lambda: Llama(LlamaConfig.tiny(
            sliding_window=16, kv_cache_len=32, dtype=jnp.float32)),
        "falcon_h1": lambda: FalconH1(FalconH1Config.tiny(dtype=jnp.float32)),
        "solar_open2": lambda: SolarOpen2(SolarOpen2Config.tiny(dtype=jnp.float32)),
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


def test_the_engine_names_no_model():
    import inspect

    from tensorflowonspark_tpu.serving import engine

    for word in ("phi4", "sambay", "gmu", "Phi4Flash("):
        assert word not in inspect.getsource(engine)
    assert phi4_flash.Phi4Flash.head  # the engine's ``method="head"``
