"""The dropless expert layer (``parallel/moe.py``): routing against a
per-token loop, nothing dropped whatever the imbalance, the shards' parts
adding up to the uncut layer, and softmax routing still expressible."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.parallel.moe import (
    DroplessMoE,
    MoEConfig,
    dropless_experts,
    route,
    top_k_routing,
)

E, K, D, F = 16, 4, 32, 48


def _layer(**kw):
    base = dict(
        num_experts=E, top_k=K, intermediate_size=F,
        shared_size=24, scaling=2.5, dtype=jnp.float32,
    )
    base.update(kw)
    return DroplessMoE(**base)


@pytest.fixture(scope="module")
def uncut():
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, D))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    # flax's 0.02 makes flat scores and outputs: spread both
    params = jax.tree.map(lambda a: a * 20.0, params)
    return layer, params, x


def _swiglu(t, g, u, d):
    return (nn.silu(t @ g) * (t @ u)) @ d


def _loop(params, x, first=0, held=E, scoring="sigmoid", norm=True,
          scaling=2.5, shared=True):
    """One token at a time, one chosen expert at a time."""
    t = np.asarray(x.reshape(-1, D))
    logits = t @ np.asarray(params["router"])
    if scoring == "sigmoid":
        s = 1.0 / (1.0 + np.exp(-logits))
    else:
        s = np.exp(logits - logits.max(-1, keepdims=True))
        s = s / s.sum(-1, keepdims=True)
    out = np.zeros_like(t)
    sizes = np.zeros((held,), np.int64)
    for i in range(t.shape[0]):
        chosen = np.argsort(-s[i], kind="stable")[:K]
        w = s[i][chosen]
        if norm:
            w = w / (w.sum() + 1e-20)
        for e, we in zip(chosen, w * scaling):
            if first <= e < first + held:
                j = e - first
                out[i] += we * np.asarray(_swiglu(
                    t[i], params["w_gate"][j], params["w_up"][j],
                    params["w_down"][j],
                ))
                sizes[j] += 1
        if shared:
            out[i] += np.asarray(_swiglu(
                t[i], params["shared_gate"]["kernel"],
                params["shared_up"]["kernel"], params["shared_down"]["kernel"],
            ))
    return out.reshape(x.shape), sizes


def test_layer_equals_a_per_token_loop(uncut):
    layer, params, x = uncut
    y, sizes = layer.apply({"params": params}, x)
    want, want_sizes = _loop(params, x)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)
    assert np.asarray(sizes).tolist() == want_sizes.tolist()
    assert int(sizes.sum()) == x.shape[0] * x.shape[1] * K  # all held: all routed


def _shard(params, first, held):
    cut = dict(params)
    for name in ("w_gate", "w_up", "w_down"):
        cut[name] = params[name][first : first + held]
    return cut


@pytest.mark.parametrize("held", [4, 8])
def test_the_shards_parts_add_up_to_the_uncut_layer(uncut, held):
    """Every shard routes over the whole range and computes its own
    experts' part; the parts and the shared expert, counted once, are the
    uncut layer."""
    layer, params, x = uncut
    whole, _ = layer.apply({"params": params}, x)
    shared_only, none = _layer(held=0).apply(
        {"params": _shard(params, 0, 0)}, x
    )
    assert none.shape == (0,)
    total, pairs = np.asarray(shared_only, np.float64), 0
    for first in range(0, E, held):
        part, sizes = _layer(first_held=first, held=held).apply(
            {"params": _shard(params, first, held)}, x
        )
        total += np.asarray(part, np.float64) - np.asarray(shared_only, np.float64)
        pairs += int(sizes.sum())
    np.testing.assert_allclose(total, np.asarray(whole), atol=3e-4)
    assert pairs == x.shape[0] * x.shape[1] * K


def test_all_tokens_to_one_expert_and_nothing_dropped():
    """Every token's every choice on one held expert: a capacity buffer
    would drop most of them; here each is computed."""
    t, k = 24, 2
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(key[0], (t, D))
    banks = [jax.random.normal(kk, s) * 0.3 for kk, s in zip(
        key[1:], [(3, D, F), (3, D, F), (3, F, D)])]
    experts = jnp.full((t, k), 6, jnp.int32)  # held: 5, 6, 7
    weights = jnp.full((t, k), 0.5, jnp.float32)
    y, sizes = dropless_experts(x, weights, experts, *banks, first_held=5)
    assert sizes.tolist() == [0, t * k, 0]
    want = _swiglu(x, banks[0][1], banks[1][1], banks[2][1])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
    # and with every choice on absent experts the part is exactly zero
    y, sizes = dropless_experts(
        x, weights, jnp.full((t, k), 2, jnp.int32), *banks, first_held=5
    )
    assert sizes.tolist() == [0, 0, 0] and not np.asarray(y).any()


def test_rows_past_the_real_ones_cannot_leak(uncut):
    """The buffer's tail is unspecified by contract: poison what the
    grouped product returns there and the result must not move."""
    from tensorflowonspark_tpu.parallel import moe

    layer, params, x = uncut
    cut = _layer(first_held=4, held=4)
    want, _ = cut.apply({"params": _shard(params, 4, 4)}, x)
    real = moe.grouped_matmul

    def poisoned(xs, bank, group_sizes):
        out = real(xs, bank, group_sizes)
        tail = jnp.arange(out.shape[0])[:, None] >= jnp.sum(group_sizes)
        return jnp.where(tail, jnp.nan, out)

    moe.grouped_matmul = poisoned
    try:
        got, _ = cut.apply({"params": _shard(params, 4, 4)}, x)
    finally:
        moe.grouped_matmul = real
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("scoring,norm", [
    ("sigmoid", True), ("sigmoid", False), ("softmax", True), ("softmax", False),
])
def test_scoring_and_renormalisation_are_arguments(uncut, scoring, norm):
    _, params, x = uncut
    layer = _layer(scoring=scoring, norm_topk_prob=norm, scaling=1.5,
                   first_held=2, held=6, shared_size=0)
    cut = {k: v for k, v in _shard(params, 2, 6).items() if "shared" not in k}
    y, _ = layer.apply({"params": cut}, x)
    want, _ = _loop(cut, x, first=2, held=6, scoring=scoring, norm=norm,
                    scaling=1.5, shared=False)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)


def test_softmax_then_renormalise_is_the_capacity_layers_routing():
    """``route`` with softmax scoring and renormalisation gives the gates
    ``top_k_routing`` gives where nothing is over capacity."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (12, 8)) * 2.0
    weights, experts = route(logits, 2, scoring="softmax")
    cfg = MoEConfig(num_experts=8, top_k=2, capacity_factor=8.0)
    _, combine, _ = top_k_routing(logits, cfg, 12)
    gates = np.asarray(combine.sum(-1))  # (T, E)
    for t in range(12):
        for w, e in zip(np.asarray(weights[t]), np.asarray(experts[t])):
            assert gates[t, e] == pytest.approx(float(w), abs=1e-6)
    assert np.count_nonzero(gates) == 24


def test_route_refuses_an_unknown_scoring_and_a_shard_outside_the_range(uncut):
    with pytest.raises(ValueError, match="scoring"):
        route(jnp.zeros((2, 4)), 2, scoring="tanh")
    _, params, x = uncut
    with pytest.raises(ValueError, match="router"):
        _layer(first_held=14, held=4).apply({"params": _shard(params, 12, 4)}, x)


@pytest.mark.parametrize("rows", [1024, 8192])  # a decode step's, a prefill's
@pytest.mark.parametrize("d,f,want", [
    (4096, 1280, (512, 1280)),  # Solar-Open2 gate, up: the bank's whole width
    (1280, 4096, (256, 2048)),  # Solar-Open2 down
    (7680, 2048, (512, 2048)),  # the expert cell's gate, up: as PR 31 measured
    (2048, 7680, (512, 1920)),  # the expert cell's down: no power of two
])
def test_gmm_tiling_takes_the_widest_tiles_that_divide(rows, d, f, want):
    from tensorflowonspark_tpu.parallel import moe

    tm, tk, tn = moe.gmm_tiling(rows, d, f, 2)
    assert (tm, tk, tn) == (128, *want)
    for tile, size in ((tk, d), (tn, f)):
        assert tile % 128 == 0 and size % tile == 0
    # the budget the rule states: the bank tile's two pipeline buffers
    # and the float32 accumulator
    assert 2 * tk * tn * 2 <= moe._GMM_BANK_TILE_BYTES
    assert tm * tn * 4 <= 128 * moe._GMM_COLUMNS * 4
    # wider elements get a smaller tile; a dimension that no multiple of
    # 128 divides is taken whole, as before
    _, tk4, tn4 = moe.gmm_tiling(rows, d, f, 4)
    assert 2 * tk4 * tn4 * 4 <= moe._GMM_BANK_TILE_BYTES
    assert moe.gmm_tiling(16, 32, 48, 4) == (128, 32, 48)


@pytest.mark.parametrize("d,f,tiles", [
    (128, 256, (128, 128, 256)),
    (256, 1280, (128, 256, 1280)),  # a column tile that is no power of two
])
def test_the_pallas_grouped_matmul_equals_ragged_dot(monkeypatch, d, f, tiles):
    """On a TPU ``grouped_matmul`` takes the installed Pallas kernel
    (here in the interpreter); its rows of real groups are ragged_dot's,
    whatever lies past them."""
    from tensorflowonspark_tpu.ops import attention as attn_mod
    from tensorflowonspark_tpu.parallel import moe

    assert moe.gmm_tiling(256, d, f, 4) == tiles
    key = jax.random.split(jax.random.PRNGKey(7), 2)
    xs = jax.random.normal(key[0], (256, d))
    bank = jax.random.normal(key[1], (4, d, f)) * 0.1
    sizes = jnp.asarray([3, 0, 130, 40], jnp.int32)
    want = moe.grouped_matmul(xs, bank, sizes)
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    monkeypatch.setattr(moe, "INTERPRET", True)
    assert moe._pallas_gmm()
    got = moe.grouped_matmul(xs, bank, sizes)
    np.testing.assert_allclose(
        np.asarray(got[:173]), np.asarray(want[:173]), atol=2e-4
    )
    # rows that are no whole tile of the kernel's (a batch of two, top-8)
    few = jnp.asarray([5, 0, 9, 2], jnp.int32)
    got = moe.grouped_matmul(xs[:16], bank, few)
    assert got.shape == (16, f)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jax.lax.ragged_dot(xs[:16], bank, few)),
        atol=2e-4,
    )
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.parallel import use_mesh

    with use_mesh(make_mesh({"data": 8})):
        assert not moe._pallas_gmm()  # GSPMD partitions ragged_dot
