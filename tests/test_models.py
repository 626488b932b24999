"""Model forward/backward tests, incl. llama sharded on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflowonspark_tpu.models import mnist
from tensorflowonspark_tpu.models.llama import (
    Llama,
    LlamaConfig,
    cross_entropy_loss,
    llama_loss_fn,
    llama_param_shardings,
)


def test_mnist_mlp_trains():
    model = mnist.MLP(hidden=32)
    batch = mnist.synthetic_batch(0, 16)
    params = model.init(jax.random.PRNGKey(0), batch["image"])["params"]
    loss = mnist.loss_fn(model.apply)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        l, g = jax.value_and_grad(loss)(params, batch)
        upd, opt_state = tx.update(g, opt_state)
        return optax.apply_updates(params, upd), opt_state, l

    l0 = None
    for i in range(20):
        params, opt_state, l = step(params, opt_state, batch)
        l0 = l0 if l0 is not None else float(l)
    assert float(l) < l0


@pytest.mark.slow
def test_unet_trains_and_shards():
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.models import unet

    cfg = unet.UNetConfig.tiny()
    model = unet.UNet(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.normal(size=(4, 16, 16, 3)), jnp.float32),
        "mask": jnp.asarray(rng.integers(0, 3, size=(4, 16, 16))),
    }
    params = model.init(jax.random.PRNGKey(0), batch["image"])["params"]
    logits = model.apply({"params": params}, batch["image"])
    assert logits.shape == (4, 16, 16, 3)
    assert logits.dtype == jnp.float32

    mesh = make_mesh({"data": -1, "fsdp": 2})
    shardings = unet.unet_param_shardings(params, mesh)
    params = jax.tree.map(jax.device_put, params, shardings)
    loss = unet.loss_fn(model)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        l, g = jax.value_and_grad(loss)(params, batch)
        upd, opt_state = tx.update(g, opt_state)
        return optax.apply_updates(params, upd), opt_state, l

    l0 = None
    for _ in range(5):
        params, opt_state, l = step(params, opt_state, batch)
        l0 = l0 if l0 is not None else float(l)
    assert float(l) < l0
    m_iou = unet.iou(model, params, batch, cfg.num_classes)
    assert 0.0 <= float(m_iou) <= 1.0


@pytest.mark.slow
def test_inception_v3_trains_and_shards():
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.models import inception

    cfg = inception.InceptionConfig.tiny()
    model = inception.InceptionV3(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.normal(size=(4, 64, 64, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, size=4), jnp.int32),
    }
    variables = model.init(jax.random.PRNGKey(0), batch["image"])
    params, batch_stats = variables["params"], variables["batch_stats"]
    logits = model.apply(
        {"params": params, "batch_stats": batch_stats}, batch["image"]
    )
    assert logits.shape == (4, 10)
    assert logits.dtype == jnp.float32

    mesh = make_mesh({"data": -1, "fsdp": 2})
    shardings = inception.inception_param_shardings(params, mesh)
    params = jax.tree.map(jax.device_put, params, shardings)
    loss = inception.loss_fn(model)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state, batch):
        (l, new_bs), g = jax.value_and_grad(loss, has_aux=True)(
            params, batch_stats, batch
        )
        upd, opt_state = tx.update(g, opt_state)
        return optax.apply_updates(params, upd), new_bs, opt_state, l

    l0 = None
    for _ in range(5):
        params, batch_stats, opt_state, l = step(
            params, batch_stats, opt_state, batch
        )
        l0 = l0 if l0 is not None else float(l)
    assert float(l) < l0


@pytest.mark.slow
def test_inception_aux_head_train_only():
    """aux_logits configs return (logits, aux) under train, logits alone
    in eval — and the aux loss contributes to the gradient."""
    from tensorflowonspark_tpu.models import inception

    cfg = inception.InceptionConfig.tiny(aux_logits=True)
    model = inception.InceptionV3(cfg)
    img = jnp.zeros((2, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), img, train=True)
    out, _ = model.apply(
        {
            "params": variables["params"],
            "batch_stats": variables["batch_stats"],
        },
        img,
        train=True,
        mutable=["batch_stats"],
    )
    logits, aux = out
    assert logits.shape == (2, 10) and aux.shape == (2, 10)
    eval_logits = model.apply(
        {
            "params": variables["params"],
            "batch_stats": variables["batch_stats"],
        },
        img,
        train=False,
    )
    assert eval_logits.shape == (2, 10)


def test_mnist_cnn_forward():
    model = mnist.CNN()
    batch = mnist.synthetic_batch(1, 4)
    params = model.init(jax.random.PRNGKey(0), batch["image"])["params"]
    logits = model.apply({"params": params}, batch["image"])
    assert logits.shape == (4, 10)
    acc = mnist.accuracy(model.apply, params, batch)
    assert 0.0 <= float(acc) <= 1.0


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    return cfg, model, params


def test_llama_forward_shape(tiny_llama):
    cfg, model, params = tiny_llama
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_llama_causality(tiny_llama):
    """Changing a future token must not affect past logits."""
    cfg, model, params = tiny_llama
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(5)
    l1 = model.apply({"params": params}, t1)
    l2 = model.apply({"params": params}, t2)
    np.testing.assert_allclose(
        np.asarray(l1[0, :10]), np.asarray(l2[0, :10]), atol=1e-5
    )
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_llama_grad_and_loss(tiny_llama):
    cfg, model, params = tiny_llama
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size)

    def loss(p):
        logits = model.apply({"params": p}, tokens[:, :-1])
        return cross_entropy_loss(logits, tokens[:, 1:])

    l, g = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l))
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(g)]
    assert all(np.isfinite(n) for n in norms)
    assert any(n > 0 for n in norms)


def test_llama_generate_topk_topp(tiny_llama):
    """top_k=1 and a vanishing nucleus must both reduce to greedy; bad
    sampling params are rejected before compilation."""
    from tensorflowonspark_tpu.models.llama import generate

    cfg, model, params = tiny_llama
    prompt = jax.random.randint(
        jax.random.PRNGKey(5), (2, 8), 0, cfg.vocab_size
    )
    greedy = generate(model, params, prompt, 6, temperature=0.0)
    k1 = generate(model, params, prompt, 6, temperature=1.0, top_k=1)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))
    p_tiny = generate(model, params, prompt, 6, temperature=1.0, top_p=1e-9)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(p_tiny))
    # sampled path stays in-vocab and respects the rng
    s1 = generate(
        model, params, prompt, 6, temperature=1.0, top_k=5, top_p=0.9,
        rng=jax.random.PRNGKey(1),
    )
    s2 = generate(
        model, params, prompt, 6, temperature=1.0, top_k=5, top_p=0.9,
        rng=jax.random.PRNGKey(1),
    )
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert int(np.asarray(s1).min()) >= 0
    assert int(np.asarray(s1).max()) < cfg.vocab_size
    # min_p ~ 1 keeps only the most likely token -> greedy again; it
    # composes with k/p by mask intersection (the static twin of the
    # engine's per-row filter)
    m1 = generate(
        model, params, prompt, 6, temperature=1.0, min_p=0.9999
    )
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(m1))
    m2 = generate(
        model, params, prompt, 6, temperature=1.0, top_k=5, min_p=0.9999
    )
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(m2))
    with pytest.raises(ValueError, match="top_k"):
        generate(model, params, prompt, 2, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        generate(model, params, prompt, 2, top_p=1.5)
    with pytest.raises(ValueError, match="min_p"):
        generate(model, params, prompt, 2, temperature=1.0, min_p=1.5)
    with pytest.raises(ValueError, match="temperature"):
        generate(model, params, prompt, 2, top_k=5)  # greedy + top_k


def test_llama_chunked_loss_matches_full(tiny_llama):
    """logit_chunk CE (no materialized (B,S,V) logits) must reproduce the
    full-logits loss and its gradients."""
    cfg, model, params = tiny_llama
    tokens = jax.random.randint(
        jax.random.PRNGKey(3), (2, 17), 0, cfg.vocab_size
    )
    full = llama_loss_fn(model)
    chunked = llama_loss_fn(model, logit_chunk=4)
    lf, gf = jax.value_and_grad(full)(params, tokens)
    lc, gc = jax.value_and_grad(chunked)(params, tokens)
    np.testing.assert_allclose(float(lf), float(lc), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        gf,
        gc,
    )
    with pytest.raises(ValueError, match="must divide"):
        jax.value_and_grad(llama_loss_fn(model, logit_chunk=5))(
            params, tokens
        )


def test_llama_kv_cache_matches_full_forward(tiny_llama):
    """Decode-mode attention against the KV cache must reproduce the
    training-path logits: prefill == full forward, and each cached
    single-token step == the last position of a full forward."""
    import numpy as np

    _, model, params = tiny_llama
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, size=(2, 12)), jnp.int32
    )

    full = model.apply({"params": params}, tokens)
    prefill_logits, state = model.apply(
        {"params": params},
        tokens[:, :8],
        positions=jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8)),
        decode=True,
        mutable=["cache"],
    )
    np.testing.assert_allclose(
        np.asarray(prefill_logits),
        np.asarray(full[:, :8]),
        rtol=2e-2,
        atol=2e-2,
    )
    cache = state["cache"]
    for pos in range(8, 12):
        step_logits, state = model.apply(
            {"params": params, "cache": cache},
            tokens[:, pos : pos + 1],
            positions=jnp.full((2, 1), pos, jnp.int32),
            decode=True,
            mutable=["cache"],
        )
        cache = state["cache"]
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]),
            np.asarray(full[:, pos]),
            rtol=2e-2,
            atol=2e-2,
        )


def test_llama_generate_greedy_matches_naive(tiny_llama):
    """generate() (cached scan) == naive greedy via full recompute."""
    import numpy as np

    from tensorflowonspark_tpu.models.llama import generate

    _, model, params = tiny_llama
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, size=(2, 6)), jnp.int32
    )
    out = generate(model, params, prompt, max_new_tokens=5)
    assert out.shape == (2, 5)

    seq = prompt
    naive = []
    for _ in range(5):
        logits = model.apply({"params": params}, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(out), np.stack([np.asarray(t) for t in naive], axis=1)
    )


def test_llama_generate_respects_max_seq_len(tiny_llama):
    import pytest as _pytest

    from tensorflowonspark_tpu.models.llama import generate

    _, model, params = tiny_llama
    prompt = jnp.zeros((1, model.cfg.max_seq_len - 2), jnp.int32)
    with _pytest.raises(ValueError, match="max_seq_len"):
        generate(model, params, prompt, max_new_tokens=8)


def test_llama_sharded_train_step(mesh8):
    """Full FSDP+TP sharded train step on the 8-device CPU mesh."""
    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import shard_batch

    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    psh = llama_param_shardings(params, mesh8)
    params = jax.tree.map(jax.device_put, params, psh)
    tx = optax.adamw(1e-3)
    state = TrainState.create(params, tx)

    def loss(p, batch):
        logits = model.apply({"params": p}, batch["tokens"][:, :-1])
        return cross_entropy_loss(logits, batch["tokens"][:, 1:])

    step = build_train_step(loss, tx, mesh8, param_shardings=psh)
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(3), (8, 17), 0, cfg.vocab_size
        )
    }
    sharded = shard_batch(mesh8, batch)
    state, l1 = step(state, sharded)
    state, l2 = step(state, sharded)
    assert float(l2) < float(l1)
    # a 2D weight is actually sharded over fsdp
    q = state.params["layer0"]["attn"]["q_proj"]["kernel"]
    assert q.sharding.spec in (
        jax.sharding.PartitionSpec("fsdp", "model"),
        jax.sharding.PartitionSpec("fsdp"),
    )


def test_resnet_forward_and_train():
    from tensorflowonspark_tpu.models.resnet import (
        ResNet,
        ResNetConfig,
        loss_fn as resnet_loss_fn,
    )

    cfg = ResNetConfig.tiny(dtype=jnp.float32)
    model = ResNet(cfg)
    img = jax.random.uniform(jax.random.PRNGKey(0), (2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(1), img, train=False)
    logits = model.apply(variables, img, train=False)
    assert logits.shape == (2, cfg.num_classes)
    assert logits.dtype == jnp.float32

    loss = resnet_loss_fn(model)
    batch = {"image": img, "label": jnp.array([1, 2])}
    tx = optax.sgd(0.1)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state, batch):
        (l, bs), g = jax.value_and_grad(loss, has_aux=True)(
            params, batch_stats, batch
        )
        upd, opt_state = tx.update(g, opt_state)
        return optax.apply_updates(params, upd), bs, opt_state, l

    l0 = None
    for _ in range(5):
        params, batch_stats, opt_state, l = step(
            params, batch_stats, opt_state, batch
        )
        l0 = l0 if l0 is not None else float(l)
    assert float(l) < l0


def test_vit_forward_and_train():
    from tensorflowonspark_tpu.models.vit import (
        ViT,
        ViTConfig,
        loss_fn as vit_loss_fn,
    )

    cfg = ViTConfig.tiny()
    model = ViT(cfg)
    img = jax.random.uniform(jax.random.PRNGKey(0), (2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(1), img)["params"]
    logits = model.apply({"params": params}, img)
    assert logits.shape == (2, cfg.num_classes)
    assert logits.dtype == jnp.float32
    # token count: (16/4)^2 patches + CLS
    assert params["pos_embed"].shape == (1, 17, cfg.hidden_size)

    loss = vit_loss_fn(model)
    batch = {"image": img, "label": jnp.array([1, 2])}
    tx = optax.sgd(0.3)
    opt_state = tx.init(params)
    l0 = None
    for _ in range(20):
        l, g = jax.value_and_grad(loss)(params, batch)
        if l0 is None:
            l0 = float(l)
        upd, opt_state = tx.update(g, opt_state)
        params = optax.apply_updates(params, upd)
    assert float(l) < l0, (float(l), l0)  # overfits 2 examples


def test_vit_b16_config_scale():
    from tensorflowonspark_tpu.models.vit import ViTConfig

    cfg = ViTConfig.b16()
    # canonical ViT-B/16: 196 patches, 12 layers, hidden 768
    assert (cfg.image_size // cfg.patch_size) ** 2 == 196
    assert cfg.num_layers == 12 and cfg.hidden_size == 768


def test_resnet50_config_depth():
    from tensorflowonspark_tpu.models.resnet import ResNetConfig

    cfg = ResNetConfig.resnet50()
    # 3+4+6+3 bottleneck blocks * 3 convs + stem + fc = the canonical 50
    assert sum(cfg.stage_sizes) * 3 + 2 == 50


def test_resnet_sharded(mesh8):
    from tensorflowonspark_tpu.models.resnet import (
        ResNet,
        ResNetConfig,
        resnet_param_shardings,
    )

    cfg = ResNetConfig.tiny(dtype=jnp.float32, width=8)
    model = ResNet(cfg)
    img = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), img, train=False)
    psh = resnet_param_shardings(variables["params"], mesh8)
    params = jax.tree.map(jax.device_put, variables["params"], psh)
    logits = jax.jit(
        lambda p, x: model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]}, x, train=False
        )
    )(params, img)
    assert logits.shape == (2, cfg.num_classes)


@pytest.fixture(scope="module")
def tiny_bert():
    from tensorflowonspark_tpu.models.bert import Bert, BertConfig

    cfg = BertConfig.tiny(dtype=jnp.float32)
    model = Bert(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    return cfg, model, params


def test_bert_forward_shapes(tiny_bert):
    cfg, model, params = tiny_bert
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    seq, pooled = model.apply({"params": params}, tokens)
    assert seq.shape == (2, 16, cfg.hidden_size)
    assert pooled.shape == (2, cfg.hidden_size)


def test_bert_bidirectional(tiny_bert):
    """Unlike llama, changing a late token MUST change early outputs."""
    cfg, model, params = tiny_bert
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 12].set(5)
    s1, _ = model.apply({"params": params}, t1)
    s2, _ = model.apply({"params": params}, t2)
    assert not np.allclose(np.asarray(s1[0, :5]), np.asarray(s2[0, :5]), atol=1e-6)


def test_bert_padding_mask(tiny_bert):
    """With a padding mask, changing a PAD token must not change real outputs."""
    cfg, model, params = tiny_bert
    mask = jnp.concatenate([jnp.ones((1, 10), jnp.int32), jnp.zeros((1, 6), jnp.int32)], -1)
    t1 = jnp.ones((1, 16), jnp.int32)
    t2 = t1.at[0, 14].set(7)  # only a padded position differs
    s1, _ = model.apply({"params": params}, t1, attention_mask=mask)
    s2, _ = model.apply({"params": params}, t2, attention_mask=mask)
    np.testing.assert_allclose(
        np.asarray(s1[0, :10]), np.asarray(s2[0, :10]), atol=1e-5
    )


def test_bert_classifier_trains(mesh8):
    from tensorflowonspark_tpu.models.bert import (
        BertConfig,
        BertForClassification,
        bert_param_shardings,
        classification_loss_fn,
    )
    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import shard_batch

    cfg = BertConfig.tiny(dtype=jnp.float32)
    model = BertForClassification(cfg, num_classes=3)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    psh = bert_param_shardings(params, mesh8)
    params = jax.tree.map(jax.device_put, params, psh)
    tx = optax.adamw(1e-3)
    state = TrainState.create(params, tx)
    loss = classification_loss_fn(model)
    step = build_train_step(loss, tx, mesh8, param_shardings=psh)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab_size),
        "label": jax.random.randint(jax.random.PRNGKey(3), (8,), 0, 3),
    }
    sharded = shard_batch(mesh8, batch)
    state, l1 = step(state, sharded)
    for _ in range(4):
        state, l = step(state, sharded)
    assert float(l) < float(l1)


def test_bert_mlm_trains(tiny_bert):
    """Masked-LM head: masked-position CE drops over a few steps."""
    from tensorflowonspark_tpu.models.bert import BertForMLM

    cfg, _, _ = tiny_bert
    model = BertForMLM(config=cfg)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(4, 16)), jnp.int32
    )
    mask_pos = jnp.asarray(rng.random(size=(4, 16)) < 0.25)
    inputs = jnp.where(mask_pos, 0, tokens)  # 0 = [MASK]
    params = model.init(jax.random.PRNGKey(0), inputs)["params"]

    def loss_fn(p):
        logits = model.apply({"params": p}, inputs)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tokens)
        return jnp.sum(ce * mask_pos) / jnp.maximum(jnp.sum(mask_pos), 1)

    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        l, g = jax.value_and_grad(loss_fn)(params)
        upd, opt_state = tx.update(g, opt_state)
        return optax.apply_updates(params, upd), opt_state, l

    l0 = None
    for _ in range(10):
        params, opt_state, l = step(params, opt_state)
        l0 = l0 if l0 is not None else float(l)
    assert float(l) < l0


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_llama_remat_policies_match_no_remat(policy):
    """Every remat policy computes the same loss and grads as remat=False."""
    tokens = jax.random.randint(
        jax.random.PRNGKey(3), (2, 17), 0, 64
    ).astype(jnp.int32)

    def loss_and_grad(remat, remat_policy="full"):
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32,
            vocab_size=64,
            remat=remat,
            remat_policy=remat_policy,
        )
        model = Llama(cfg)
        params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
        fn = llama_loss_fn(model)
        return jax.value_and_grad(lambda p: fn(p, tokens))(params)

    chex = pytest.importorskip("chex")
    base_loss, base_grad = loss_and_grad(False)
    l, g = loss_and_grad(True, policy)
    assert float(l) == pytest.approx(float(base_loss), rel=1e-6)
    chex.assert_trees_all_close(g, base_grad, rtol=1e-5, atol=1e-6)


def test_llama_packed_sequences_match_separate_docs(tiny_llama):
    """Packing two documents into one row with segment_ids must give the
    same total NLL as encoding each document separately: attention is
    isolated per document, RoPE positions restart at each boundary, and
    the boundary target (doc A's last token predicting doc B's first) is
    dropped from the loss."""
    cfg, model, params = tiny_llama
    rng = np.random.default_rng(7)
    a = rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)  # doc A
    b = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)  # doc B

    packed = jnp.asarray(np.concatenate([a, b])[None])  # (1, 17)
    # ids start at 1: segment id 0 means PADDING and is dropped from loss
    seg = jnp.asarray(
        np.concatenate([np.full(9, 1, np.int32), np.full(8, 2, np.int32)])[
            None
        ]
    )

    loss = llama_loss_fn(model)
    packed_loss = float(loss(params, packed, segment_ids=seg))

    # separate-document reference: per-doc mean NLL, recombined by
    # target counts (8 targets in A, 7 in B; the boundary target is
    # excluded from the packed loss by the mask)
    la = float(loss(params, jnp.asarray(a[None])))
    lb = float(loss(params, jnp.asarray(b[None])))
    expected = (la * 8 + lb * 7) / 15
    np.testing.assert_allclose(packed_loss, expected, rtol=1e-5)

    # chunked CE agrees on the packed input too (17 -> 16 targets, 4|16)
    chunked = llama_loss_fn(model, logit_chunk=4)
    np.testing.assert_allclose(
        float(chunked(params, packed, segment_ids=seg)),
        packed_loss,
        rtol=1e-5,
    )


def test_llama_packed_reused_ids_do_not_leak(tiny_llama):
    """A packer that reuses a segment id for a later document (e.g.
    [1,1,2,2,1,1]) must still get document isolation: llama_loss_fn
    canonicalizes adjacency runs before the equality-based attention
    mask sees them."""
    cfg, model, params = tiny_llama
    rng = np.random.default_rng(11)
    docs = [
        rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        for n in (6, 6, 5)
    ]
    packed = jnp.asarray(np.concatenate(docs)[None])  # (1, 17)
    reused = np.concatenate(
        [np.full(6, 1), np.full(6, 2), np.full(5, 1)]
    ).astype(np.int32)[None]
    unique = np.concatenate(
        [np.full(6, 1), np.full(6, 2), np.full(5, 3)]
    ).astype(np.int32)[None]

    loss = llama_loss_fn(model)
    l_reused = float(loss(params, packed, segment_ids=jnp.asarray(reused)))
    l_unique = float(loss(params, packed, segment_ids=jnp.asarray(unique)))
    np.testing.assert_allclose(l_reused, l_unique, rtol=1e-6)


def test_llama_packed_decode_matches_per_document(tiny_llama):
    """The segment-masked KV cache: packed
    two-document prefill under decode=True must produce exactly the
    logits each document gets when prefilled alone, and continuing a
    chosen document against the packed cache must decode the same
    greedy tokens as continuing it against its own unpacked cache."""
    cfg, model, params = tiny_llama
    rng = np.random.default_rng(13)
    a = rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
    b = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    packed = jnp.asarray(np.concatenate([a, b])[None])  # (1, 17)
    seg = jnp.asarray(
        np.concatenate([np.full(9, 1, np.int32), np.full(8, 2, np.int32)])[
            None
        ]
    )

    # packed prefill: positions=None -> per-document RoPE restart
    packed_logits, packed_cache = model.apply(
        {"params": params}, packed, segment_ids=seg, decode=True,
        mutable=["cache"],
    )
    alone = {}
    for name, doc in (("a", a), ("b", b)):
        alone[name] = model.apply(
            {"params": params}, jnp.asarray(doc[None]), decode=True,
            mutable=["cache"],
        )
    np.testing.assert_allclose(
        np.asarray(packed_logits[0, :9]),
        np.asarray(alone["a"][0][0]),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(packed_logits[0, 9:]),
        np.asarray(alone["b"][0][0]),
        rtol=1e-5, atol=1e-6,
    )

    # continue document B for 4 greedy steps against each cache: the
    # packed cache writes at global slots (17, 18, ...) while the
    # unpacked one writes at (8, 9, ...), but the segment mask makes
    # the attended sets identical, so the tokens must be too
    def continue_doc(cache, first_logits_row, seg_id, start_pos):
        toks, cache = [], dict(cache)
        tok = jnp.argmax(first_logits_row).astype(jnp.int32)[None, None]
        for i in range(4):
            toks.append(int(tok[0, 0]))
            sids = None
            if seg_id is not None:
                sids = jnp.full((1, 1), seg_id, jnp.int32)
            logits, updated = model.apply(
                {"params": params, "cache": cache},
                tok,
                positions=jnp.asarray([[start_pos + i]], jnp.int32),
                segment_ids=sids,
                decode=True,
                mutable=["cache"],
            )
            cache = updated["cache"]
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[
                :, None
            ]
        return toks

    from_packed = continue_doc(
        packed_cache["cache"], packed_logits[0, -1], seg_id=2, start_pos=8
    )
    _, alone_cache = alone["b"]
    from_alone = continue_doc(
        alone_cache["cache"], alone["b"][0][0, -1], seg_id=None, start_pos=8
    )
    assert from_packed == from_alone

    # padded + packed is rejected (scatter slots vs global slots)
    with pytest.raises(ValueError, match="padded"):
        model.apply(
            {"params": params}, packed, positions=jnp.zeros_like(packed),
            segment_ids=seg, decode=True, padded=True, mutable=["cache"],
        )


def test_llama_generate_mesh_sharded_matches_single_device(tiny_llama):
    """Mesh-sharded decode: greedy decode
    with weights TP-sharded on 'model' and batch + KV caches sharded on
    'data' must be token-identical to the single-device decode — the
    serving-side analog of what the FSDP tests prove for training."""
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.models.llama import generate

    cfg, model, params = tiny_llama  # heads=4, kv_heads=2, fp32
    mesh = make_mesh({"data": 4, "model": 2})
    prompt = jax.random.randint(
        jax.random.PRNGKey(5), (4, 12), 0, cfg.vocab_size
    ).astype(jnp.int32)

    single = generate(model, params, prompt, max_new_tokens=8)
    sharded = generate(model, params, prompt, max_new_tokens=8, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(single), np.asarray(sharded))

    # mixed-length (padded) prompts under the mesh
    lengths = jnp.asarray([5, 12, 7, 9], jnp.int32)
    single_p = generate(
        model, params, prompt, max_new_tokens=8, prompt_lengths=lengths
    )
    sharded_p = generate(
        model, params, prompt, max_new_tokens=8, prompt_lengths=lengths,
        mesh=mesh,
    )
    np.testing.assert_array_equal(np.asarray(single_p), np.asarray(sharded_p))

    # EOS early-stop path under the mesh
    eos = int(np.asarray(single)[0, 2])
    single_e = generate(model, params, prompt, max_new_tokens=8, eos_id=eos)
    sharded_e = generate(
        model, params, prompt, max_new_tokens=8, eos_id=eos, mesh=mesh
    )
    np.testing.assert_array_equal(np.asarray(single_e), np.asarray(sharded_e))

    # clear errors instead of GSPMD padding surprises
    with pytest.raises(ValueError, match="data"):
        generate(model, params, prompt[:3], max_new_tokens=4, mesh=mesh)
    with pytest.raises(ValueError, match="model"):
        generate(
            model, params, prompt, max_new_tokens=4,
            mesh=make_mesh({"model": 8}),
        )


def test_llama_generate_eos_early_stop(tiny_llama):
    """eos_id semantics: identical to the plain decode up to and
    including each row's first EOS, eos_id-filled afterwards; and a
    never-appearing eos_id reproduces the plain decode exactly."""
    from tensorflowonspark_tpu.models.llama import generate

    cfg, model, params = tiny_llama
    prompt = jax.random.randint(
        jax.random.PRNGKey(9), (2, 4), 0, cfg.vocab_size
    )
    ref = np.asarray(generate(model, params, prompt, max_new_tokens=12))

    # pick row 0's 4th generated token as the "EOS": the eos run must
    # match ref until that emission, then pad with eos_id
    eos = int(ref[0, 3])
    out = np.asarray(
        generate(model, params, prompt, max_new_tokens=12, eos_id=eos)
    )
    for row in range(2):
        hits = np.where(ref[row] == eos)[0]
        cut = (hits[0] + 1) if len(hits) else 12
        np.testing.assert_array_equal(out[row, :cut], ref[row, :cut])
        assert (out[row, cut:] == eos).all()

    # an id outside the vocab can never be emitted: exact match
    out2 = np.asarray(
        generate(
            model, params, prompt, max_new_tokens=12,
            eos_id=cfg.vocab_size + 1,
        )
    )
    np.testing.assert_array_equal(out2, ref)


def test_llama_generate_padded_prompts_match_unpadded(tiny_llama):
    """Mixed-length batch decode: right-padded prompts + prompt_lengths
    must produce, row for row, exactly what each prompt generates alone
    unpadded (per-row first-token selection, per-row positions, padding
    slots overwritten in the cache)."""
    from tensorflowonspark_tpu.models.llama import generate

    cfg, model, params = tiny_llama
    rng = np.random.default_rng(5)
    p_a = rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)
    p_b = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)

    ref_a = np.asarray(
        generate(model, params, jnp.asarray(p_a[None]), max_new_tokens=8)
    )
    ref_b = np.asarray(
        generate(model, params, jnp.asarray(p_b[None]), max_new_tokens=8)
    )

    padded = np.zeros((2, 6), np.int32)
    padded[0, :4] = p_a
    padded[1] = p_b
    out = np.asarray(
        generate(
            model,
            params,
            jnp.asarray(padded),
            max_new_tokens=8,
            prompt_lengths=jnp.asarray([4, 6]),
        )
    )
    np.testing.assert_array_equal(out[0], ref_a[0])
    np.testing.assert_array_equal(out[1], ref_b[0])

    # composes with eos_id (the while_loop path)
    eos = int(ref_a[0, 2])
    out_eos = np.asarray(
        generate(
            model,
            params,
            jnp.asarray(padded),
            max_new_tokens=8,
            prompt_lengths=jnp.asarray([4, 6]),
            eos_id=eos,
        )
    )
    hits = np.where(ref_a[0] == eos)[0]
    cut = hits[0] + 1
    np.testing.assert_array_equal(out_eos[0, :cut], ref_a[0, :cut])
    assert (out_eos[0, cut:] == eos).all()


# -- sliding-window attention (Mistral-family) -------------------------


@pytest.fixture(scope="module")
def tiny_windowed():
    import dataclasses

    cfg = LlamaConfig.tiny(
        dtype=jnp.float32, remat=False, sliding_window=5
    )
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    full = Llama(dataclasses.replace(cfg, sliding_window=None))
    return cfg, model, full, params


def test_sliding_window_changes_long_range_logits(tiny_windowed):
    """Sanity: beyond the window the outputs must differ from full
    attention (a vacuous window would make every other test here
    meaningless), while a window >= seq matches full exactly."""
    import dataclasses

    cfg, model, full, params = tiny_windowed
    toks = jax.random.randint(
        jax.random.PRNGKey(3), (1, 12), 0, cfg.vocab_size
    )
    w = np.asarray(model.apply({"params": params}, toks))
    f = np.asarray(full.apply({"params": params}, toks))
    np.testing.assert_allclose(w[0, :5], f[0, :5], rtol=1e-5, atol=1e-6)
    assert np.abs(w[0, 5:] - f[0, 5:]).max() > 1e-4
    wide = Llama(dataclasses.replace(cfg, sliding_window=12))
    np.testing.assert_allclose(
        np.asarray(wide.apply({"params": params}, toks)), f,
        rtol=1e-5, atol=1e-6,
    )


def test_sliding_window_cached_decode_matches_forward(tiny_windowed):
    """Teacher-forced cached decode (prefill + per-token steps) must
    reproduce the training-path windowed logits exactly — the cache's
    position-plane mask is the same window the tril mask expresses."""
    cfg, model, full, params = tiny_windowed
    toks = jax.random.randint(
        jax.random.PRNGKey(5), (2, 11), 0, cfg.vocab_size
    )
    want = np.asarray(model.apply({"params": params}, toks))
    # prefill 6, then 5 single-token steps
    logits_p, state = model.apply(
        {"params": params}, toks[:, :6], decode=True, mutable=["cache"]
    )
    got = [np.asarray(logits_p)]
    cache = state["cache"]
    for i in range(6, 11):
        logits_i, state = model.apply(
            {"params": params, "cache": cache},
            toks[:, i : i + 1],
            positions=jnp.full((2, 1), i, jnp.int32),
            decode=True,
            mutable=["cache"],
        )
        cache = state["cache"]
        got.append(np.asarray(logits_i))
    np.testing.assert_allclose(
        np.concatenate(got, axis=1), want, rtol=1e-5, atol=1e-6
    )


def test_sliding_window_generate_engine_parity(tiny_windowed):
    """generate() and the continuous engine agree under a window config
    (the padded-scatter path writes the position plane correctly)."""
    from tensorflowonspark_tpu.models.llama import generate
    from tensorflowonspark_tpu.serving import ContinuousBatcher

    cfg, model, full, params = tiny_windowed
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), prefill_chunk=3
    )
    try:
        for p in ([1, 2, 3], [7, 5, 2, 9, 4, 8, 6]):
            want = np.asarray(
                generate(model, params, jnp.asarray([p], jnp.int32), 6)
            )[0].tolist()
            assert eng.submit(p, 6) == want, p
    finally:
        eng.close()


def test_sliding_window_packed_prefill_matches_per_document(
    tiny_windowed,
):
    """Packed windowed prefill: the window applies within each document
    (position distance), composed with the segment mask."""
    cfg, model, full, params = tiny_windowed
    rng = np.random.default_rng(17)
    a = rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
    b = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    packed = jnp.asarray(np.concatenate([a, b])[None])
    seg = jnp.asarray(
        np.concatenate(
            [np.full(9, 1, np.int32), np.full(8, 2, np.int32)]
        )[None]
    )
    packed_logits, _ = model.apply(
        {"params": params}, packed, segment_ids=seg, decode=True,
        mutable=["cache"],
    )
    for sl, doc in ((slice(0, 9), a), (slice(9, 17), b)):
        alone, _ = model.apply(
            {"params": params}, jnp.asarray(doc[None]), decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(packed_logits[0, sl]),
            np.asarray(alone[0]),
            rtol=1e-5, atol=1e-6,
        )


def test_rolling_kv_cache_matches_dense_windowed():
    """kv_cache_len < max_seq_len: slots wrap (slot = pos % C) and the
    positional mask reproduces dense windowed attention exactly, long
    past the wrap point; the cache really is C slots, not max_seq_len."""
    import dataclasses

    cfg = LlamaConfig.tiny(
        dtype=jnp.float32, remat=False, sliding_window=5, kv_cache_len=8
    )
    model = Llama(cfg)
    dense = Llama(dataclasses.replace(cfg, kv_cache_len=None))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    toks = jax.random.randint(
        jax.random.PRNGKey(7), (2, 24), 0, cfg.vocab_size
    )
    want = np.asarray(dense.apply({"params": params}, toks))

    # prefill in width-4 chunks (C - W + 1), then single-token steps —
    # positions wrap the 8-slot cache three times over 24 tokens
    got = []
    cache = None
    for start in range(0, 16, 4):
        piece = toks[:, start : start + 4]
        pos = (
            jnp.arange(start, start + 4, dtype=jnp.int32)[None, :]
            .repeat(2, axis=0)
        )
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        logits, state = model.apply(
            variables, piece, positions=pos, decode=True, mutable=["cache"]
        )
        cache = state["cache"]
        got.append(np.asarray(logits))
    for i in range(16, 24):
        logits, state = model.apply(
            {"params": params, "cache": cache},
            toks[:, i : i + 1],
            positions=jnp.full((2, 1), i, jnp.int32),
            decode=True,
            mutable=["cache"],
        )
        cache = state["cache"]
        got.append(np.asarray(logits))
    np.testing.assert_allclose(
        np.concatenate(got, axis=1), want, rtol=1e-5, atol=1e-6
    )
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if leaf.ndim >= 2:
            assert leaf.shape[1] == 8, (path, leaf.shape)  # C, not 128


def test_rolling_kv_cache_engine_parity_and_int8():
    """The serving composition: rolling cache + chunked prefill +
    prefix cache + int8 KV in the continuous engine, token-identical
    to generate() under the same config (short prompts keep generate's
    whole-prompt prefill within the write-width bound)."""
    import dataclasses

    from tensorflowonspark_tpu.models.llama import generate
    from tensorflowonspark_tpu.serving import ContinuousBatcher

    cfg = LlamaConfig.tiny(
        dtype=jnp.float32, remat=False, sliding_window=5, kv_cache_len=12,
        kv_cache_dtype="int8",
    )
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), prefill_chunk=4,
        prefix_cache=4,
    )
    try:
        for p in ([1, 2, 3], [7, 5, 2, 9], [1, 2, 3, 8]):
            want = np.asarray(
                generate(model, params, jnp.asarray([p], jnp.int32), 9)
            )[0].tolist()
            assert eng.submit(p, 9) == want, p
    finally:
        eng.close()


def test_rolling_kv_cache_validation():
    cfg = LlamaConfig.tiny(
        dtype=jnp.float32, remat=False, kv_cache_len=16
    )  # no sliding_window
    model = Llama(cfg)
    with pytest.raises(ValueError, match="sliding_window"):
        model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32),
            decode=True,
        )
    cfg2 = LlamaConfig.tiny(
        dtype=jnp.float32, remat=False, sliding_window=8, kv_cache_len=10
    )
    model2 = Llama(cfg2)
    # a call that starts its sequence attends the K/V in hand, so it may
    # be as wide as it likes; one that was handed the ring may not wrap
    # onto slots it still attends
    variables = model2.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), decode=True,
    )
    with pytest.raises(ValueError, match="write width"):
        # width-8 write into a 10-slot cache with window 8: 10 < 8+8-1
        model2.apply(
            variables, jnp.zeros((1, 8), jnp.int32),
            positions=jnp.arange(8, 16, dtype=jnp.int32)[None],
            decode=True, mutable=["cache"],
        )


def test_rolling_kv_cache_rejects_packed_rows():
    cfg = LlamaConfig.tiny(
        dtype=jnp.float32, remat=False, sliding_window=4, kv_cache_len=8
    )
    model = Llama(cfg)
    seg = jnp.asarray([[1, 1, 2, 2]], jnp.int32)
    with pytest.raises(ValueError, match="collide"):
        model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32),
            segment_ids=seg, decode=True,
        )
