"""Compute-layer tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tensorflowonspark_tpu.compute import (
    TrainState,
    build_train_step,
    fsdp_shardings,
    make_mesh,
)
from tensorflowonspark_tpu.compute.mesh import shard_batch
from tensorflowonspark_tpu.compute.train import state_shardings
from tensorflowonspark_tpu.compute.mesh import replicated


def test_make_mesh_shapes():
    m = make_mesh({"data": 2, "fsdp": 4})
    assert m.shape["data"] == 2 and m.shape["fsdp"] == 4 and m.shape["model"] == 1
    m2 = make_mesh({"fsdp": -1})
    assert m2.shape["fsdp"] == 8
    with pytest.raises(ValueError):
        make_mesh({"data": 3})
    with pytest.raises(ValueError):
        make_mesh({"bogus": 8})


def test_fsdp_shardings_rules(mesh8):
    params = {
        "w": jnp.zeros((16, 64)),   # 64 % 4 == 0 -> shard dim 1 (largest)
        "b": jnp.zeros((64,)),      # tiny -> replicated
        "odd": jnp.zeros((6, 4096)),  # shard largest divisible dim
    }
    sh = fsdp_shardings(params, mesh8, min_shard_elements=128)
    assert sh["w"].spec == P(None, "fsdp")
    assert sh["b"].spec == P()
    assert sh["odd"].spec == P(None, "fsdp")


def test_train_step_dp_matches_single_device(mesh_dp):
    """DP over 8 devices must give the same result as 1 device."""

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    tx = optax.sgd(0.1)
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32))
    batch = {
        "x": rng.normal(size=(16, 4)).astype(np.float32),
        "y": rng.normal(size=(16, 2)).astype(np.float32),
    }

    # single-device reference
    state1 = TrainState.create({"w": w0}, tx)
    loss1, grads = jax.value_and_grad(loss_fn)({"w": w0}, batch)
    upd, _ = tx.update(grads, state1.opt_state, state1.params)
    ref_w = optax.apply_updates(state1.params, upd)["w"]

    # sharded step
    step = build_train_step(loss_fn, tx, mesh_dp)
    state = TrainState.create({"w": w0}, tx)
    sharded = shard_batch(mesh_dp, batch)
    state2, loss2 = step(state, sharded)
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state2.params["w"]), np.asarray(ref_w), rtol=1e-5)
    assert int(state2.step) == 1


def test_train_step_fsdp(mesh8):
    """FSDP-sharded params train and stay sharded."""

    def loss_fn(params, batch):
        h = jax.nn.relu(batch["x"] @ params["w1"])
        pred = h @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.default_rng(1)
    params = {
        "w1": jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(size=(64, 2)).astype(np.float32)),
    }
    tx = optax.adam(1e-2)
    psh = fsdp_shardings(params, mesh8, min_shard_elements=64)
    params = jax.tree.map(jax.device_put, params, psh)
    state = TrainState.create(params, tx)
    step = build_train_step(loss_fn, tx, mesh8, param_shardings=psh)

    batch = {
        "x": rng.normal(size=(32, 8)).astype(np.float32),
        "y": rng.normal(size=(32, 2)).astype(np.float32),
    }
    sharded = shard_batch(mesh8, batch)
    losses = []
    for _ in range(5):
        state, loss = step(state, sharded)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # it learns
    # params remained sharded on fsdp axis
    assert state.params["w1"].sharding.spec == P(None, "fsdp")
    # adam moments follow the param shardings PLUS the default ZeRO
    # data-axis partition on their divisible leading dim (mesh8 carries
    # data=2: 8 % 2 == 0)
    mu = state.opt_state[0].mu
    assert mu["w1"].sharding.spec == P("data", "fsdp")


def test_state_shardings_structural(mesh8):
    params = {"a": jnp.zeros((8, 8)), "b": jnp.zeros((8, 8))}
    tx = optax.adam(1e-3)
    state = TrainState.create(params, tx)
    psh = {
        "a": NamedSharding(mesh8, P("fsdp", None)),
        "b": NamedSharding(mesh8, P(None, "fsdp")),
    }
    # the replicated-optimizer escape hatch: moments mirror their own
    # param position-for-position, nothing else
    ssh_off = state_shardings(state, mesh8, psh, zero_sharding=False)
    assert ssh_off.opt_state[0].mu["a"].spec == P("fsdp", None)
    assert ssh_off.opt_state[0].mu["b"].spec == P(None, "fsdp")
    assert ssh_off.opt_state[0].count.spec == P()
    assert ssh_off.step.spec == P()
    # default (ZeRO on): the data axis merges onto each moment's own
    # param spec where the dim divides (8 % (2*4) == 0 on dim 0 of 'a',
    # 8 % 2 == 0 on dim 0 of 'b'); count/step stay replicated
    ssh = state_shardings(state, mesh8, psh)
    assert ssh.opt_state[0].mu["a"].spec == P(("data", "fsdp"))
    assert ssh.opt_state[0].mu["b"].spec == P("data", "fsdp")
    assert ssh.opt_state[0].count.spec == P()
    assert ssh.step.spec == P()


def test_state_shardings_explicit_role_resolution(mesh8):
    """The mirrors-params decision is by declared field role, not shape
    coincidence: with a ONE-leaf param tree, Adam's scalar count (and
    any undeclared same-shaped lone array) resolves replicated, while
    mu/nu still mirror (and ZeRO-partition) — the train.py:90-99
    one-leaf special case is gone."""
    import collections

    params = jnp.zeros((8, 8))  # a bare one-leaf param tree
    tx = optax.adam(1e-3)
    state = TrainState.create(params, tx)
    psh = NamedSharding(mesh8, P("fsdp", None))
    ssh = state_shardings(state, mesh8, psh)
    assert ssh.opt_state[0].count.spec == P()
    assert ssh.opt_state[0].mu.spec == P(("data", "fsdp"))
    assert ssh.opt_state[0].nu.spec == P(("data", "fsdp"))

    # an UNDECLARED field holding a lone array — even one whose shape
    # happens to equal the single param's — replicates instead of
    # accidentally inheriting the param sharding
    Fake = collections.namedtuple("Fake", ["lookalike"])
    fake_state = TrainState(
        step=state.step,
        params=params,
        opt_state=(Fake(lookalike=jnp.zeros((8, 8))),),
    )
    fssh = state_shardings(fake_state, mesh8, psh)
    assert fssh.opt_state[0].lookalike.spec == P()


def test_zero_train_step_matches_replicated(mesh_dp):
    """zero_sharding on vs off on a pure data-parallel mesh: the same
    loss and the same params trajectory up to gradient-reduction order
    (reduce-scatter vs all-reduce group the sum differently, so the two
    legs may differ by a few ulp of fp32 — not by bytes under every
    compiler), moments genuinely data-partitioned only on the ZeRO
    leg."""

    def loss_fn(params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.default_rng(5)
    params = {
        "w1": jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(size=(8, 2)).astype(np.float32)),
    }
    tx = optax.adamw(1e-2)
    batch = shard_batch(
        mesh_dp,
        {
            "x": rng.normal(size=(32, 16)).astype(np.float32),
            "y": rng.normal(size=(32, 2)).astype(np.float32),
        },
    )

    def run(zero):
        state = TrainState.create(jax.tree.map(jnp.array, params), tx)
        step = build_train_step(
            loss_fn, tx, mesh_dp, zero_sharding=zero
        )
        for _ in range(5):
            state, loss = step(state, batch)
        return state, float(loss)

    s_on, l_on = run(True)
    s_off, l_off = run(False)
    assert l_on == l_off
    # 5 AdamW steps at lr 1e-2 on O(1) weights: a reduction-order
    # difference enters as a few ulp (2**-23 relative) of a gradient and
    # leaves as a few ulp of the weight. 16 ulp of fp32 is the allowance;
    # a wrong partition of the update would miss it by many orders.
    ulp = np.finfo(np.float32).eps
    for a, b in zip(
        jax.tree.leaves(jax.device_get(s_on.params)),
        jax.tree.leaves(jax.device_get(s_off.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=16 * ulp, atol=16 * ulp)
    # the ZeRO leg's moments really are partitioned across the replicas
    assert s_on.opt_state[0].mu["w1"].sharding.spec == P("data")
    assert s_off.opt_state[0].mu["w1"].sharding.spec == P()


def test_checkpoint_roundtrip(tmp_path, mesh_dp):
    from tensorflowonspark_tpu.compute.checkpoint import (
        restore_checkpoint,
        save_checkpoint,
    )

    state = {"w": jnp.arange(8.0), "step": jnp.int32(3)}
    path = save_checkpoint(str(tmp_path / "ckpt"), state)
    restored = restore_checkpoint(path, target=state)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8.0))
    assert int(restored["step"]) == 3


def test_checkpoint_manager(tmp_path):
    from tensorflowonspark_tpu.compute.checkpoint import CheckpointManager

    state = {"w": jnp.arange(4.0)}
    with CheckpointManager(str(tmp_path / "mgr"), max_to_keep=2) as mgr:
        for step in (1, 2, 3):
            mgr.save(step, {"w": jnp.arange(4.0) * step})
        mgr.wait()
        assert mgr.latest_step() == 3
        restored = mgr.restore(3, target=state)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(4.0) * 3)


def test_checkpoint_manager_save_interval(tmp_path):
    from tensorflowonspark_tpu.compute.checkpoint import CheckpointManager

    with CheckpointManager(
        str(tmp_path / "mgr"), save_interval_steps=5, async_save=False
    ) as mgr:
        results = [mgr.save(s, {"w": jnp.ones(2) * s}) for s in range(11)]
        mgr.wait()
        # only steps 0, 5, 10 land; off-interval saves are no-ops
        assert [s for s, r in enumerate(results) if r] == [0, 5, 10]
        assert mgr.latest_step() == 10


def test_checkpoint_manager_keep_best(tmp_path):
    from tensorflowonspark_tpu.compute.checkpoint import CheckpointManager

    losses = {1: 3.0, 2: 1.0, 3: 2.0, 4: 5.0}
    with CheckpointManager(
        str(tmp_path / "mgr"),
        max_to_keep=2,
        keep_best_metric="loss",
        async_save=False,
    ) as mgr:
        for step, loss in losses.items():
            mgr.save(step, {"w": jnp.ones(2) * step}, metrics={"loss": loss})
        mgr.wait()
        kept = sorted(mgr._mgr.all_steps())
        assert kept == [2, 3]  # the two lowest-loss checkpoints survive

    import pytest

    with pytest.raises(ValueError, match="keep_best_mode"):
        CheckpointManager(str(tmp_path / "bad"), keep_best_mode="sideways")


def test_restore_latest_helper(tmp_path):
    from tensorflowonspark_tpu.compute.checkpoint import (
        CheckpointManager,
        restore_latest,
    )

    target = {"state": jnp.zeros(3), "extra": jnp.zeros(())}
    with CheckpointManager(str(tmp_path / "empty")) as mgr:
        step, restored = restore_latest(mgr, target)
        assert step is None and restored is target

    with CheckpointManager(str(tmp_path / "mgr"), async_save=False) as mgr:
        mgr.save(5, {"state": jnp.arange(3.0), "extra": jnp.ones(())})
        mgr.wait()
        step, restored = restore_latest(mgr, target)
        assert step == 5
        np.testing.assert_array_equal(
            np.asarray(restored["state"]), np.arange(3.0)
        )

    # a directory written with DIFFERENT keys -> the clear wrong-trainer
    # error (the legacy params-only layout scenario)
    import pytest

    with CheckpointManager(str(tmp_path / "old"), async_save=False) as mgr:
        mgr.save(1, {"params": jnp.zeros(2), "batch_stats": jnp.zeros(())})
        mgr.wait()
        with pytest.raises(ValueError, match="different trainer"):
            restore_latest(mgr, target)


def test_gradient_accumulation_matches_full_batch(mesh_dp):
    """accum_steps=4 must produce the same post-update params and loss
    as the full-batch step (mean of microbatch means == global mean),
    at 1/4 the per-microbatch activation footprint."""

    def loss_fn(params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    from tensorflowonspark_tpu.compute import optim

    tx = optim.adamw(1e-2, moment_dtype=jnp.bfloat16)
    rng = np.random.default_rng(3)
    params = {
        "w1": jnp.asarray(rng.normal(size=(6, 8)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(size=(8, 2)).astype(np.float32)),
    }
    batch = shard_batch(
        mesh_dp,
        {
            "x": rng.normal(size=(32, 6)).astype(np.float32),
            "y": rng.normal(size=(32, 2)).astype(np.float32),
        },
    )

    def fresh():
        # donated input states must not share buffers across steps
        return TrainState.create(jax.tree.map(jnp.array, params), tx)

    full = build_train_step(loss_fn, tx, mesh_dp)
    accum = build_train_step(loss_fn, tx, mesh_dp, accum_steps=4)
    s_full, l_full = full(fresh(), batch)
    s_acc, l_acc = accum(fresh(), batch)

    np.testing.assert_allclose(float(l_acc), float(l_full), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        s_acc.params,
        s_full.params,
    )

    with pytest.raises(ValueError, match="accum_steps"):
        build_train_step(loss_fn, tx, mesh_dp, accum_steps=0)
    bad = build_train_step(loss_fn, tx, mesh_dp, accum_steps=5)
    with pytest.raises(ValueError, match="not divisible"):
        bad(fresh(), batch)


def test_weighted_accumulation_exact_for_masked_loss(mesh_dp):
    """A count-normalized (packed/masked) loss under accumulation with
    ``batch_weight_fn`` must match the unaccumulated full-batch step to
    tight tolerance even when microbatch valid counts differ wildly —
    the case where averaging microbatch means is only approximate."""

    def loss_fn(params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        err = jnp.sum((pred - batch["y"]) ** 2, axis=-1)
        m = batch["mask"]
        return jnp.sum(err * m) / jnp.maximum(jnp.sum(m), 1)

    tx = optax.adamw(1e-2)
    rng = np.random.default_rng(7)
    params = {
        "w1": jnp.asarray(rng.normal(size=(6, 8)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(size=(8, 2)).astype(np.float32)),
    }
    # strongly unequal per-microbatch valid counts (rows of 8, accum=4):
    # microbatch 0 nearly full, microbatch 3 nearly empty
    mask = np.zeros((32,), np.float32)
    for i, keep in enumerate([8, 5, 2, 1]):
        mask[8 * i : 8 * i + keep] = 1.0
    batch = shard_batch(
        mesh_dp,
        {
            "x": rng.normal(size=(32, 6)).astype(np.float32),
            "y": rng.normal(size=(32, 2)).astype(np.float32),
            "mask": mask,
        },
    )

    def fresh():
        return TrainState.create(jax.tree.map(jnp.array, params), tx)

    weight = lambda b: jnp.sum(b["mask"])  # noqa: E731
    full = build_train_step(loss_fn, tx, mesh_dp)
    exact = build_train_step(
        loss_fn, tx, mesh_dp, accum_steps=4, batch_weight_fn=weight
    )
    approx = build_train_step(loss_fn, tx, mesh_dp, accum_steps=4)

    s_full, l_full = full(fresh(), batch)
    s_exact, l_exact = exact(fresh(), batch)
    s_approx, l_approx = approx(fresh(), batch)

    np.testing.assert_allclose(float(l_exact), float(l_full), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        s_exact.params,
        s_full.params,
    )
    # sanity: with these skewed counts the unweighted average is NOT the
    # full-batch loss — the approximation the weight_fn removes
    assert abs(float(l_approx) - float(l_full)) > 1e-3
