"""Sharded train/eval step builders.

This module replaces the reference's two data-parallel families
(async parameter-server and MultiWorkerMirroredStrategy, SURVEY.md §2.3)
with one mechanism: ``jax.jit`` over a mesh with ``NamedSharding``.

- DP   = params replicated, batch sharded on ``('data','fsdp')`` — XLA
  inserts the gradient psum that NCCL all-reduce did in the reference.
- FSDP = additionally shard params/optimizer state on ``'fsdp'`` — the
  sharded-state role the reference's parameter servers played, without the
  asymmetric-role processes.
- ZeRO (``zero_sharding=True``, the default) = additionally partition
  the optimizer state and the weight update across the ``'data'``
  replica axis (arXiv 2004.13336, the PAPERS.md recipe): the gradient
  mean's psum lowers to a reduce-scatter the scheduler overlaps into
  the backward, the Adam/master update computes on 1/N of every leaf,
  and one all-gather republishes the updated params. The layout is
  derived from ``LAYOUT_TABLES['optimizer']``
  (:func:`layout.optimizer_state_spec`), never hand-built here; the
  replicated path stays available as ``zero_sharding=False`` for A/B.

Adding TP/SP later is a sharding-rule change, not a rewrite (the mesh
already carries ``model``/``seq`` axes).
"""

from __future__ import annotations

import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding

from tensorflowonspark_tpu.compute import layout as _layout
from tensorflowonspark_tpu.compute.mesh import batch_sharding, replicated
from tensorflowonspark_tpu.obs import spans as obs_spans

# The layout table's declared per-param optimizer-state roles (Adam
# moments, masters, momentum traces): the EXPLICIT resolution
# state_shardings uses instead of shape-coincidence guessing.
_PER_PARAM_STATE_RE = re.compile(_layout.OPTIMIZER_PARAM_STATE_PATTERN)

# The named scope grouping the optimizer's device ops in traces.
WEIGHT_UPDATE_SCOPE = "train.weight_update"


@struct.dataclass
class TrainState:
    """Minimal train state pytree: step counter, params, optimizer state.

    (flax's ``train_state.TrainState`` keeps ``apply_fn``/``tx`` inside the
    pytree; we keep the state pure data so it shards, checkpoints, and
    crosses process boundaries cleanly.)
    """

    step: jax.Array
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
        )


def fsdp_shardings(
    params: Any,
    mesh: Mesh,
    min_shard_elements: int = 1024,
    axis: str = "fsdp",
) -> Any:
    """Derive FSDP NamedShardings for a param pytree.

    Rule: shard the *largest* dimension divisible by the fsdp axis size;
    tiny tensors (biases, norms) stay replicated (the layout table's
    generic shape-driven rule, :func:`layout.fsdp_leaf_spec`). This
    mirrors how the reference's PS spread variables across ps shards
    (greedy variable placement), re-expressed as mesh sharding.
    """

    def rule(x) -> NamedSharding:
        return _layout.fsdp_leaf_sharding(
            mesh, np.shape(x), axis=axis,
            min_shard_elements=min_shard_elements,
        )

    return jax.tree.map(rule, params)


def state_shardings(
    state: TrainState,
    mesh: Mesh,
    param_shardings: Any,
    zero_sharding: bool = True,
) -> TrainState:
    """Shardings for a full TrainState, derived from the layout table's
    optimizer-state rules (``LAYOUT_TABLES['optimizer']``).

    Optimizer-state subtrees that structurally mirror the param tree
    (Adam moments, momentum traces, mixed-precision masters) reuse the
    param shardings position-for-position; with ``zero_sharding=True``
    (the default) the per-param state fields the table declares
    additionally partition over the ``'data'`` replica axis — the
    ZeRO-style cross-replica weight update (arXiv 2004.13336) — with
    the table's divisibility semantics dropping indivisible leaves back
    to the mirrored spec. Scalars and undeclared fields replicate.

    Resolution is EXPLICIT: whether a subtree mirrors the param tree is
    decided by tree structure, and — for the one-leaf param tree where
    ANY lone array matches structurally (e.g. Adam's scalar ``count``)
    — by the field's declared role in the table, not by the old
    shape-coincidence special case.
    """
    params_treedef = jax.tree.structure(state.params)
    multi_leaf = params_treedef.num_leaves > 1

    def mirrors_params(node, path: str) -> bool:
        if jax.tree.structure(node) != params_treedef:
            return False
        if multi_leaf:
            return True
        return bool(_PER_PARAM_STATE_RE.search(path))

    def mirrored(node, path: str):
        def leaf_rule(ppath, psh, leaf) -> NamedSharding:
            if not zero_sharding:
                return psh
            name = _layout._path_name(ppath)
            return _layout.optimizer_state_sharding(
                mesh,
                f"{path}/{name}" if name else path,
                np.shape(leaf),
                psh.spec,
            )

        return jax.tree_util.tree_map_with_path(
            leaf_rule, param_shardings, node
        )

    def rec(node, path: str):
        if mirrors_params(node, path):
            return mirrored(node, path)
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple
            return type(node)(*(
                rec(getattr(node, f), f"{path}/{f}" if path else f)
                for f in node._fields
            ))
        if isinstance(node, (tuple, list)):
            return type(node)(
                rec(c, f"{path}/{i}" if path else str(i))
                for i, c in enumerate(node)
            )
        if isinstance(node, dict):
            return {
                k: rec(v, f"{path}/{k}" if path else str(k))
                for k, v in node.items()
            }
        return jax.tree.map(lambda _: replicated(mesh), node)

    return TrainState(
        step=replicated(mesh),
        params=param_shardings,
        opt_state=rec(state.opt_state, ""),
    )


def zero_update_shardings(
    params: Any, mesh: Mesh, param_shardings: Any
) -> Any:
    """NamedShardings for a param-shaped UPDATE tree (gradients,
    optimizer deltas) under the layout table's ZeRO rules: each leaf's
    param spec plus the ``'data'`` partition where divisible. This is
    the sharding the gradient reduce-scatters INTO and the sharded Adam
    update computes in."""

    def rule(path, p, psh) -> NamedSharding:
        return _layout.optimizer_state_sharding(
            mesh,
            "update/" + _layout._path_name(path),
            np.shape(p),
            psh.spec,
        )

    return jax.tree_util.tree_map_with_path(rule, params, param_shardings)


def shard_state(
    state: TrainState,
    mesh: Mesh,
    param_shardings: Any,
    zero_sharding: bool = True,
) -> TrainState:
    """Commit every leaf of ``state`` to its mesh sharding: params to
    ``param_shardings``, optimizer subtrees that mirror the param tree
    likewise (ZeRO data-axis partitioned by default — see
    :func:`state_shardings`), scalars (step, Adam count) replicated.

    Create train state as ``shard_state(TrainState.create(p, tx), mesh,
    psh)`` whenever it will be checkpointed: orbax restores each array to
    the *target's* committed sharding, and a target with stray
    default-device leaves (e.g. from an optimizer init that used plain
    ``jnp.zeros``) restores to committed single-device arrays, which the
    train step's explicit in_shardings then reject under
    multi-controller FSDP instead of implicitly resharding.
    """
    return jax.tree.map(
        jax.device_put,
        state,
        state_shardings(state, mesh, param_shardings, zero_sharding),
    )


def build_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    param_shardings: Any | None = None,
    donate: bool = True,
    accum_steps: int = 1,
    batch_weight_fn: Callable[[Any], jax.Array] | None = None,
    zero_sharding: bool = True,
) -> Callable[[TrainState, Any], tuple[TrainState, jax.Array]]:
    """Compile ``(state, batch) -> (state, loss)`` with mesh shardings.

    ``loss_fn(params, batch) -> scalar`` must mean-reduce over the global
    batch; since the batch is sharded over ``('data','fsdp')``, XLA lowers
    the mean's reduction to a psum over ICI — the entire gradient-sync
    machinery the reference delegated to NCCL/PS.

    ``zero_sharding`` (default True) turns that psum into the ZeRO
    decomposition where the mesh has a ``'data'`` axis wider than 1:
    gradients reduce-scatter into the layout table's data-partitioned
    update layout (overlappable with the backward), the optimizer state
    lives and updates in the same partition, and the updated params
    all-gather back to their table shardings. ``zero_sharding=False``
    is the replicated-optimizer escape hatch for A/B: the weight-update
    decomposition itself is elementwise, hence byte-identical across
    knobs on identical gradients; the full train paths agree to
    reduction-order tolerance
    (reduce-scatter vs all-reduce summation grouping, ~1 ulp). State
    committed with :func:`shard_state` should use the SAME knob value
    (a mismatched state is re-committed once at the first call).

    ``accum_steps > 1`` runs gradient accumulation: the batch's leading
    dim splits into that many microbatches, a ``lax.scan`` accumulates
    their gradients in fp32 (so bf16-param configs don't round 8-bit
    mantissas per add), and ONE optimizer update applies the mean. For
    losses whose mean weights every microbatch equally (fixed-shape
    batches — the usual case) this reproduces the full-batch step
    exactly. For losses that normalize by a per-call VALID count (e.g.
    the packed/masked CE: ``sum(nll*mask)/sum(mask)``), pass
    ``batch_weight_fn(microbatch) -> scalar`` returning that count
    (e.g. ``lambda b: b["mask"].sum()``): each microbatch's loss and
    gradients are then accumulated as (value·count, count) and divided
    once by the total, reproducing the full-batch token weighting
    exactly instead of weighting microbatch *means* equally.
    Accumulation is the memory lever when the target global batch's
    activations exceed HBM even after remat; each microbatch must still
    divide the ``('data','fsdp')`` mesh extent.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    compiled: dict[str, Any] = {}

    def jitted(state: TrainState):
        """The jitted step, built from the first state seen (concrete
        or abstract: only its tree structure and shapes are read)."""
        if "fn" not in compiled:
            psh = (
                param_shardings
                if param_shardings is not None
                else jax.tree.map(lambda _: replicated(mesh), state.params)
            )
            step = make_step_fn(
                loss_fn,
                tx,
                mesh,
                accum_steps=accum_steps,
                batch_weight_fn=batch_weight_fn,
                param_shardings=psh,
                zero_sharding=zero_sharding,
            )
            state_sh = state_shardings(state, mesh, psh, zero_sharding)
            compiled["state_sh"] = state_sh
            compiled["fn"] = jax.jit(
                step,
                in_shardings=(state_sh, batch_sharding(mesh)),
                out_shardings=(state_sh, replicated(mesh)),
                donate_argnums=(0,) if donate else (),
            )
        return compiled["fn"]

    def wrapped(state: TrainState, batch):
        fn = jitted(state)
        if "n" not in compiled:
            # First-call commit: a state built without shard_state
            # (moments inherit the PARAM placement via zeros_like)
            # arrives committed off the ZeRO layout, which explicit
            # in_shardings reject rather than silently reshard.
            # device_put is a no-op for already-matching leaves, and
            # every subsequent step's input is this step's output.
            state = jax.tree.map(jax.device_put, state, compiled["state_sh"])
        # Host-side step span (obs/): measures DISPATCH time — jit
        # returns as soon as the computation is enqueued, so the
        # data-wait vs step split reads as "host blocked here" only
        # when the caller's fetch forces it. StepTraceAnnotation makes
        # an active jax.profiler device trace group this step's XLA
        # ops under the same step number. A host-side call counter, not
        # state.step: fetching the device scalar per step would sync.
        n = compiled["n"] = compiled.get("n", 0) + 1
        with obs_spans.get_tracer().step_span("train.step", step_num=n):
            return fn(state, batch)

    # ``jax.jit``'s own AOT door on the very program ``wrapped`` runs:
    # ``step.lower(state, batch).compile()`` gives ``as_text()`` (is the
    # kernel in it? which collectives?) and ``memory_analysis()``.
    # Arguments may be ``jax.ShapeDtypeStruct`` trees, so a step can be
    # compiled for a mesh of described devices that holds no array.
    wrapped.lower = lambda state, batch: jitted(state).lower(state, batch)
    return wrapped


def make_step_fn(
    loss_fn: Callable[[Any, Any], jax.Array],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    accum_steps: int = 1,
    batch_weight_fn: Callable[[Any], jax.Array] | None = None,
    param_shardings: Any | None = None,
    zero_sharding: bool = True,
) -> Callable[[TrainState, Any], tuple[TrainState, jax.Array]]:
    """The UNJITTED ``(state, batch) -> (state, loss)`` train step.

    :func:`build_train_step` jits this with shardings/donation;
    ``tools/shardcheck.py`` lowers it abstractly (AOT, on faux CPU
    devices) to census the collectives the layout table implies — both
    consumers must see the SAME program, which is why this is one
    function and not two copies.

    With ``zero_sharding`` on (and ``param_shardings`` given, on a mesh
    whose ``'data'`` axis is wider than 1) the gradient tree is pinned
    to the layout table's data-partitioned update layout before the
    optimizer update: GSPMD then lowers the grad mean's psum to a
    reduce-scatter (which the latency-hiding scheduler overlaps into
    the backward), the Adam/master arithmetic runs on the shard, and
    the updated params all-gather back to their own shardings — the
    arXiv 2004.13336 dataflow. The optimizer arithmetic itself is
    grouped under a ``train.weight_update`` ``jax.named_scope`` so
    device traces can attribute its ops.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    zero_on = (
        zero_sharding
        and param_shardings is not None
        and dict(mesh.shape).get("data", 1) > 1
    )

    def scatter(tree):
        """Pin a param-shaped gradient/carry tree to the ZeRO update
        layout (a no-op leaf-wise where the table dropped the data
        axis, and entirely when the knob is off)."""
        if not zero_on:
            return tree
        shardings = zero_update_shardings(tree, mesh, param_shardings)

        def pin(g, sh, psh):
            if sh.spec == psh.spec:
                return g  # dropped-to-mirrored leaf: nothing to add
            return jax.lax.with_sharding_constraint(g, sh)

        return jax.tree.map(pin, tree, shardings, param_shardings)

    def grads_of(state: TrainState, batch):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
            return loss, scatter(grads)

        dp_extent = mesh.shape["data"] * mesh.shape["fsdp"]

        def split(x):
            if x.shape[0] % accum_steps:
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by "
                    f"accum_steps {accum_steps}"
                )
            if (x.shape[0] // accum_steps) % dp_extent:
                # silent GSPMD padding would idle chips on exactly the
                # big-pod configs accumulation targets — fail fast
                raise ValueError(
                    f"microbatch dim {x.shape[0] // accum_steps} "
                    f"(batch {x.shape[0]} / accum_steps {accum_steps}) "
                    f"not divisible by the (data, fsdp) mesh extent "
                    f"{dp_extent}"
                )
            return x.reshape(
                accum_steps, x.shape[0] // accum_steps, *x.shape[1:]
            )

        micro = jax.tree.map(split, batch)
        # fp32 carry regardless of param dtype: summing bf16 gradient
        # trees would round at each add; optax updates widen anyway.
        # Under ZeRO the carry lives scattered too: each microbatch's
        # reduce lands as a reduce-scatter accumulated into the shard.
        zeros = scatter(
            jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
        )

        def body(carry, mb):
            loss_sum, grad_sum, w_sum = carry
            loss, grads = jax.value_and_grad(loss_fn)(state.params, mb)
            grads = scatter(grads)
            w = (
                jnp.ones((), jnp.float32)
                if batch_weight_fn is None
                else batch_weight_fn(mb).astype(jnp.float32)
            )
            return (
                loss_sum + loss * w,
                jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) * w,
                    grad_sum,
                    grads,
                ),
                w_sum + w,
            ), None

        (loss_sum, grad_sum, w_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros, jnp.zeros((), jnp.float32)), micro
        )
        # w_sum == accum_steps for the unweighted path; guard a fully
        # masked-out batch (all counts zero) against 0/0
        inv = 1.0 / jnp.maximum(w_sum, 1e-6)
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, grad_sum)

    def step(state: TrainState, batch):
        # Publish the mesh for the duration of the trace: model code deep
        # inside loss_fn keys mesh-aware dispatch on the ambient mesh
        # (ops.attention's auto -> mesh_flash_attention shard_map route,
        # impl='ring'/'ulysses') and must see it without the caller
        # remembering to wrap every train call in parallel.use_mesh.
        from tensorflowonspark_tpu.parallel import use_mesh

        with use_mesh(mesh):
            loss, grads = grads_of(state, batch)
        return _apply_weight_update(tx, state, grads), loss

    return step


def _apply_weight_update(
    tx: optax.GradientTransformation, state: TrainState, grads
) -> TrainState:
    """The optimizer apply of :func:`make_step_fn`, under the named
    scope device traces attribute."""
    with jax.named_scope(WEIGHT_UPDATE_SCOPE):
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    return TrainState(
        step=state.step + 1, params=new_params, opt_state=new_opt
    )


def build_eval_step(
    metric_fn: Callable[[Any, Any], Any], mesh: Mesh
) -> Callable[[Any, Any], Any]:
    """Compile ``(params, batch) -> metrics`` with batch sharded on the mesh."""

    def traced(params, batch):
        # same ambient-mesh publication as build_train_step: eval-path
        # model code keys mesh-aware dispatch on it too
        from tensorflowonspark_tpu.parallel import use_mesh

        with use_mesh(mesh):
            return metric_fn(params, batch)

    return jax.jit(
        traced,
        in_shardings=(None, batch_sharding(mesh)),
        out_shardings=replicated(mesh),
    )
