"""Mixture-of-experts with expert parallelism over the ``expert`` axis.

GShard-style top-k routing with a fixed per-expert capacity so every shape
is static under ``jit``: tokens are scattered into an ``(experts,
capacity, d)`` buffer with one einsum against a dispatch mask, the expert
FFN bank runs as a single batched matmul over the stacked expert dimension
(one big MXU-friendly contraction, not a Python loop over experts), and a
second einsum with the combine weights gathers results back to token order.

Expert parallelism is pure sharding: the stacked expert dim of the FFN
params and of the dispatched buffer carries ``PartitionSpec('expert')``,
and XLA lowers the token exchange implied by resharding (tokens sharded on
batch → buffers sharded on expert) to ``all_to_all`` over ICI. There is no
hand-written dispatch collective to maintain.

Load balancing is the standard Switch/GShard auxiliary loss
(``aux_load_balancing_loss``): mean fraction of tokens routed to each
expert × mean router probability per expert, × num_experts.

The reference has no MoE/expert parallelism (SURVEY.md §2.3) — this is
beyond-parity capability.

Beside it, the dropless layer a served expert model takes
(:func:`route`, :func:`dropless_experts`, :class:`DroplessMoE`): the
layer is told which experts of the router's range it holds, routes every
token over the whole range, sorts the (token, choice) pairs whose expert
is held by expert into a buffer sized for the worst case and runs one
grouped matmul a projection over the held banks. No token is dropped
whatever the imbalance, no ``(T, E, C)`` mask exists, and what the
absent experts would add is left out: the partial result goes on, as one
chip's share of an expert-parallel deployment computes it before the
exchange (which this layer does not make: ROADMAP M1).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tensorflowonspark_tpu.compute import layout


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0
    hidden_size: int = 128
    intermediate_size: int = 256
    dtype: jnp.dtype = jnp.bfloat16
    router_aux_weight: float = 0.01


def _capacity(num_tokens: int, cfg: MoEConfig) -> int:
    # ceil, per GShard/Switch: capacity_factor=1.0 must mean "exactly
    # enough slots under perfect balance", never fewer.
    cap = math.ceil(
        num_tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts
    )
    return max(cap, cfg.top_k)


def top_k_routing(
    router_logits: jax.Array, cfg: MoEConfig, num_tokens: int
):
    """Build dispatch mask and combine weights from router logits.

    router_logits (T, E) → dispatch (T, E, C) bool-ish float, combine
    (T, E, C) float32, aux_loss scalar. Tokens over an expert's capacity
    are dropped (standard fixed-capacity semantics); priority is token
    order, matching GShard/Switch.
    """
    t, e = router_logits.shape
    c = _capacity(num_tokens, cfg)
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    # top-k expert choices per token
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)  # (T, k)
    # normalise the selected gates to sum to 1 per token
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # position of each (token, choice) within its expert's capacity buffer:
    # cumulative count of earlier assignments to the same expert, counting
    # across choices-major-then-token order.
    choice_mask = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # (T,k,E)
    flat_mask = choice_mask.reshape(t * cfg.top_k, e)  # choices flattened
    pos_in_expert = jnp.cumsum(flat_mask, axis=0) - flat_mask  # (T*k, E)
    pos = jnp.sum(pos_in_expert * flat_mask, axis=-1).reshape(t, cfg.top_k)
    keep = pos < c  # over-capacity assignments dropped

    gates = gate_vals * keep
    # scatter into (T, E, C)
    combine = jnp.einsum(
        "tk,tke,tkc->tec",
        gates,
        jax.nn.one_hot(expert_idx, e, dtype=jnp.float32),
        jax.nn.one_hot(jnp.where(keep, pos, 0), c, dtype=jnp.float32)
        * keep[..., None],
    )
    dispatch = (combine > 0).astype(jnp.float32)

    # Switch-style load-balancing aux loss on the top-1 assignment.
    top1 = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    frac_tokens = jnp.mean(top1, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Drop-in MLP block: top-k routed bank of SwiGLU experts.

    Call with x (B, S, d); returns (B, S, d). Stores the aux loss with
    ``self.sow('losses', 'router_aux', ...)`` — collect via
    ``mutable=['losses']`` or read it from a surrounding train step.
    """

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        t = b * s
        tokens = x.reshape(t, d)

        router = nn.Dense(
            cfg.num_experts, use_bias=False, dtype=jnp.float32,
            name="router", kernel_init=nn.initializers.normal(0.02),
        )
        logits = router(tokens.astype(jnp.float32))
        dispatch, combine, aux = top_k_routing(logits, cfg, t)
        self.sow("losses", "router_aux", cfg.router_aux_weight * aux)

        init = nn.initializers.normal(0.02)
        e, f = cfg.num_experts, cfg.intermediate_size
        w_gate = self.param("w_gate", init, (e, d, f))
        w_up = self.param("w_up", init, (e, d, f))
        w_down = self.param("w_down", init, (e, f, d))

        # (T,E,C) x (T,d) -> (E,C,d): the resharding T-sharded -> E-sharded
        # is the all_to_all dispatch.
        xs = jnp.einsum(
            "tec,td->ecd", dispatch.astype(cfg.dtype), tokens.astype(cfg.dtype)
        )
        gate = jnp.einsum("ecd,edf->ecf", xs, w_gate.astype(cfg.dtype))
        up = jnp.einsum("ecd,edf->ecf", xs, w_up.astype(cfg.dtype))
        ys = jnp.einsum(
            "ecf,efd->ecd", nn.silu(gate) * up, w_down.astype(cfg.dtype)
        )
        out = jnp.einsum(
            "tec,ecd->td", combine.astype(cfg.dtype), ys
        )
        return out.reshape(b, s, d).astype(x.dtype)


def route(
    logits: jax.Array,
    top_k: int,
    *,
    scoring: str = "sigmoid",
    norm_topk_prob: bool = True,
    scaling: float = 1.0,
):
    """Router logits (T, E) -> weights (T, k) float32 and experts (T, k)
    int32, the k best by score. ``scoring`` is ``"sigmoid"`` or
    ``"softmax"`` over all E; ``norm_topk_prob`` divides the chosen
    scores by their sum (over all k chosen, wherever their experts
    live); ``scaling`` multiplies what is left."""
    logits = logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown scoring {scoring!r}")
    weights, experts = jax.lax.top_k(scores, top_k)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scaling, experts.astype(jnp.int32)


# Test hook: run the Pallas grouped matmul in the interpreter (on a CPU).
INTERPRET = False
# Why ``gmm_tiling`` chooses as it does, from the chip (PERF.md §6, PR
# 34). The kernel's grid is (column tiles, row tiles that hold a group's
# rows, contraction tiles); a step fetches a (tk, tn) tile of a bank and
# the (128, tk) tile of the rows. A product's time is the read of the
# banks it reaches, and on top of it a step's fixed cost, which shows
# under about a megabyte a tile, and the rows' tile again at every step,
# 128 / tn of the bank's bytes. So the width of the column tile decides,
# and at one width the contraction tile moved nothing (512 to 2048 deep:
# within 1 %).
# In the cells, ms a call of the kernel alone at a decode step's 1,024
# rows (device trace; 123 real rows and 38 of 40 banks reached in
# Solar-Open2's, 64 and 15.6 of 16 in the expert cell's; * marks the tiles
# taken). Solar-Open2 gate or up (4096, 1280): PR 33's (128, 512, 256)
# 0.875; (128, 512, 1280)* 0.538; (128, 4096, 256) 0.547. Down (1280,
# 4096): (128, 256, 2048)* 0.552-0.571, as before; (128, 1280, 512) 0.555.
# The expert cell's gate or up (7680, 2048): (128, 512, 2048)* 0.655, as
# PR 31 chose. Down (2048, 7680): PR 31's (128, 512, 512) 0.87; (128, 512,
# 1920)* 0.659; (128, 2048, 512) 0.736.
# A product alone, us at a decode step's 1,024 rows / a 1024-wide
# prefill's 8,192 (host clock over 40 calls, the group metadata's
# operations included). Solar-Open2, 40 banks, 112 / 1,024 real rows, 36 /
# 40 banks reached. Gate or up: (128, 512, 256) 1,006 / 1,303; (128, 512,
# 640) 675 / 876; (128, 512, 1280)* 582 / 754; (128, 2048, 1280) 587 /
# 757; (128, 4096, 640) 546 / 668; (128, 4096, 256) 536 / 674. Down: (128,
# 256, 2048)* 572 / 751; (128, 256, 4096) 548 / 721; (128, 1280, 2048) 535
# / 663; (128, 1280, 512) 533 / 691. The expert cell, 16 banks, 55 / 529
# real rows, 14 / 16 reached. Gate or up: (128, 512, 2048)* 649 / 921;
# (128, 256 to 1280, 2048) 647-650 / 919-923; (128, 512, 1024) 717 /
# 1,021; (128, 512, 512) 843 / 1,198; (128, 7680, 256) 661 / 878. Down:
# (128, 512, 512) 855 / 1,225; (128, 512, 1920)* 654 / 940; (128, 512,
# 3840) 637 / 914; (128, 256, 7680) 625 / 894; (128, 2048, 512) 618 / 858.
# Alone, a whole contraction read best (the rows' tile then stands still
# and is fetched once); in the cells it did not: a rule that took the
# contraction whole first read the expert cell's down-projection 12 %
# slower and Solar-Open2's three products equal, so it was not kept. Judge
# a tile by the cell's trace.
# Rows, a product alone at its best tile: 64 read within 1.5 % of 128 at
# the decode buffer and 2-3 % worse at the prefill's; 256 read 11-18 %
# worse or was refused for VMEM. So 128 stays, and the buffer's size does
# not enter: the order of the tiles was the same at both.
# PR 31's table, one SwiGLU's three products over the expert cell's banks,
# us at 1,024 rows (59 real) / 8,192 (559 real): (128, 512, 2048) 2,125 /
# 2,753; (128, 1024, 1024) 2,201 / 2,897; (64, 1024, 1024) 2,152 / 3,292;
# (128, 512, 512) 2,717 / 3,591; (256, 1024, 512) 3,039 / 3,662;
# ``jax.lax.ragged_dot`` 4,886 / 6,091. (Under that day's rule, the first
# of (the tile, 512, 256, 128) that divides, the down-projection of each
# of these ran 512 columns wide.)
# The VMEM budget: what a step's bank tile may take, both buffers of its
# pipeline together (a quarter of the 16 MiB a kernel is given on a v5e;
# the compiler refuses a tile of 7.5 MiB), and the widest column tile (a
# float32 accumulator of 1 MiB at 128 rows).
_GMM_BANK_TILE_BYTES = 4 * 2**20
_GMM_COLUMNS = 2048


def _widest_tile(size: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``cap``; the whole of ``size`` where none does."""
    return next(
        (t for t in range(cap - cap % 128, 0, -128) if size % t == 0), size
    )


def gmm_tiling(m: int, d: int, f: int, itemsize: int) -> tuple[int, int, int]:
    """(rows, contraction, columns) a grid step of the Pallas grouped
    matmul takes for ``m`` rows against banks of ``(d, f)``: the widest
    column tile up to ``_GMM_COLUMNS``, then the deepest contraction tile
    that keeps the bank tile inside ``_GMM_BANK_TILE_BYTES``. A tile is
    the largest multiple of 128 that divides its dimension, or the
    dimension whole where none does. A pure function of the shapes
    (``m`` does not enter: the comment above says why)."""
    del m
    tn = _widest_tile(f, _GMM_COLUMNS)
    tk = _widest_tile(d, _GMM_BANK_TILE_BYTES // (2 * tn * itemsize))
    return 128, tk, tn


def _pallas_gmm() -> bool:
    """Whether ``grouped_matmul`` takes the Pallas kernel in this
    process: on one TPU without an ambient mesh (GSPMD cannot partition
    a ``pallas_call``), as ``ops.decode_attention.cache_block_k`` asks."""
    from tensorflowonspark_tpu.ops import attention
    from tensorflowonspark_tpu.parallel.context import current_mesh

    return attention._on_tpu() and current_mesh() is None


def grouped_matmul(xs: jax.Array, bank: jax.Array, group_sizes: jax.Array):
    """Rows ``xs`` (M, d), sorted by group, times ``bank`` (G, d, f):
    row ``i`` of group ``g`` meets ``bank[g]``. What comes back for rows
    past ``sum(group_sizes)`` is unspecified: the caller masks them.
    float32 accumulation, the result in ``xs``'s dtype.

    On one TPU: the installed JAX's Pallas grouped matmul
    (``megablox.gmm``), which visits only the row tiles that hold a
    group's rows, so its cost follows the real rows and the banks they
    reach, where ``jax.lax.ragged_dot``'s follows the buffer (PR 31's
    table at ``gmm_tiling``). The tiles a grid step takes follow from
    ``xs.shape``, ``bank.shape`` and the dtype alone, by
    :func:`gmm_tiling`.

    ``jax.lax.ragged_dot`` stays only for the platforms the kernel
    cannot run on, the CPU (tier-1) and an ambient mesh (GSPMD cannot
    partition the Pallas call): no benchmark cell runs it, and it is
    unmeasured there."""
    bank = bank.astype(xs.dtype)
    group_sizes = group_sizes.astype(jnp.int32)
    if not _pallas_gmm():
        return jax.lax.ragged_dot(
            xs, bank, group_sizes, preferred_element_type=jnp.float32,
        ).astype(xs.dtype)
    from jax.experimental.pallas.ops.tpu import megablox

    m, (_, d, f) = xs.shape[0], bank.shape
    tm, tk, tn = gmm_tiling(m, d, f, xs.dtype.itemsize)
    pad = -m % tm  # the kernel takes whole row tiles; none is visited
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    out = megablox.gmm(
        xs, bank, group_sizes, preferred_element_type=xs.dtype,
        tiling=(tm, tk, tn), interpret=INTERPRET,
    )
    return out[:m] if pad else out


def dropless_experts(
    x: jax.Array,
    weights: jax.Array,
    experts: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    first_held: int = 0,
):
    """The held experts' part of a routed SwiGLU layer.

    ``x`` (T, d); ``weights`` / ``experts`` (T, k) from :func:`route`;
    the banks ``(held, d, f)``, ``(held, d, f)``, ``(held, f, d)`` of
    experts ``first_held .. first_held + held - 1``. Returns ``y`` (T, d)
    in ``x``'s dtype, ``sum_k w_k * SwiGLU_{e_k}(x)`` over the chosen
    experts that are held, and ``group_sizes`` (held,) int32, the pairs
    each held expert got. The buffer has ``T * k`` rows, the worst case,
    so nothing is ever dropped; ``group_sizes`` say how many are real.
    """
    t, k = experts.shape
    held = w_gate.shape[0]
    with jax.named_scope("moe.dispatch"):
        local = experts.reshape(-1) - first_held
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held)  # pairs of absent experts last
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32,
        )
        xs = x[order // k]  # (T * k, d): the pair's token
    with jax.named_scope("moe.experts"):
        act = nn.silu(grouped_matmul(xs, w_gate, group_sizes)) * grouped_matmul(
            xs, w_up, group_sizes
        )
        ys = grouped_matmul(act, w_down, group_sizes)
    with jax.named_scope("moe.combine"):
        # back to (token, choice) order by a gather (the inverse of the
        # sort), then the weighted sum over a token's choices: no
        # scatter-add. A pair whose expert is absent reads a row past the
        # real ones and is masked, not multiplied by zero.
        keep = is_held.reshape(t, k)
        back = ys[jnp.argsort(order)].reshape(t, k, -1).astype(jnp.float32)
        back = jnp.where(keep[..., None], back, 0.0)
        y = jnp.einsum("tk,tkd->td", jnp.where(keep, weights, 0.0), back)
    return y.astype(x.dtype), group_sizes


class DroplessMoE(nn.Module):
    """Routed experts without capacity, one shard of them held here, and
    the shared experts beside them: ``y = sum_{e chosen, e held} w_e *
    SwiGLU_e(x) + SwiGLU_shared(x)`` on x (B, S, d).

    ``num_experts`` is the router's range; the layer holds experts
    ``first_held .. first_held + held - 1`` of it. ``shared_size`` is the
    shared experts' summed intermediate size (0: none). The router's
    kernel and arithmetic are float32. Returns ``(y, group_sizes)``:
    the pairs each held expert got, for whoever counts."""

    num_experts: int
    top_k: int
    intermediate_size: int
    shared_size: int = 0
    first_held: int = 0
    held: int | None = None
    scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    scaling: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        held = self.num_experts if self.held is None else self.held
        if not 0 <= self.first_held <= self.num_experts - held:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + held - 1} "
                f"are not inside the router's {self.num_experts}"
            )
        tokens = x.reshape(b * s, d).astype(self.dtype)
        init = nn.initializers.normal(0.02)
        with jax.named_scope("moe.route"):
            router = self.param(
                "router", init, (d, self.num_experts), jnp.float32
            )
            logits = jnp.dot(
                tokens.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            weights, experts = route(
                logits, self.top_k, scoring=self.scoring,
                norm_topk_prob=self.norm_topk_prob, scaling=self.scaling,
            )
        f = self.intermediate_size
        y, group_sizes = dropless_experts(
            tokens, weights, experts,
            self.param("w_gate", init, (held, d, f)),
            self.param("w_up", init, (held, d, f)),
            self.param("w_down", init, (held, f, d)),
            self.first_held,
        )
        if self.shared_size:
            with jax.named_scope("moe.shared"):
                dense = lambda feats, name: nn.Dense(  # noqa: E731
                    feats, use_bias=False, dtype=self.dtype, name=name,
                    kernel_init=init,
                )
                act = nn.silu(dense(self.shared_size, "shared_gate")(tokens))
                act = act * dense(self.shared_size, "shared_up")(tokens)
                y = y + dense(d, "shared_down")(act)
        return y.reshape(b, s, d).astype(x.dtype), group_sizes


def moe_expert_bank_spec(param_name: str) -> P:
    """PartitionSpec for one 3-dim expert bank leaf: stacked dim on
    ``expert``, FFN hidden on ``model``, the remaining dim on ``fsdp``
    — the declarative 'moe' table in
    :mod:`tensorflowonspark_tpu.compute.layout` (the llama table
    carries the same rules, pinned equal by tests/test_layout.py)."""
    return layout.expert_bank_spec(param_name)


def moe_param_shardings(params, mesh: Mesh):
    """Sharding rules for an MoEMLP param tree: expert banks per
    :func:`moe_expert_bank_spec`; the router is replicated."""
    return layout.param_shardings(params, mesh, "moe")
