"""Laguna (poolside's Laguna-XS.2): layers of windowed and of full
attention with different query-head counts over one K/V layout, routed
experts beside a shared one.

The published description is the model's ``config.json`` (``model_type:
laguna``); field names here follow its keys. One layer ``l``, pre-norm
with RMSNorm (epsilon ``rms_norm_eps``)::

    x = x + Attn_l(N1(x));   x = x + F_l(N2(x))

then the final norm and an untied head.

**Attention.** ``h_l = num_attention_heads_per_layer[l]`` query heads (48
in a ``full_attention`` layer, 64 in a ``sliding_attention`` one as
published), ``num_key_value_heads`` K/V heads of ``head_dim``, no bias:
``q = n W_q``, ``k = n W_k``, ``v = n W_v``; query head ``i`` reads K/V
head ``floor(i / (h_l / kv))``::

    a_i = softmax_j(q_i . k_j / sqrt(head_dim)) v_j

over ``j <= i``, or ``i - sliding_window < j <= i`` in a windowed layer.
Each kind has its rotary embedding (``rope_parameters``): the full layers
rotate the first ``partial_rotary_factor`` of each q and k head (the
first half, split in halves as HF's ``rotate_half`` splits them) under
YaRN's frequencies (``llama.RopeScaling(kind="yarn")``: theta 5e5, factor
64 over an original 4,096 positions, beta_fast 64, beta_slow 1, cos and
sin times ``attention_factor``) and pass the rest; the windowed layers
rotate the whole head at theta 1e4. The output is gated a head,
``out = concat_i(sigmoid(n W_g)_i a_i) W_o`` with ``W_g`` (hidden, h_l).

It is ``llama.Attention`` under a per-layer view (:meth:`LagunaConfig.
attention`): the windowed layers keep a ring of ``sliding_window``
positions (``kv_cache_len`` = the window), the full ones a dense plane of
``max_seq_len``; both are K/V of ``kv`` heads, so the cache tree's K/V
layout is one, whatever the layer's query heads.

**Feed-forward.** ``F_l`` is SwiGLU ``hidden -> intermediate_size ->
hidden`` where ``mlp_layer_types[l]`` is ``dense`` (layer 0 as
published); elsewhere routed experts (``parallel.moe.DroplessMoE``, every
expert held)::

    p = softmax(n W_r) over num_experts;  T = the num_experts_per_tok best
    w_e = moe_routed_scaling_factor * p_e / sum_T p
    F = sum_{e in T} w_e SwiGLU_e(n) + SwiGLU_shared(n)

each SwiGLU ``hidden -> moe_intermediate_size (shared:
shared_expert_intermediate_size) -> hidden``.

Readings the config leaves open, each ``assumed`` in the benchmark's
configuration file:

- ``gating: true`` is a per-head sigmoid gate from the layer's normed
  input, before ``W_o`` (the sibling Laguna-S-2.1 says ``per-head``);
- the router scores are a softmax over all experts (the Qwen-MoE family's
  keys; the config names no ``scoring_func``);
- the top-k weights are renormalised (the sibling's ``norm_topk_prob:
  true``), then scaled by ``moe_routed_scaling_factor``;
- the shared expert has no gate; there is no QK norm;
- HF's YaRN and partial-rotary conventions, as above.

Reused: ``llama.Attention`` / ``MLP`` / ``RMSNorm`` / ``embed_rows`` /
``head_logits``, ``parallel.moe.DroplessMoE``. No ``model``-axis sharding
table.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.decode_cache import (
    moe_count_entries,
    moe_counts,
)
from tensorflowonspark_tpu.models.llama import (
    MLP,
    Attention,
    RMSNorm,
    RopeScaling,
    embed_rows,
    head_logits,
)
from tensorflowonspark_tpu.parallel.moe import DroplessMoE

FULL, WINDOW = "full_attention", "sliding_attention"


def pattern(num_layers: int, full_every: int = 4) -> tuple[str, ...]:
    """The published layer pattern: a full layer, then ``full_every - 1``
    windowed ones, repeated."""
    return tuple(FULL if i % full_every == 0 else WINDOW for i in range(num_layers))


@dataclasses.dataclass(frozen=True)
class AttentionView:
    """What ``llama.Attention`` reads of its config, for one layer."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int
    dtype: jnp.dtype
    sliding_window: int | None
    kv_cache_len: int | None
    rope_theta: float
    rope_scaling: RopeScaling | None
    rotary_dim: int | None
    rms_norm_eps: float
    attention_impl: str = "auto"
    attention_bias: bool = False
    use_rope: bool = True
    kv_cache_dtype: str = "model"
    key_multiplier: float = 1.0
    attention_output_gate: str = "per_head"
    differential_lambda_init: None = None
    kv_heads_in_rows: bool = False


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The defaults are Laguna-XS.2's published ``config.json`` (but
    ``max_seq_len``: the published ``max_position_embeddings`` is
    262144)."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_layers: int = 40
    layer_types: tuple[str, ...] = pattern(40)
    mlp_layer_types: tuple[str, ...] = ("dense",) + ("sparse",) * 39
    # query heads a layer; num_heads is the published
    # num_attention_heads (the full layers')
    heads_per_layer: tuple[int, ...] = tuple(48 if t == FULL else 64 for t in pattern(40))
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    # rope_parameters, by kind of layer
    full_rope_theta: float = 500000.0
    full_rope_scaling: RopeScaling | None = RopeScaling(
        kind="yarn", factor=64.0, original_max_seq_len=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672,
    )
    full_rotary_factor: float = 0.5
    window_rope_theta: float = 10000.0
    window_rotary_factor: float = 1.0
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    # What the engine reads of a config: the dense planes' length and
    # storage (the windowed layers' rings are their own)
    kv_cache_len: None = None
    kv_cache_dtype: str = "model"

    def attention(self, layer: int) -> AttentionView:
        """``llama.Attention``'s config for ``layer``: its query heads,
        its kind's rotary embedding, and a ring of ``sliding_window``
        positions where the layer is windowed."""
        full = self.layer_types[layer] == FULL
        window = None if full else self.sliding_window
        factor = self.full_rotary_factor if full else self.window_rotary_factor
        rotary = int(self.head_dim * factor)
        return AttentionView(
            hidden_size=self.hidden_size, num_heads=self.heads_per_layer[layer],
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            max_seq_len=self.max_seq_len, dtype=self.dtype,
            sliding_window=window, kv_cache_len=window,
            rope_theta=self.full_rope_theta if full else self.window_rope_theta,
            rope_scaling=self.full_rope_scaling if full else None,
            rotary_dim=None if rotary == self.head_dim else rotary,
            rms_norm_eps=self.rms_norm_eps, attention_impl=self.attention_impl,
        )

    @staticmethod
    def tiny(**overrides) -> "LagunaConfig":
        """Test size: the dense full layer, then a period (windowed x 3,
        full); 4 query heads in the full layers and 6 in the windowed over
        2 K/V heads of 16; half the head rotated under YaRN in the full
        layers; a window of 8 that a short prompt outgrows; 16 experts,
        top 4, and a shared one."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, num_layers=5,
            layer_types=pattern(5), mlp_layer_types=("dense",) + ("sparse",) * 4,
            heads_per_layer=(4, 6, 6, 6, 4), num_heads=4, num_kv_heads=2,
            head_dim=16, sliding_window=8,
            full_rope_scaling=RopeScaling(
                kind="yarn", factor=8.0, original_max_seq_len=16, beta_fast=8.0,
                beta_slow=1.0, attention_factor=1.2,
            ),
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, max_seq_len=128,
        )
        base.update(overrides)
        return LagunaConfig(**base)


def _rope(params: dict, full: bool) -> tuple[float, RopeScaling | None, float]:
    """(theta, scaling, rotary fraction) of one kind's ``rope_parameters``;
    refuses a kind of rotary embedding this implementation does not
    compute."""
    kind = params.get("rope_type", "default")
    factor = float(params.get("partial_rotary_factor", 1.0))
    if kind == "default":
        return float(params["rope_theta"]), None, factor
    if kind != "yarn" or not full:
        raise ValueError(f"laguna: rope_type={kind!r} is not supported here")
    for k in ("mscale", "mscale_all_dim"):
        if params.get(k):
            raise ValueError(f"laguna: yarn {k}={params[k]!r} is not supported")
    if params.get("truncate", True) is not True:
        raise ValueError("laguna: yarn truncate=false is not supported")
    return float(params["rope_theta"]), RopeScaling(
        kind="yarn", factor=float(params["factor"]),
        original_max_seq_len=int(params["original_max_position_embeddings"]),
        beta_fast=float(params.get("beta_fast", 32.0)),
        beta_slow=float(params.get("beta_slow", 1.0)),
        attention_factor=params.get("attention_factor"),
    ), factor


def from_hf_config(hf: dict, **overrides) -> LagunaConfig:
    """The config of a published ``config.json`` (``model_type: laguna``).
    Refuses what this implementation does not compute."""
    want = {
        "moe_apply_router_weight_on_input": False, "moe_router_logit_softcapping": 0,
        "attention_bias": False, "tie_word_embeddings": False, "hidden_act": "silu",
        "norm_topk_prob": True, "decoder_sparse_step": 1,
    }
    for k, v in want.items():
        if hf.get(k, v) != v:
            raise ValueError(f"laguna: {k}={hf[k]!r} is not supported (only {v!r})")
    if hf.get("gating", True) not in (True, "per-head"):
        raise ValueError(f"laguna: gating={hf['gating']!r}: only a per-head output gate")
    if any(g != "per_head" for g in hf.get("gating_types", ())):
        raise ValueError("laguna: gating_types other than per_head are not supported")
    layers = hf["num_hidden_layers"]
    kinds = tuple(hf["layer_types"])
    mlp = tuple(hf["mlp_layer_types"])
    heads = tuple(hf.get("num_attention_heads_per_layer") or
                  (hf["num_attention_heads"],) * layers)
    if not len(kinds) == len(mlp) == len(heads) == layers:
        raise ValueError(f"laguna: the per-layer lists do not cover {layers} layers")
    if set(kinds) - {FULL, WINDOW} or set(mlp) - {"dense", "sparse"}:
        raise ValueError(f"laguna: unknown layer kinds {set(kinds) | set(mlp)}")
    kv = hf["num_key_value_heads"]
    if any(h % kv for h in heads):
        raise ValueError(f"laguna: query heads {sorted(set(heads))} are no multiple of {kv}")
    rp = hf["rope_parameters"]
    full_theta, full_scaling, full_factor = _rope(rp[FULL], True)
    window_theta, _, window_factor = _rope(rp[WINDOW], False)
    kw = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"], num_layers=layers,
        layer_types=kinds, mlp_layer_types=mlp, heads_per_layer=heads,
        num_heads=hf["num_attention_heads"], num_kv_heads=kv, head_dim=hf["head_dim"],
        sliding_window=hf["sliding_window"], full_rope_theta=full_theta,
        full_rope_scaling=full_scaling, full_rotary_factor=full_factor,
        window_rope_theta=window_theta, window_rotary_factor=window_factor,
        num_experts=hf["num_experts"], num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=hf["shared_expert_intermediate_size"],
        routed_scaling_factor=float(hf["moe_routed_scaling_factor"]),
        rms_norm_eps=hf["rms_norm_eps"], max_seq_len=hf["max_position_embeddings"],
    )
    kw.update(overrides)
    return LagunaConfig(**kw)


class Block(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(
        self, x, positions, decode=False, padded=False, adapter_ids=None,
        valid=None,
    ):
        cfg = self.cfg
        kind = "full" if cfg.layer_types[self.layer] == FULL else "window"
        n = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="in_norm")(x)
        with jax.named_scope(f"laguna.{kind}"):
            x = x + Attention(cfg.attention(self.layer), name="attn")(
                n, positions, None, decode, padded, adapter_ids,
                # a prefill wider than a ring stores each row's last
                # window of *real* positions, not of the padding
                valid if decode and padded else None,
            )
        n = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="ff_norm")(x)
        if cfg.mlp_layer_types[self.layer] == "dense":
            return x + MLP(cfg, name="mlp")(n, adapter_ids)
        y, group_sizes = DroplessMoE(
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            intermediate_size=cfg.moe_intermediate_size,
            shared_size=cfg.shared_expert_intermediate_size, scoring="softmax",
            norm_topk_prob=True, scaling=cfg.routed_scaling_factor,
            dtype=cfg.dtype, name="moe",
        )(n)  # routes by token, not adapter
        if decode:
            # what this layer has routed: decode_cache's ``moe_counts``
            routed = self.variable(
                "cache", "moe_counts", jnp.zeros, (cfg.num_experts + 2,), jnp.int32
            )
            pairs = n.shape[0] * n.shape[1] * cfg.num_experts_per_tok
            routed.value = routed.value + moe_counts(group_sizes, pairs)
        return x + y


class Laguna(nn.Module):
    cfg: LagunaConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        positions=None,
        decode=False,
        return_hidden=False,
        padded=False,
        adapter_ids=None,
        valid=None,
    ):
        """tokens (B, S) int32 -> float32 logits (B, S, vocab).

        The call signature of :class:`~tensorflowonspark_tpu.models.
        solar_open2.SolarOpen2`: no packed rows (a ring's slot is its
        position modulo the window, which documents restarting their
        positions would collide in). ``decode=True`` runs against the
        per-layer cache (apply with ``mutable=["cache"]``); ``valid``
        (B, S) bool marks the real tokens of a padded call, so that a
        ring keeps the last window of them. ``return_hidden=True``
        returns ``(hidden, lm_head)``, the head then being applied by
        :meth:`head`.
        """
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
            )
        embed = self.param(
            "embed", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.hidden_size),
        )
        x = embed_rows(embed, tokens).astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = Block(cfg, i, name=f"layer{i}")(
                x, positions, decode, padded, adapter_ids, valid
            )
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        head = self.param(
            "lm_head", nn.initializers.normal(0.02), (cfg.hidden_size, cfg.vocab_size),
        )
        if return_hidden:
            return x, head
        return head_logits(x, head, cfg.dtype)

    def head(self, hidden):
        """Logits of final-norm hidden states (..., H): see
        ``Llama.head``."""
        return head_logits(
            hidden, self.get_variable("params", "lm_head"), self.cfg.dtype
        )

    def counter_entries(self) -> tuple:
        """What each entry of the cache's ``moe_counts`` leaves counts,
        for whoever reads them (``decode_cache.moe_count_entries``)."""
        return moe_count_entries(0, self.cfg.num_experts)


def laguna_param_shardings(params, mesh):
    """Every leaf replicated: no ``model``- or ``expert``-axis table for
    the per-layer head counts and the banks yet (ROADMAP M1, M3). A
    ``zoo.ZooEntry`` must name one; nothing else calls it."""
    from tensorflowonspark_tpu.compute import layout

    return jax.tree.map(lambda _: layout.replicated(mesh), params)


def laguna_loss_fn(model: Laguna):
    """Next-token cross-entropy over unpacked rows ``tokens`` (B, S+1):
    the zoo entry's loss. Training through the routed experts is ROADMAP
    M1's; no cell trains this model."""
    from tensorflowonspark_tpu.models.llama import cross_entropy_loss

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return cross_entropy_loss(logits, tokens[:, 1:])

    return loss
