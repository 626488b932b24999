"""openPangu-Ultra-MoE: latent attention, a dense leading stack, then
routed experts beside a shared one, four norms a layer.

The published description is the model's ``config.json`` (``model_type:
pangu_ultra_moe``); field names here follow its keys. One layer
(``sandwich_norm``, four RMSNorms)::

    x = x + N_post_attn(Attn(N_in(x)))
    x = x + N_post_mlp(F(N_pre_mlp(x)))

``F`` is a SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers and, after them, the routed experts with
the shared expert (``parallel.moe.DroplessMoE``). A model may hold one
shard of the routed experts (``first_expert``, ``experts_held``): the
router still scores all ``n_routed_experts``, and what the absent
experts would add is left out.

Latent attention, per token and head ``h``::

    c_q = RMSNorm(x W_qa);  [q_nope_h | q_rope_h] = c_q W_qb
    [c_kv | k_r] = x W_kva;  c_kv <- RMSNorm(c_kv);  k_r <- RoPE(k_r)
    [k_nope_h | v_h] = c_kv W_kvb
    score = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_r) / sqrt(nope + rope)

``k_r`` is one for all heads. **The decode cache holds ``(c_kv, k_r)``**,
one entry a position and layer (``latent``, ``models/decode_cache.py``),
not per-head keys and values. A call that makes its cache (a prompt from
position 0) expands keys and values and attends among its own positions;
a call against a cache attends in the *absorbed* form, the same
mathematics reassociated: ``q~_h = q_nope_h W_kvb,h^K`` meets ``c_kv``
directly, the context is ``sum p c_kv`` and goes through ``W_kvb,h^V``
once, so the plane is both K and V. One new position a row on one TPU
takes ``ops.decode_attention.latent_decode_attention``; every other such
call the einsum.

Departures from the published implementation, each ``assumed``:

- RoPE pairs dimension ``i`` with ``i + d/2`` as ``llama.rope`` does (the
  published code permutes the rotary dimensions first: with the weights
  this repo is given, a permutation of columns of ``W_qb`` and ``W_kva``);
- sigmoid scoring, no group limit, no correction bias (the config
  carries none of ``scoring_func``, ``n_group``, ``topk_group``);
- the next-token-prediction module (``num_nextn_predict_layers``) is not
  part of the next-token forward pass and is not built (ROADMAP M5).

Reused from ``models/llama.py``: ``rope``, ``RMSNorm``, ``QDense``,
``embed_rows``, ``head_logits``. No ``model``-axis sharding table.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.decode_cache import (
    moe_count_entries,
    moe_counts,
    starts_sequence,
)
from tensorflowonspark_tpu.models.llama import (
    QDense,
    RMSNorm,
    embed_rows,
    head_logits,
    rope,
)
from tensorflowonspark_tpu.ops.attention import dot_product_attention
from tensorflowonspark_tpu.ops.decode_attention import (
    cache_block_k,
    latent_decode_attention,
    latent_entry_width,
)
from tensorflowonspark_tpu.parallel.moe import DroplessMoE


@dataclasses.dataclass(frozen=True)
class PanguMoEConfig:
    """The defaults are openPangu-Ultra-MoE-718B's published
    ``config.json`` (but ``max_seq_len``: the published
    ``max_position_embeddings`` is 131072)."""

    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_layers: int = 61
    first_k_dense_replace: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    # the shard of routed experts this model holds: experts
    # first_expert .. first_expert + experts_held - 1 (None: all)
    first_expert: int = 0
    experts_held: int | None = None
    max_seq_len: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    # What the serving engine and ``cache_block_k`` read of a config and
    # this architecture does not vary: one latent entry a position in the
    # model's dtype, full causal attention. Any other value is refused.
    kv_cache_dtype: str = "model"
    kv_cache_len: None = None
    sliding_window: None = None

    @property
    def num_kv_heads(self) -> int:
        return self.num_heads

    @property
    def held(self) -> int:
        return (
            self.n_routed_experts if self.experts_held is None
            else self.experts_held
        )

    @staticmethod
    def tiny(**overrides) -> "PanguMoEConfig":
        """Test size: a dense layer and two expert layers, a shard of 4
        of 16 experts that does not start at 0, every head width
        different from the others."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_layers=3, first_k_dense_replace=1,
            num_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
            n_routed_experts=16, num_experts_per_tok=4, first_expert=4,
            experts_held=4, rope_theta=10000.0, max_seq_len=128,
        )
        base.update(overrides)
        return PanguMoEConfig(**base)


def from_hf_config(hf: dict, **overrides) -> PanguMoEConfig:
    """The config of a published ``config.json`` (``model_type:
    pangu_ultra_moe``). Refuses what this implementation does not
    compute."""
    want = {
        "attention_bias": False, "hidden_act": "silu", "sandwich_norm": True,
        "tie_word_embeddings": False, "scoring_func": "sigmoid",
        "rope_scaling": None,
    }
    for k, v in want.items():
        if hf.get(k, v) != v:
            raise ValueError(f"pangu_moe: {k}={hf[k]!r} is not supported (only {v!r})")
    for k in ("n_group", "topk_group"):
        if hf.get(k, 1) not in (None, 1):
            raise ValueError(f"pangu_moe: {k}={hf[k]!r}: no group-limited routing")
    if hf.get("num_key_value_heads", hf["num_attention_heads"]) != hf["num_attention_heads"]:
        raise ValueError("pangu_moe: latent attention has no grouped KV heads")
    kw = dict(
        num_layers=hf["num_hidden_layers"], num_heads=hf["num_attention_heads"],
        rope_theta=float(hf["rope_theta"]),
        max_seq_len=hf["max_position_embeddings"],
    )
    for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "first_k_dense_replace", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
        "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps",
    ):
        kw[k] = hf[k]
    kw.update(overrides)
    return PanguMoEConfig(**kw)


class LatentAttention(nn.Module):
    cfg: PanguMoEConfig

    @nn.compact
    def __call__(
        self, x, positions, decode=False, adapter_ids=None, valid=None
    ):
        cfg = self.cfg
        if cfg.kv_cache_dtype != "model" or cfg.kv_cache_len is not None:
            raise ValueError(
                "a latent cache is kept whole and in the model's dtype: "
                f"kv_cache_dtype={cfg.kv_cache_dtype!r} and kv_cache_len="
                f"{cfg.kv_cache_len!r} are unsupported (no int8 entry, no "
                "rolling plane)"
            )
        b, s, _ = x.shape
        heads, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rot, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        scale = (nope + rot) ** -0.5
        dense = lambda feats, name: QDense(feats, cfg.dtype, name=name)  # noqa: E731
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        with jax.named_scope("mla.project"):
            c_q = norm("q_a_norm")(dense(cfg.q_lora_rank, "q_a_proj")(x, adapter_ids))
            q = dense(heads * (nope + rot), "q_b_proj")(c_q, adapter_ids)
            q = q.reshape(b, s, heads, nope + rot)
            q_nope = q[..., :nope]
            q_rope = rope(q[..., nope:], positions, cfg.rope_theta)
            kv = dense(rank + rot, "kv_a_proj")(x, adapter_ids)
            c_kv = norm("kv_a_norm")(kv[..., :rank])
            k_r = rope(kv[..., None, rank:], positions, cfg.rope_theta)[:, :, 0]
            # (rank, heads x [k_nope | v]): the up-projection of the
            # compressed key-value, used whole or a head's half at a time
            w_kvb = self.param(
                "kv_b_proj", nn.initializers.normal(0.02),
                (rank, heads * (nope + vd)),
            ).astype(cfg.dtype)
        fresh = not decode or starts_sequence(self, "latent")
        if decode:
            C = cfg.max_seq_len
            width = latent_entry_width(rank, rot)
            plane = self.variable(
                "cache", "latent", jnp.zeros, (b, C, width), cfg.dtype
            )
            entry = jnp.concatenate(
                [c_kv, k_r, jnp.zeros((b, s, width - rank - rot), cfg.dtype)],
                axis=-1,
            )
            at = positions
            if valid is not None:
                at = jnp.where(valid, positions, C)  # out of range: dropped
            plane.value = plane.value.at[jnp.arange(b)[:, None], at].set(
                entry, mode="drop"
            )
        if fresh:
            # A call that starts its sequence: per-head keys and values
            # from the latent, attention among the call's own positions
            # (query-key width nope + rope, value width v_head_dim).
            with jax.named_scope("mla.attend"):
                kv_up = (c_kv @ w_kvb).reshape(b, s, heads, nope + vd)
                k = jnp.concatenate(
                    [kv_up[..., :nope],
                     jnp.broadcast_to(k_r[:, :, None, :], (b, s, heads, rot))],
                    axis=-1,
                )
                out = dot_product_attention(
                    jnp.concatenate([q_nope, q_rope], axis=-1), k,
                    kv_up[..., nope:], causal=True, scale=scale,
                    impl=cfg.attention_impl,
                )
        else:
            w = w_kvb.reshape(rank, heads, nope + vd)
            with jax.named_scope("mla.absorb"):
                q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w[..., :nope])
            with jax.named_scope("mla.attend"):
                if s == 1 and cache_block_k(cfg) is not None:
                    ctx = latent_decode_attention(
                        q_lat[:, 0], q_rope[:, 0], plane.value,
                        positions[:, 0] + 1, scale=scale,
                    )[:, None]
                else:
                    lat = plane.value
                    logits = (
                        jnp.einsum(
                            "bshc,bkc->bhsk", q_lat, lat[..., :rank],
                            preferred_element_type=jnp.float32,
                        )
                        + jnp.einsum(
                            "bshr,bkr->bhsk", q_rope,
                            lat[..., rank : rank + rot],
                            preferred_element_type=jnp.float32,
                        )
                    ) * scale
                    mask = (
                        jnp.arange(C)[None, None, None, :]
                        <= positions[:, None, :, None]
                    )
                    probs = jax.nn.softmax(
                        jnp.where(mask, logits, -1e30), axis=-1
                    ).astype(cfg.dtype)
                    ctx = jnp.einsum("bhsk,bkc->bshc", probs, lat[..., :rank])
            with jax.named_scope("mla.absorb"):
                out = jnp.einsum("bshc,chd->bshd", ctx, w[..., nope:])
        return dense(cfg.hidden_size, "o_proj")(
            out.reshape(b, s, heads * vd), adapter_ids
        )


class MLP(nn.Module):
    cfg: PanguMoEConfig

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        cfg = self.cfg
        dense = lambda feats, name: QDense(feats, cfg.dtype, name=name)  # noqa: E731
        gate = dense(cfg.intermediate_size, "gate_proj")(x, adapter_ids)
        up = dense(cfg.intermediate_size, "up_proj")(x, adapter_ids)
        return dense(cfg.hidden_size, "down_proj")(nn.silu(gate) * up, adapter_ids)


class Block(nn.Module):
    cfg: PanguMoEConfig
    routed: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, adapter_ids=None, valid=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
        attended = LatentAttention(cfg, name="attn")(
            norm("in_norm")(x), positions, decode, adapter_ids, valid
        )
        x = x + norm("post_attn_norm")(attended)
        n = norm("pre_mlp_norm")(x)
        if not self.routed:
            return x + norm("post_mlp_norm")(MLP(cfg, name="mlp")(n, adapter_ids))
        y, group_sizes = DroplessMoE(
            num_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            intermediate_size=cfg.moe_intermediate_size,
            shared_size=cfg.n_shared_experts * cfg.moe_intermediate_size,
            first_held=cfg.first_expert, held=cfg.held,
            scoring=cfg.scoring_func, norm_topk_prob=cfg.norm_topk_prob,
            scaling=cfg.routed_scaling_factor, dtype=cfg.dtype, name="moe",
        )(n)  # routes by token, not adapter
        if decode:
            # what this layer has routed: decode_cache's ``moe_counts``
            routed = self.variable(
                "cache", "moe_counts", jnp.zeros, (cfg.held + 2,), jnp.int32
            )
            pairs = n.shape[0] * n.shape[1] * cfg.num_experts_per_tok
            routed.value = routed.value + moe_counts(group_sizes, pairs)
        return x + norm("post_mlp_norm")(y)


class PanguMoE(nn.Module):
    cfg: PanguMoEConfig

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

        The call signature of :class:`~tensorflowonspark_tpu.models.llama.
        Llama` without packed rows. ``decode=True`` runs against the
        per-layer latent cache (apply with ``mutable=["cache"]``): every
        row writes at its own ``positions`` whether ``padded`` or not, a
        call without a cache starts its sequence and attends among its
        own positions, any other attends everything its row has written.
        ``valid`` (B, S) bool: a position marked false writes no cache
        entry (entries are masked by position when read, so ``Llama``'s
        rule holds and the engine's padding may be written too).
        ``return_hidden=True`` returns ``(hidden, lm_head)``, the head
        then being applied by :meth:`head`.
        """
        del padded
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
            )
        embed = self.param(
            "embed", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size),
        )
        x = embed_rows(embed, tokens).astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = Block(
                cfg, routed=i >= cfg.first_k_dense_replace, name=f"layer{i}"
            )(x, positions, decode, adapter_ids, valid)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.hidden_size, cfg.vocab_size),
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
        return moe_count_entries(self.cfg.first_expert, self.cfg.held)


def pangu_moe_param_shardings(params, mesh):
    """Every leaf replicated: no ``model``- or ``expert``-axis table for
    the latent projections and the held banks yet (ROADMAP M1, M4). A
    ``zoo.ZooEntry`` must name one; nothing else calls it."""
    from tensorflowonspark_tpu.compute import layout

    return jax.tree.map(lambda _: layout.replicated(mesh), params)


def pangu_moe_loss_fn(model: PanguMoE):
    """Next-token cross-entropy over unpacked rows ``tokens`` (B, S+1):
    the zoo entry's loss (``tests/test_zoo.py`` trains one step of every
    name at its tiny size). Training at the published size is ROADMAP
    M1's; no cell trains this model."""
    from tensorflowonspark_tpu.models.llama import cross_entropy_loss

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return cross_entropy_loss(logits, tokens[:, 1:])

    return loss
