"""Solar-Open2: layers that differ in kind. Three gated delta-rule
linear-attention layers to every gated grouped-query attention layer
without rotary positions, routed experts beside a shared one in all of
them.

The published description is the model's ``config.json`` (``model_type:
solar_open2``); field names here follow its keys, and the ``kda_`` keys
name Kimi Delta Attention (arXiv:2510.26692), whose gates the linear
layer follows. One layer ``l``, pre-norm::

    x = x + Mix_l(N1(x))        Mix_l: gated GQA where l in gqa_layers,
    x = x + MoE(N2(x))                 else the linear layer

**Gated GQA** is ``llama.Attention`` with ``use_rope`` false (NoPE: the
causal mask alone orders the keys) and ``attention_output_gate`` true:
``o = (softmax(q k^T / sqrt(d)) v * sigmoid(x W_g)) W_o``.

**The linear layer**, per head ``h`` of ``linear_num_heads``, ``d_k =
d_v = linear_head_dim``::

    q~, k~, v~ = SiLU(Conv(x W_q)), SiLU(Conv(x W_k)), SiLU(Conv(x W_v))
    q = L2norm_h(q~) / sqrt(d_k);  k = L2norm_h(k~);  v = v~
    g = -exp(A_log_h) * softplus((x W_f_a) W_f_b + dt_bias);  a = exp(g)
    b = sigmoid(x W_b)           (doubled where kda_allow_neg_eigval)
    S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T;   o_t = S_t^T q_t
    y_h = RMSNorm(o_h) * sigmoid((x W_g_a) W_g_b + bias);  out = y W_o

(``ops/kda.py``: the chunked form for a prompt, the one-position step
for decode). The convolutions are causal, depthwise, without bias.

A layer's cache therefore holds *either* K/V planes (a GQA layer) *or*
the delta-rule state ``kda`` (rows, h, d_k, d_v) and the last ``k - 1``
inputs of the three convolutions, one window ``conv`` (rows, k - 1,
3 h d): see ``models/decode_cache.py``. Recurrent state cannot be masked
after the fact, so a call says which of its positions are real tokens
(``valid``), as ``models/falcon_h1.py``'s does.

A model may hold one shard of the routed experts (``first_expert``,
``experts_held``) as ``models/pangu_moe.py`` does: the router still
scores all ``n_routed_experts``, what the absent experts would add is
left out.

Readings the config leaves open, each ``assumed``:

- the attention's gate is elementwise over its ``heads x head_dim``
  outputs, from the layer's normed input, before ``o_proj``; no QK norm;
- ``kda_use_full_proj`` false: the decay gate and the output gate are
  low-rank, of rank ``linear_head_dim``; only the output gate has a bias;
- the head norm's scale is one vector of ``linear_head_dim`` for all
  heads;
- the state is float32 whatever the model's dtype (a recurrence carried
  over thousands of steps), the window the model's dtype, stored (rows,
  k - 1, c) channels last as ``falcon_h1`` stores its own;
- sigmoid scoring, no group limit, no correction bias (the config carries
  none of ``scoring_func``, ``n_group``, ``topk_group``).

Reused: ``llama.Attention`` / ``RMSNorm`` / ``QDense`` / ``embed_rows`` /
``head_logits``, ``ops.ssd.causal_conv1d``, ``parallel.moe.DroplessMoE``.
No ``model``-axis sharding table.
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
    Attention,
    QDense,
    RMSNorm,
    embed_rows,
    head_logits,
)
from tensorflowonspark_tpu.ops.kda import kda_chunked, kda_step
from tensorflowonspark_tpu.ops.ssd import causal_conv1d
from tensorflowonspark_tpu.parallel.moe import DroplessMoE


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """The defaults are Solar-Open2-250B's published ``config.json`` (but
    ``max_seq_len``: the published ``max_position_embeddings`` is
    1048576)."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    moe_intermediate_size: int = 1280
    num_layers: int = 48
    # the layers that are gated GQA; every other one is linear
    gqa_layers: tuple[int, ...] = tuple(range(0, 48, 4))
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    use_rope: bool = False
    rope_theta: float = 10000.0
    # use_gqa_gate, under the name llama.Attention reads
    attention_output_gate: bool = True
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    linear_conv_kernel: int = 4
    kda_allow_neg_eigval: bool = True
    kda_chunk_size: int = 32
    n_routed_experts: int = 320
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    # the shard of routed experts this model holds: experts
    # first_expert .. first_expert + experts_held - 1 (None: all)
    first_expert: int = 0
    experts_held: int | None = None
    max_seq_len: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    # What llama.Attention reads of its config and this architecture does
    # not vary: full causal attention, no bias, keys as projected, one
    # map a head, K/V stored by position.
    attention_impl: str = "auto"
    attention_bias: bool = False
    rope_scaling: None = None
    rotary_dim: None = None
    sliding_window: None = None
    kv_cache_len: None = None
    kv_cache_dtype: str = "model"
    key_multiplier: float = 1.0
    differential_lambda_init: None = None
    kv_heads_in_rows: bool = False

    @property
    def held(self) -> int:
        return (
            self.n_routed_experts if self.experts_held is None
            else self.experts_held
        )

    @property
    def linear_dim(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @staticmethod
    def tiny(**overrides) -> "SolarOpen2Config":
        """Test size: a period and a layer (GQA, linear, linear, linear,
        GQA), a shard of 4 of 16 experts that does not start at 0, a
        linear head width unlike the attention's, a chunk that a short
        prompt crosses."""
        base = dict(
            vocab_size=256, hidden_size=64, moe_intermediate_size=32,
            num_layers=5, gqa_layers=(0, 4), num_heads=4, num_kv_heads=2,
            head_dim=16, linear_num_heads=4, linear_head_dim=8,
            kda_chunk_size=8, n_routed_experts=16, num_experts_per_tok=4,
            first_expert=4, experts_held=4, max_seq_len=128,
        )
        base.update(overrides)
        return SolarOpen2Config(**base)


def from_hf_config(hf: dict, **overrides) -> SolarOpen2Config:
    """The config of a published ``config.json`` (``model_type:
    solar_open2``). Refuses what this implementation does not compute."""
    want = {
        "kda_use_full_proj": False, "first_k_dense_replace": 0,
        "partial_rotary_factor": 1, "tie_word_embeddings": False,
        "attention_bias": False, "hidden_act": "silu",
        "scoring_func": "sigmoid", "rope_scaling": None,
    }
    for k, v in want.items():
        if hf.get(k, v) != v:
            raise ValueError(f"solar_open2: {k}={hf[k]!r} is not supported (only {v!r})")
    for k in ("n_group", "topk_group"):
        if hf.get(k, 1) not in (None, 1):
            raise ValueError(f"solar_open2: {k}={hf[k]!r}: no group-limited routing")
    lin = hf["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError(
            "solar_open2: linear_attn_config.num_kv_heads="
            f"{lin['num_kv_heads']!r}: the linear layer has a key a head"
        )
    layers = hf["num_hidden_layers"]
    gqa = tuple(hf["gqa_layers"])
    if any(not 0 <= l < layers for l in gqa):
        raise ValueError(f"solar_open2: gqa_layers={gqa} outside {layers} layers")
    kw = dict(
        num_layers=layers, gqa_layers=gqa,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        rope_theta=float(hf["rope_theta"]),
        attention_output_gate=hf["use_gqa_gate"],
        linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        linear_conv_kernel=lin["short_conv_kernel_size"],
        max_seq_len=hf["max_position_embeddings"],
    )
    for k in (
        "vocab_size", "hidden_size", "moe_intermediate_size", "head_dim",
        "use_rope", "kda_allow_neg_eigval", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
        "routed_scaling_factor", "rms_norm_eps",
    ):
        kw[k] = hf[k]
    kw.update(overrides)
    return SolarOpen2Config(**kw)


class DeltaMixer(nn.Module):
    """The linear layer: projections, the three convolutions as one over
    their channels side by side, the gates, the delta rule, the gated
    head norm, ``o_proj``."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, n, decode=False, valid=None, adapter_ids=None):
        cfg = self.cfg
        h, d, kw = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel
        c = cfg.linear_dim
        rows, s, _ = n.shape
        f32 = jnp.float32
        dense = lambda feats, name, b=False: QDense(  # noqa: E731
            feats, cfg.dtype, use_bias=b, name=name
        )
        with jax.named_scope("kda.project"):
            qkv = jnp.concatenate(
                [dense(c, f"{t}_proj")(n, adapter_ids) for t in "qkv"], axis=-1
            )
            f = dense(c, "f_b_proj")(dense(d, "f_a_proj")(n, adapter_ids), adapter_ids)
            gate = dense(c, "g_b_proj", True)(
                dense(d, "g_a_proj")(n, adapter_ids), adapter_ids
            )
            b = dense(h, "b_proj")(n, adapter_ids)
        with jax.named_scope("kda.conv"):
            init = nn.initializers.normal(kw**-0.5)
            conv_w = jnp.concatenate(
                [self.param(f"{t}_conv", init, (kw, c)) for t in "qkv"], axis=-1
            )
            no_bias = jnp.zeros((3 * c,), f32)
            if decode:
                window = self.variable(
                    "cache", "conv", jnp.zeros, (rows, kw - 1, 3 * c), cfg.dtype
                )
                qkv, window.value = causal_conv1d(
                    qkv, conv_w, no_bias, window.value, valid
                )
            else:
                qkv, _ = causal_conv1d(qkv, conv_w, no_bias, None, valid)
            qkv = nn.silu(qkv).astype(f32).reshape(rows, s, 3, h, d)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6
            )
            q, k = unit(q) * d**-0.5, unit(k)
        with jax.named_scope("kda.gates"):
            A_log = self.param("A_log", nn.initializers.zeros, (h,))
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (c,))
            g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
                (f.astype(f32) + dt_bias.astype(f32)).reshape(rows, s, h, d)
            )
            beta = jax.nn.sigmoid(b.astype(f32))
            if cfg.kda_allow_neg_eigval:
                beta = 2.0 * beta
        if decode:
            state = self.variable(
                "cache", "kda", jnp.zeros, (rows, h, d, d), f32
            )
        if decode and s == 1 and valid is None:
            with jax.named_scope("kda.step"):
                o, state.value = kda_step(
                    state.value, q[:, 0], k[:, 0], v[:, 0],
                    jnp.exp(g[:, 0]), beta[:, 0],
                )
                o = o[:, None]
        else:
            with jax.named_scope("kda.chunk"):
                o, final = kda_chunked(
                    q, k, v, g, beta, chunk=cfg.kda_chunk_size,
                    initial_state=state.value if decode else None, valid=valid,
                )
                if decode:
                    state.value = final
        with jax.named_scope("kda.out"):
            scale = self.param("o_norm", nn.initializers.ones, (d,))
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps
            ) * scale.astype(f32)
            y = o.reshape(rows, s, c) * jax.nn.sigmoid(gate.astype(f32))
            return dense(cfg.hidden_size, "o_proj")(y.astype(cfg.dtype), adapter_ids)


class Block(nn.Module):
    cfg: SolarOpen2Config
    gqa: bool

    @nn.compact
    def __call__(
        self, x, positions, decode=False, padded=False, adapter_ids=None,
        valid=None,
    ):
        cfg = self.cfg
        n = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="in_norm")(x)
        if self.gqa:
            mixed = Attention(cfg, name="attn")(
                n, positions, None, decode, padded, adapter_ids,
                # a linear layer's output, and so every later layer's K/V,
                # is wrong at an invalid position: it may not overwrite a
                # cached row
                valid if decode and padded else None,
            )
        else:
            mixed = DeltaMixer(cfg, name="mixer")(n, decode, valid, adapter_ids)
        x = x + mixed
        n = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="ff_norm")(x)
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
        return x + y


class SolarOpen2(nn.Module):
    cfg: SolarOpen2Config

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
        falcon_h1.FalconH1`, for its reasons: no packed rows (a document
        boundary would have to reset the delta-rule state). ``decode=
        True`` runs against the per-layer cache (apply with ``mutable=
        ["cache"]``); a call of one position without ``valid`` steps the
        recurrence (``kda_step``), any other runs the chunked form from
        the cached state. ``valid`` (B, S) bool marks the real tokens:
        padding after a prompt must be marked false, or the recurrence
        runs over it. ``return_hidden=True`` returns ``(hidden,
        lm_head)``, the head then being applied by :meth:`head`.
        """
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
            x = Block(cfg, gqa=i in cfg.gqa_layers, name=f"layer{i}")(
                x, positions, decode, padded, adapter_ids, valid
            )
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


def solar_open2_param_shardings(params, mesh):
    """Every leaf replicated: no ``model``- or ``expert``-axis table for
    the linear layer's projections and the held banks yet (ROADMAP M1,
    M3). A ``zoo.ZooEntry`` must name one; nothing else calls it."""
    from tensorflowonspark_tpu.compute import layout

    return jax.tree.map(lambda _: layout.replicated(mesh), params)


def solar_open2_loss_fn(model: SolarOpen2):
    """Next-token cross-entropy over unpacked rows ``tokens`` (B, S+1):
    the zoo entry's loss. Training at the published size is ROADMAP
    M1's; no cell trains this model."""
    from tensorflowonspark_tpu.models.llama import cross_entropy_loss

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return cross_entropy_loss(logits, tokens[:, 1:])

    return loss
