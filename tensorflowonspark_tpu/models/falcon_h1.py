"""Falcon-H1: a Mamba-2 mixer and grouped-query attention in parallel in
every block.

The published description is HF ``transformers``
``modeling_falcon_h1.py`` with the model's ``config.json``; field names
here follow that file's keys. One block::

    n = RMSNorm_in(x)
    x = x + ssm_out_multiplier * Mixer(n)
          + attention_out_multiplier * Attn(attention_in_multiplier * n)
    x = x + MLP(RMSNorm_ff(x))

Mixer and attention read the same ``n``; every branch carries the muP
multipliers of the published config (embedding, keys, the five zones of
the mixer's input projection, both halves of the MLP, the head).

Per request and layer the decode cache carries, beside the attention's
K/V planes, the mixer's recurrent state ``ssm`` (rows, h, p, N) and the
last ``k - 1`` inputs of its convolution ``conv`` (rows, k - 1, c): see
``models/decode_cache.py``. Recurrent state cannot be masked after the
fact, so a call says which of its positions are real tokens (``valid``).

Departures from the published implementation, each ``assumed``:

- the recurrent state is float32 whatever the model's dtype (a
  recurrence carried over a thousand steps; the published cache keeps
  the model's dtype);
- the convolution's window is stored (rows, k - 1, c), channels last
  (the published ``conv_states`` are (rows, c, k)): a minor dimension of
  3 would be padded to a TPU tile's 128 lanes;
- the convolution's weight is (k, c), tap ``k - 1`` on the current token
  (published: (c, 1, k)).

Reused from ``models/llama.py``: ``Attention`` (with its cached path),
``rope``, ``RMSNorm``, ``QDense``. No ``model``-axis sharding table yet
(ROADMAP M3).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu.models.llama import (
    Attention,
    QDense,
    RMSNorm,
    embed_rows,
    head_logits,
)
from tensorflowonspark_tpu.ops.ssd import causal_conv1d, ssd_scan, ssm_step


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The defaults are Falcon-H1-34B-Instruct's published
    ``config.json`` (but ``max_seq_len``: the published
    ``max_position_embeddings`` is 262144)."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    # the mixer: d_ssm channels as heads of d_head, n_groups of B / C,
    # state size, convolution kernel, the scan's chunk
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    # muP multipliers, as published
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # the zones of the mixer's input projection: z, x, B, C, dt
    ssm_multipliers: tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738,
    )
    mlp_multipliers: tuple[float, float] = (
        0.1767766952966369, 0.011160714285714284,
    )
    max_seq_len: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    kv_cache_dtype: str = "model"
    # What llama.Attention reads of its config and this architecture does
    # not vary: full causal attention, unscaled rotary positions, no
    # bias, no output gate, one map a head, K/V stored by position.
    attention_impl: str = "auto"
    attention_bias: bool = False
    use_rope: bool = True
    attention_output_gate: bool = False
    differential_lambda_init: None = None
    kv_heads_in_rows: bool = False
    rope_scaling: None = None
    sliding_window: None = None
    kv_cache_len: None = None

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @staticmethod
    def tiny(**overrides) -> "FalconH1Config":
        """Test size: every multiplier away from 1, groups fewer than
        heads, an attention head size that is not hidden / heads."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
            rope_theta=10000.0, mamba_d_ssm=64, mamba_n_heads=8,
            mamba_d_head=8, mamba_n_groups=2, mamba_d_state=16,
            mamba_d_conv=4, mamba_chunk_size=8, embedding_multiplier=2.0,
            lm_head_multiplier=0.5, key_multiplier=0.5,
            attention_in_multiplier=0.8, attention_out_multiplier=0.7,
            ssm_in_multiplier=0.9, ssm_out_multiplier=0.6,
            ssm_multipliers=(0.9, 1.1, 0.8, 1.2, 0.7),
            mlp_multipliers=(0.8, 0.6), max_seq_len=128,
        )
        base.update(overrides)
        return FalconH1Config(**base)


def from_hf_config(hf: dict, **overrides) -> FalconH1Config:
    """The config of a published ``config.json`` (``model_type:
    falcon_h1``). Refuses what this implementation does not compute."""
    want = {
        "mamba_rms_norm": True, "mamba_norm_before_gate": False,
        "attention_bias": False, "mlp_bias": False,
        "mamba_proj_bias": False, "projectors_bias": False,
        "mamba_conv_bias": True, "tie_word_embeddings": False,
        "rope_scaling": None, "hidden_act": "silu",
    }
    for k, v in want.items():
        if hf.get(k, v) != v:
            raise ValueError(f"falcon_h1: {k}={hf[k]!r} is not supported (only {v!r})")
    if hf["mamba_d_ssm"] != hf["mamba_n_heads"] * hf["mamba_d_head"]:
        raise ValueError("falcon_h1: mamba_d_ssm != mamba_n_heads * mamba_d_head")
    kw = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"], num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        max_seq_len=hf["max_position_embeddings"],
        ssm_multipliers=tuple(hf["ssm_multipliers"]),
        mlp_multipliers=tuple(hf["mlp_multipliers"]),
    )
    for k in (
        "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
        "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
        "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "ssm_in_multiplier", "ssm_out_multiplier",
    ):
        kw[k] = hf[k]
    kw.update(overrides)
    return FalconH1Config(**kw)


class Mixer(nn.Module):
    """The Mamba-2 mixer: in_proj -> [z | xBC | dt], a causal depthwise
    convolution and SiLU on xBC, the selective scan over x with B, C and
    dt, a gate by z, a grouped RMSNorm, out_proj."""

    cfg: FalconH1Config

    @nn.compact
    def __call__(self, n, decode=False, valid=None, adapter_ids=None):
        cfg = self.cfg
        d, h, p = cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_d_head
        g, N, k = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv
        c = cfg.conv_dim
        rows, s, _ = n.shape
        u = QDense(d + c + h, cfg.dtype, name="in_proj")(
            n * jnp.asarray(cfg.ssm_in_multiplier, n.dtype), adapter_ids
        )
        # one multiplier a zone of the projection: z | x | B | C | dt
        zones = np.repeat(
            np.asarray(cfg.ssm_multipliers, np.float32),
            [d, d, g * N, g * N, h],
        )
        u = u * jnp.asarray(zones, u.dtype)
        z, xBC, dt = u[..., :d], u[..., d : d + c], u[..., d + c :]
        conv_w = self.param(
            "conv_weight", nn.initializers.normal(k**-0.5), (k, c)
        )
        conv_b = self.param("conv_bias", nn.initializers.zeros, (c,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,))
        A_log = self.param("A_log", nn.initializers.zeros, (h,))
        D = self.param("D", nn.initializers.ones, (h,))
        A = -jnp.exp(A_log.astype(jnp.float32))
        if decode:
            state = self.variable(
                "cache", "ssm", jnp.zeros, (rows, h, p, N), jnp.float32
            )
            window = self.variable(
                "cache", "conv", jnp.zeros, (rows, k - 1, c), cfg.dtype
            )
            xBC, window.value = causal_conv1d(
                xBC, conv_w, conv_b, window.value, valid
            )
        else:
            xBC, _ = causal_conv1d(xBC, conv_w, conv_b, None, valid)
        xBC = nn.silu(xBC)
        x = xBC[..., :d].reshape(rows, s, h, p)
        B = xBC[..., d : d + g * N].reshape(rows, s, g, N)
        C = xBC[..., d + g * N :].reshape(rows, s, g, N)
        # no clamp: the published time_step_limit is (0, inf)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        if decode and s == 1 and valid is None:
            y, state.value = ssm_step(
                state.value, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D
            )
            y = y[:, None]
        else:
            y, final = ssd_scan(
                x, dt, A, B, C, D, chunk=cfg.mamba_chunk_size,
                initial_state=state.value if decode else None, valid=valid,
            )
            if decode:
                state.value = final
        # mamba_norm_before_gate false: gate, then RMSNorm over each group
        y = y.reshape(rows, s, d) * nn.silu(z.astype(jnp.float32))
        yg = y.reshape(rows, s, g, d // g)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_norm_eps
        )
        scale = self.param("norm_scale", nn.initializers.ones, (d,))
        y = (yg.reshape(rows, s, d) * scale).astype(cfg.dtype)
        return QDense(cfg.hidden_size, cfg.dtype, name="out_proj")(y, adapter_ids)


class MLP(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        cfg = self.cfg
        dense = lambda feats, name: QDense(feats, cfg.dtype, name=name)  # noqa: E731
        m_gate, m_down = cfg.mlp_multipliers
        gate = dense(cfg.intermediate_size, "gate_proj")(x, adapter_ids)
        up = dense(cfg.intermediate_size, "up_proj")(x, adapter_ids)
        act = up * nn.silu(gate * jnp.asarray(m_gate, gate.dtype))
        out = dense(cfg.hidden_size, "down_proj")(act, adapter_ids)
        return out * jnp.asarray(m_down, out.dtype)


class Block(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(
        self, x, positions, decode=False, padded=False, adapter_ids=None,
        valid=None,
    ):
        cfg = self.cfg
        n = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="in_norm")(x)
        mixed = Mixer(cfg, name="mixer")(n, decode, valid, adapter_ids)
        attended = Attention(cfg, name="attn")(
            n * jnp.asarray(cfg.attention_in_multiplier, n.dtype),
            positions, None, decode, padded, adapter_ids,
            # the mixer's output, and so every later layer's K/V, is wrong
            # at an invalid position: it may not overwrite a cached row
            valid if decode and padded else None,
        )
        x = (
            x
            + mixed * jnp.asarray(cfg.ssm_out_multiplier, mixed.dtype)
            + attended * jnp.asarray(cfg.attention_out_multiplier, attended.dtype)
        )
        ff = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="ff_norm")(x)
        return x + MLP(cfg, name="mlp")(ff, adapter_ids)


class FalconH1(nn.Module):
    cfg: FalconH1Config

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
        Llama` without packed rows (``segment_ids``): a document boundary
        inside a row would have to reset the recurrent state, which the
        scan does not do. ``decode=True`` runs against the per-layer
        cache (apply with ``mutable=["cache"]``); a call of one position
        without ``valid`` steps the recurrence (``ssm_step``), any other
        scans from the cached state (``ssd_scan``). ``valid`` (B, S) bool
        marks the real tokens: padding after a prompt, or the overlap of
        a chunk shifted back, must be marked false, or the recurrence
        runs over it. ``padded`` and ``adapter_ids`` as in ``Llama``;
        ``return_hidden=True`` returns ``(hidden, lm_head)``, the head
        then being applied by :meth:`head`.
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
        x = embed_rows(embed, tokens)
        x = (x * jnp.asarray(cfg.embedding_multiplier, x.dtype)).astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = Block(cfg, name=f"layer{i}")(
                x, positions, decode, padded, adapter_ids, valid
            )
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.hidden_size, cfg.vocab_size),
        )
        if return_hidden:
            return x, head
        return head_logits(x, head, cfg.dtype, cfg.lm_head_multiplier)

    def head(self, hidden):
        """Logits of final-norm hidden states (..., H): see
        ``Llama.head``."""
        return head_logits(
            hidden, self.get_variable("params", "lm_head"), self.cfg.dtype,
            self.cfg.lm_head_multiplier,
        )


def falcon_h1_param_shardings(params, mesh):
    """Every leaf replicated: no ``model``-axis table for the mixer's
    projections yet (ROADMAP M3)."""
    from tensorflowonspark_tpu.compute import layout

    return jax.tree.map(lambda _: layout.replicated(mesh), params)


def falcon_h1_loss_fn(model: FalconH1):
    """Next-token cross-entropy over unpacked rows ``tokens`` (B, S+1)."""
    from tensorflowonspark_tpu.models.llama import cross_entropy_loss

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return cross_entropy_loss(logits, tokens[:, 1:])

    return loss
