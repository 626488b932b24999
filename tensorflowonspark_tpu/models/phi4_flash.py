"""Phi-4-mini-flash: SambaY (arXiv:2507.06607), a decoder-hybrid-decoder.
A self-decoder of Mamba-1 layers and windowed attention, one full
attention layer whose K/V the cross-decoder's attention layers read
instead of their own, and gated memory units between them.

The published description is the model's ``config.json`` (``model_type:
phi4flash``); field names here follow its keys. ``mb_per_layer`` = 2 and
``L`` layers make the pattern (``layer_types``)::

    l <= L/2, l even     Mamba-1                 (l = L/2: its y is the memory)
    l <  L/2, l odd      windowed attention, window sliding_window
    l = L/2 + 1          full attention: its K/V is the shared plane
    l >  L/2 + 1, even   GMU: (SiLU(x W1) * m) W2, m the memory
    l >  L/2 + 1, odd    cross attention: queries only, over the shared K/V

Every layer, pre-norm with LayerNorm (scale and bias)::

    x = x + Mix_l(LN1(x));   x = x + W_fc2 (SiLU(g) * v),  [g, v] = LN2(x) W_fc1

then the final LayerNorm and the head tied to the embedding.

**Mamba-1**: ``[u, z] = x W_in``; ``u = SiLU(conv(u) + b)`` (causal,
depthwise); ``[d, B, C] = u W_x``; ``dt = softplus(d W_dt + b_dt)``; ``A =
-exp(A_log)``; the selective scan (``ops/selective_scan.py``); ``(y *
SiLU(z)) W_out``. Its cache is the state ``ssm`` (rows, N, c) and the
convolution's window ``conv`` (rows, k - 1, c): ``models/decode_cache.py``.

**Differential attention** in all attention layers (Diff Transformer,
arXiv:2410.05258, as the published modelling code applies it): adjacent
heads pair up, ``a = softmax(q1 k1^T / sqrt(d)) V - lambda softmax(q2 k2^T
/ sqrt(d)) V`` with ``V = [v1 | v2]``, then RMSNorm over the 2d values and
``1 - lambda_init``. It is ``llama.Attention`` with
``differential_lambda_init`` set, over K/V stored as pairs (``attention``
below): no kernel of its own. The windowed layers keep a ring of
``sliding_window`` positions, the full layer a dense plane of
``max_seq_len``; the cross layers keep nothing.

**The cross-decoder writes no cache**, so a ``decode=True`` call of more
than one position (a prefill) runs it at each row's last valid position
only: there alone is the returned hidden state defined. A call of one
position, and a ``decode=False`` forward, run every layer at every
position.

Readings the config leaves open, each ``assumed`` in the benchmark's
configuration file: the Mamba sizes (``d_state`` 16, ``d_conv`` 4,
``expand`` 2, ``dt_rank`` ceil(hidden / 16); in, x and out projections
without bias, the convolution and ``dt`` with one); no bias on the
attention and GMU projections; the memory is the memory layer's ``y``
before its gate by ``z``; the pairing of heads, ``lambda_init = 0.8 - 0.6
exp(-0.3 l)`` and the subln's epsilon; the state float32 (rows, N, c),
channels minor.

Reused: ``llama.Attention`` / ``QDense`` / ``embed_rows`` / ``head_logits``,
``ops.ssd.causal_conv1d``. No ``model``-axis sharding table.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.llama import (
    Attention,
    QDense,
    embed_rows,
    head_logits,
)
from tensorflowonspark_tpu.ops.selective_scan import (
    selective_scan,
    selective_scan_step,
)
from tensorflowonspark_tpu.ops.ssd import causal_conv1d


def layer_types(num_layers: int, mb_per_layer: int) -> tuple[str, ...]:
    """Each layer's kind (``mamba``, ``swa``, ``full``, ``gmu``,
    ``cross``), as the module docstring lays them out."""
    half = num_layers // 2
    out = []
    for i in range(num_layers):
        mamba = i % mb_per_layer == 0
        if i <= half:
            out.append("mamba" if mamba else "swa")
        elif i == half + 1:
            out.append("full")
        else:
            out.append("gmu" if mamba else "cross")
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class AttentionView:
    """What ``llama.Attention`` reads of its config, for one attention
    layer: query rows two a differential head, K/V pairs as stored."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int
    dtype: jnp.dtype
    sliding_window: int | None
    kv_cache_len: int | None
    differential_lambda_init: float
    rms_norm_eps: float
    attention_impl: str = "auto"
    attention_bias: bool = False
    use_rope: bool = False
    rope_theta: float = 10000.0
    rope_scaling: None = None
    rotary_dim: None = None
    kv_cache_dtype: str = "model"
    key_multiplier: float = 1.0
    attention_output_gate: bool = False
    kv_heads_in_rows: bool = True


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The defaults are Phi-4-mini-flash-reasoning's published
    ``config.json`` (but ``max_seq_len``: the published
    ``max_position_embeddings`` is 262144), with the Mamba sizes it leaves
    to the modelling code."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mb_per_layer: int = 2
    layer_types: tuple[str, ...] = layer_types(32, 2)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    max_seq_len: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    # What the engine reads of a config: the dense planes' length and
    # storage (the windowed layers' rings are their own)
    kv_cache_len: None = None
    kv_cache_dtype: str = "model"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose ``y`` the GMUs read; the full attention
        layer follows it, the cross-decoder that one."""
        return self.layer_types.index("full") - 1

    def attention(self, layer: int) -> AttentionView:
        """``llama.Attention``'s config for attention ``layer``: 2h query
        rows of 2d against h / 2 pairs of K/V heads of 2d; a ring of
        ``sliding_window`` positions where the layer is windowed."""
        window = self.sliding_window if self.layer_types[layer] == "swa" else None
        return AttentionView(
            hidden_size=self.hidden_size, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads // 2, head_dim=2 * self.head_dim,
            max_seq_len=self.max_seq_len, dtype=self.dtype,
            sliding_window=window, kv_cache_len=window,
            differential_lambda_init=0.8 - 0.6 * math.exp(-0.3 * layer),
            rms_norm_eps=self.layer_norm_eps, attention_impl=self.attention_impl,
        )

    @staticmethod
    def tiny(**overrides) -> "Phi4FlashConfig":
        """Test size: every kind of layer (mamba, swa, mamba, swa, mamba,
        full, gmu, cross), GQA 2:1 over the pairs as published, a window
        of 8 that a short prompt outgrows."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_layers=8, num_heads=8, num_kv_heads=4, sliding_window=8,
            layer_types=layer_types(8, 2), mamba_d_state=8, mamba_dt_rank=4,
            max_seq_len=64,
        )
        base.update(overrides)
        return Phi4FlashConfig(**base)


def from_hf_config(hf: dict, **overrides) -> Phi4FlashConfig:
    """The config of a published ``config.json`` (``model_type:
    phi4flash``). Refuses what this implementation does not compute."""
    want = {
        "hidden_act": "silu", "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False,
    }
    for k, v in want.items():
        if hf.get(k, v) != v:
            raise ValueError(f"phi4flash: {k}={hf[k]!r} is not supported (only {v!r})")
    layers, mb = hf["num_hidden_layers"], hf["mb_per_layer"]
    kinds = layer_types(layers, mb) if mb >= 1 else ()
    if mb < 1 or kinds[layers // 2] != "mamba" or layers // 2 + 2 > layers:
        raise ValueError(
            f"phi4flash: mb_per_layer={mb} over {layers} layers puts no Mamba "
            "layer before the full attention layer, whose output the GMUs read"
        )
    heads, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    if heads % 2 or kv % 2 or heads % kv:
        raise ValueError(
            f"phi4flash: {heads} query / {kv} KV heads do not pair up for "
            "differential attention"
        )
    hidden = hf["hidden_size"]
    # the Mamba sizes the config leaves to the modelling code, where a
    # file states them (the benchmark's configurations: ``mamba``)
    m = hf.get("mamba", {})
    kw = dict(
        vocab_size=hf["vocab_size"], hidden_size=hidden,
        intermediate_size=hf["intermediate_size"], num_layers=layers,
        num_heads=heads, num_kv_heads=kv, sliding_window=hf["sliding_window"],
        layer_norm_eps=hf["layer_norm_eps"], mb_per_layer=mb, layer_types=kinds,
        mamba_d_state=m.get("d_state", 16), mamba_d_conv=m.get("d_conv", 4),
        mamba_expand=m.get("expand", 2),
        mamba_dt_rank=m.get("dt_rank", math.ceil(hidden / 16)),
        max_seq_len=hf["max_position_embeddings"],
    )
    kw.update(overrides)
    return Phi4FlashConfig(**kw)


class LayerNorm(nn.Module):
    """LayerNorm with scale and bias, in float32."""

    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (h,))
        bias = self.param("bias", nn.initializers.zeros, (h,))
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale + bias).astype(self.dtype)


class Mamba(nn.Module):
    """The Mamba-1 mixer. Returns its output and ``y`` before the gate
    by ``z`` (the memory, where this is the memory layer), float32."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, n, decode=False, valid=None, adapter_ids=None):
        cfg = self.cfg
        c, N, k, r = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
        rows, s, _ = n.shape
        f32 = jnp.float32
        uz = QDense(2 * c, cfg.dtype, name="in_proj")(n, adapter_ids)
        u, z = uz[..., :c], uz[..., c:]
        conv_w = self.param("conv_weight", nn.initializers.normal(k**-0.5), (k, c))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (c,))
        if decode:
            window = self.variable(
                "cache", "conv", jnp.zeros, (rows, k - 1, c), cfg.dtype
            )
            u, window.value = causal_conv1d(u, conv_w, conv_b, window.value, valid)
        else:
            u, _ = causal_conv1d(u, conv_w, conv_b, None, valid)
        u = nn.silu(u)
        dbc = QDense(r + 2 * N, cfg.dtype, name="x_proj")(u, adapter_ids)
        dt = QDense(c, cfg.dtype, name="dt_proj")(dbc[..., :r], adapter_ids)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (c,))
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        B, C = dbc[..., r : r + N], dbc[..., r + N :]
        # A_log (c, N) as published; the state lies (N, c)
        A_log = self.param("A_log", nn.initializers.zeros, (c, N))
        A = -jnp.exp(A_log.astype(f32)).T
        D = self.param("D", nn.initializers.ones, (c,))
        if decode:
            state = self.variable("cache", "ssm", jnp.zeros, (rows, N, c), f32)
        if decode and s == 1 and valid is None:
            with jax.named_scope("sambay.mamba.step"):
                y, state.value = selective_scan_step(
                    state.value, u[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D
                )
                y = y[:, None]
        else:
            with jax.named_scope("sambay.mamba.scan"):
                y, final = selective_scan(
                    u, dt, A, B, C, D, state.value if decode else None, valid
                )
                if decode:
                    state.value = final
        gated = (y * nn.silu(z.astype(f32))).astype(cfg.dtype)
        return QDense(cfg.hidden_size, cfg.dtype, name="out_proj")(gated, adapter_ids), y


class Block(nn.Module):
    cfg: Phi4FlashConfig
    layer: int

    @nn.compact
    def __call__(
        self, x, positions, memory, kv, decode=False, padded=False,
        adapter_ids=None, valid=None,
    ):
        """One layer. ``memory``: the memory layer's ``y`` (None before
        it); ``kv``: the full layer's K/V as ``Attention(return_kv=True)``
        gives them (None before it). Returns ``(x, memory, kv)``."""
        cfg = self.cfg
        kind = cfg.layer_types[self.layer]
        n = LayerNorm(cfg.layer_norm_eps, cfg.dtype, name="in_norm")(x)
        if kind == "mamba":
            mixed, y = Mamba(cfg, name="mamba")(n, decode, valid, adapter_ids)
            if self.layer == cfg.memory_layer:
                memory = y
        elif kind == "gmu":
            with jax.named_scope("sambay.gmu"):
                gate = QDense(cfg.d_inner, cfg.dtype, name="gmu_in")(n, adapter_ids)
                gated = nn.silu(gate.astype(jnp.float32)) * memory.astype(jnp.float32)
                mixed = QDense(cfg.hidden_size, cfg.dtype, name="gmu_out")(
                    gated.astype(cfg.dtype), adapter_ids
                )
        else:
            attn = Attention(cfg.attention(self.layer), name="attn")
            # a Mamba layer's output, and so every later layer's K/V, is
            # wrong at an invalid position: it may not overwrite a
            # cached row
            keep = valid if decode and padded else None
            with jax.named_scope(f"sambay.{kind}"):
                if kind == "cross":
                    mixed = attn(
                        n, positions, None, decode, padded, adapter_ids,
                        shared_kv=kv,
                    )
                elif kind == "full":
                    mixed, kv = attn(
                        n, positions, None, decode, padded, adapter_ids, keep,
                        return_kv=True,
                    )
                else:
                    mixed = attn(n, positions, None, decode, padded, adapter_ids, keep)
        x = x + mixed
        n = LayerNorm(cfg.layer_norm_eps, cfg.dtype, name="ff_norm")(x)
        gv = QDense(2 * cfg.intermediate_size, cfg.dtype, name="fc1")(n, adapter_ids)
        g, v = gv[..., : cfg.intermediate_size], gv[..., cfg.intermediate_size :]
        act = (nn.silu(g.astype(jnp.float32)) * v.astype(jnp.float32)).astype(cfg.dtype)
        return x + QDense(cfg.hidden_size, cfg.dtype, name="fc2")(act, adapter_ids), memory, kv


class Phi4Flash(nn.Module):
    cfg: Phi4FlashConfig

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
        boundary would have to reset the scan's state). ``decode=True``
        runs against the per-layer cache (apply with ``mutable=
        ["cache"]``); a call of one position without ``valid`` steps the
        scan (``selective_scan_step``), any other scans from the cached
        state. ``valid`` (B, S) bool marks the real tokens: padding after
        a prompt must be marked false, or the scan runs over it. A
        ``decode=True`` call of more than one position runs the
        cross-decoder at each row's last valid position (the last one
        where ``valid`` is None): its logits and hidden states are defined
        there alone. ``return_hidden=True`` returns ``(hidden, head)``,
        the head (the embedding, transposed) then being applied by
        :meth:`head`.
        """
        cfg = self.cfg
        rows, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), tokens.shape)
        embed = self.param(
            "embed", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.hidden_size),
        )
        x = embed_rows(embed, tokens).astype(cfg.dtype)
        memory = kv = last = None
        for i in range(cfg.num_layers):
            if i == cfg.memory_layer + 2 and decode and s > 1:
                # the layers from here on write no cache: only the
                # position whose logits are asked for runs through them
                last = (
                    jnp.full((rows,), s - 1, jnp.int32) if valid is None
                    else jnp.sum(valid, axis=1, dtype=jnp.int32) - 1
                )
                at = lambda t: jnp.take_along_axis(  # noqa: E731
                    t, last.reshape((rows, 1) + (1,) * (t.ndim - 2)), axis=1)
                whole = x
                x, memory, positions, valid = at(x), at(memory), at(positions), None
            x, memory, kv = Block(cfg, i, name=f"layer{i}")(
                x, positions, memory, kv, decode, padded, adapter_ids, valid
            )
        if last is not None:
            x = whole.at[jnp.arange(rows), last].set(x[:, 0])
        x = LayerNorm(cfg.layer_norm_eps, cfg.dtype, name="final_norm")(x)
        if return_hidden:
            return x, embed.T
        return head_logits(x, embed.T, cfg.dtype)

    def head(self, hidden):
        """Logits of final-norm hidden states (..., H) under the tied
        head: see ``Llama.head``."""
        return head_logits(hidden, self.get_variable("params", "embed").T, self.cfg.dtype)


def phi4_flash_param_shardings(params, mesh):
    """Every leaf replicated: no ``model``-axis table for the Mamba
    projections yet (ROADMAP M3). A ``zoo.ZooEntry`` must name one;
    nothing else calls it."""
    from tensorflowonspark_tpu.compute import layout

    return jax.tree.map(lambda _: layout.replicated(mesh), params)


def phi4_flash_loss_fn(model: Phi4Flash):
    """Next-token cross-entropy over unpacked rows ``tokens`` (B, S+1):
    the zoo entry's loss. No cell trains this model."""
    from tensorflowonspark_tpu.models.llama import cross_entropy_loss

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return cross_entropy_loss(logits, tokens[:, 1:])

    return loss
