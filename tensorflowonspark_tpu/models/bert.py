"""BERT encoder family (BERT-base is the BASELINE.json text/estimator config).

Parity note: the reference had no transformer models of its own — its
"models" layer was the examples tree (SURVEY.md §2.4) and the estimator
pipeline (`tensorflowonspark/pipeline.py:TFEstimator`) was the API users
fine-tuned text models through. The rebuild's baseline names BERT-base
fine-tune via the estimator path; this file supplies that model natively.

TPU-first design notes:

- bf16 matmuls with fp32 LayerNorm and fp32 softmax (inside the shared
  attention op) — MXU-friendly without fp16-style loss-scaling.
- Bidirectional attention via the shared
  :func:`tensorflowonspark_tpu.ops.attention.dot_product_attention`.
  Padding is handled with ``segment_ids`` so batches keep static shapes
  under jit; note the shared op currently runs masked (padded) batches on
  the XLA path — the Pallas flash kernel kicks in for unpadded batches.
- ``bert_param_shardings``: Megatron rules — attention heads and FFN
  hidden over 'model' (TP), the complementary dim over 'fsdp'.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tensorflowonspark_tpu.compute import layout

from tensorflowonspark_tpu.ops.attention import dot_product_attention


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def bert_base(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def bert_large(**kw) -> "BertConfig":
        return BertConfig(
            hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096, **kw
        )

    @staticmethod
    def tiny(**overrides) -> "BertConfig":
        base = dict(
            vocab_size=128,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            intermediate_size=128,
            max_seq_len=64,
        )
        base.update(overrides)
        return BertConfig(**base)


class _LayerNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        # fp32 statistics regardless of activation dtype.
        return nn.LayerNorm(epsilon=self.eps, dtype=jnp.float32)(x)


class EncoderBlock(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.config
        h = cfg.num_heads
        d = cfg.head_dim
        dense = lambda f, name: nn.Dense(f, dtype=cfg.dtype, name=name)

        # Post-LN (original BERT): attn -> add&norm -> ffn -> add&norm.
        q = dense(h * d, "query")(x).reshape(*x.shape[:2], h, d)
        k = dense(h * d, "key")(x).reshape(*x.shape[:2], h, d)
        v = dense(h * d, "value")(x).reshape(*x.shape[:2], h, d)
        attn = dot_product_attention(
            q, k, v, causal=False, segment_ids=segment_ids, impl=cfg.attention_impl
        )
        attn = dense(cfg.hidden_size, "attn_out")(attn.reshape(*x.shape))
        x = _LayerNorm(cfg.layer_norm_eps, name="attn_ln")(x + attn).astype(cfg.dtype)

        ffn = dense(cfg.intermediate_size, "ffn_in")(x)
        ffn = nn.gelu(ffn)
        ffn = dense(cfg.hidden_size, "ffn_out")(ffn)
        return _LayerNorm(cfg.layer_norm_eps, name="ffn_ln")(x + ffn).astype(cfg.dtype)


class Bert(nn.Module):
    """Returns (sequence_output [B,S,H], pooled_output [B,H])."""

    config: BertConfig

    @nn.compact
    def __call__(self, tokens, token_types=None, attention_mask=None):
        cfg = self.config
        B, S = tokens.shape
        if token_types is None:
            token_types = jnp.zeros_like(tokens)
        # The 0/1 padding mask is used directly as segment ids: attention
        # flows only between positions with EQUAL mask values, so real (1)
        # never attends to pad (0). Pad-pad attention is harmless — pad
        # positions are dropped by downstream masking/loss.
        segment_ids = attention_mask

        emb = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="tok")(
            tokens
        )
        emb += nn.Embed(
            cfg.type_vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="typ"
        )(token_types)
        pos = self.param(
            "pos",
            nn.initializers.normal(0.02),
            (cfg.max_seq_len, cfg.hidden_size),
        )
        emb += pos[None, :S].astype(cfg.dtype)
        x = _LayerNorm(cfg.layer_norm_eps, name="emb_ln")(emb).astype(cfg.dtype)

        block = EncoderBlock
        if cfg.remat:
            block = nn.remat(EncoderBlock, static_argnums=())
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"layer_{i}")(x, segment_ids)

        pooled = nn.tanh(
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="pooler")(x[:, 0])
        )
        return x, pooled


class BertForClassification(nn.Module):
    config: BertConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, tokens, token_types=None, attention_mask=None):
        _, pooled = Bert(self.config, name="bert")(tokens, token_types, attention_mask)
        # Head in fp32 for a stable softmax.
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(pooled)


class BertForMLM(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, tokens, token_types=None, attention_mask=None):
        cfg = self.config
        seq, _ = Bert(cfg, name="bert")(tokens, token_types, attention_mask)
        x = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="mlm_transform")(seq)
        x = _LayerNorm(cfg.layer_norm_eps, name="mlm_ln")(nn.gelu(x)).astype(cfg.dtype)
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32, name="mlm_head")(x)


def bert_param_shardings(params, mesh: Mesh):
    """Megatron-style rules keyed on param names (see module docstring)
    — the declarative 'bert' table in
    :mod:`tensorflowonspark_tpu.compute.layout`: a rule whose named
    dims don't divide the mesh extents falls through to the next."""
    return layout.param_shardings(params, mesh, "bert")


def classification_loss_fn(model: BertForClassification):
    """Build ``loss(params, batch)`` for batches
    {'tokens', 'label', optional 'mask'}."""
    import optax

    def loss(params, batch):
        logits = model.apply(
            {"params": params},
            batch["tokens"],
            attention_mask=batch.get("mask"),
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]
        ).mean()

    return loss
