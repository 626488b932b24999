"""ResNet-v1.5 family (ResNet-50 is the BASELINE.json image config).

Parity note: the reference's image-classification story was the
Inception/cifar10 example trees and the "near-linear scaling" README chart
(SURVEY.md §2.4, §6); the rebuild's baseline names ResNet-50 as the image
workload. This is a from-scratch flax implementation, not a port.

TPU-first design notes:

- NHWC layout throughout (XLA's native TPU conv layout); convs in bf16 so
  they tile onto the MXU, BatchNorm statistics accumulated in fp32.
- v1.5 variant (stride-2 in the 3x3 of the bottleneck, not the 1x1) — the
  standard throughput/accuracy tradeoff for accelerator training.
- No Python control flow under jit; the block stack is unrolled at trace
  time from a static per-stage spec.
- ``resnet_param_shardings``: batch-stat and scale/bias params replicated;
  large conv kernels and the FC layer sharded over 'fsdp' for ZeRO-style
  data parallelism. TP of convs is not worth it at ResNet scale.
"""

from __future__ import annotations

import dataclasses
import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tensorflowonspark_tpu.compute import layout

from tensorflowonspark_tpu.ops.batch_norm import FusedBatchNorm


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: tuple[int, ...] = (3, 4, 6, 3)
    bottleneck: bool = True
    num_classes: int = 1000
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16

    @staticmethod
    def resnet18(**kw) -> "ResNetConfig":
        return ResNetConfig(stage_sizes=(2, 2, 2, 2), bottleneck=False, **kw)

    @staticmethod
    def resnet34(**kw) -> "ResNetConfig":
        return ResNetConfig(stage_sizes=(3, 4, 6, 3), bottleneck=False, **kw)

    @staticmethod
    def resnet50(**kw) -> "ResNetConfig":
        return ResNetConfig(stage_sizes=(3, 4, 6, 3), bottleneck=True, **kw)

    @staticmethod
    def resnet101(**kw) -> "ResNetConfig":
        return ResNetConfig(stage_sizes=(3, 4, 23, 3), bottleneck=True, **kw)

    @staticmethod
    def tiny(**overrides) -> "ResNetConfig":
        """Test-size config: 2 stages, thin width, bottleneck on."""
        base = dict(stage_sizes=(1, 1), width=8, num_classes=10)
        base.update(overrides)
        return ResNetConfig(**base)


class _ConvBN(nn.Module):
    features: int
    kernel: tuple[int, int]
    strides: tuple[int, int]
    dtype: jnp.dtype
    act: bool = True

    @nn.compact
    def __call__(self, x, train: bool):
        x = nn.Conv(
            self.features,
            self.kernel,
            self.strides,
            padding="SAME",
            use_bias=False,
            dtype=self.dtype,
        )(x)
        # Fused-statistics BN (ops/batch_norm.py): the round-3 chip profile
        # showed 48% of the ResNet-50 step in separate BN stats reduction
        # passes under nn.BatchNorm + autodiff; the custom-VJP op computes
        # both channel statistics per direction in ONE variadic-reduce
        # pass over the bf16 activations (stats accumulate fp32).
        # name= pins the pre-round-3 auto-name (nn.BatchNorm era) so
        # checkpoints saved before the FusedBatchNorm swap restore as-is.
        x = FusedBatchNorm(
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
            name="BatchNorm_0",
        )(x, use_running_average=not train)
        return nn.relu(x) if self.act else x


class BasicBlock(nn.Module):
    features: int
    strides: tuple[int, int]
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, train: bool):
        residual = x
        y = _ConvBN(self.features, (3, 3), self.strides, self.dtype)(x, train)
        y = _ConvBN(self.features, (3, 3), (1, 1), self.dtype, act=False)(y, train)
        if residual.shape != y.shape:
            residual = _ConvBN(
                self.features, (1, 1), self.strides, self.dtype, act=False
            )(residual, train)
        return nn.relu(y + residual)


class BottleneckBlock(nn.Module):
    features: int
    strides: tuple[int, int]
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, train: bool):
        residual = x
        y = _ConvBN(self.features, (1, 1), (1, 1), self.dtype)(x, train)
        # v1.5: the stride lives on the 3x3, not the first 1x1.
        y = _ConvBN(self.features, (3, 3), self.strides, self.dtype)(y, train)
        y = _ConvBN(self.features * 4, (1, 1), (1, 1), self.dtype, act=False)(y, train)
        if residual.shape != y.shape:
            residual = _ConvBN(
                self.features * 4, (1, 1), self.strides, self.dtype, act=False
            )(residual, train)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    config: ResNetConfig

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.config
        block = BottleneckBlock if cfg.bottleneck else BasicBlock
        x = x.astype(cfg.dtype)
        x = _ConvBN(cfg.width, (7, 7), (2, 2), cfg.dtype)(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for stage, size in enumerate(cfg.stage_sizes):
            for i in range(size):
                strides = (2, 2) if stage > 0 and i == 0 else (1, 1)
                x = block(cfg.width * 2**stage, strides, cfg.dtype)(x, train)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        # Classifier head in fp32 for a stable softmax.
        return nn.Dense(cfg.num_classes, dtype=jnp.float32)(x)


def resnet_param_shardings(params, mesh: Mesh):
    """FSDP rules: shard large kernels' output-channel dim over 'fsdp';
    replicate BN scale/bias (tiny) — the declarative 'resnet' table in
    :mod:`tensorflowonspark_tpu.compute.layout`."""
    return layout.param_shardings(params, mesh, "resnet")


def loss_fn(model: ResNet):
    """Build ``loss(params, batch_stats, batch) -> (loss, new_batch_stats)``
    for batches {'image', 'label'}."""
    import optax

    def loss(params, batch_stats, batch):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["image"],
            train=True,
            mutable=["batch_stats"],
        )
        l = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]
        ).mean()
        return l, mutated["batch_stats"]

    return loss
