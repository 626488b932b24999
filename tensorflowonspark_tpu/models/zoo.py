"""Model-zoo factory: name -> ready-to-train model bundle.

Parity note: the reference's ``examples/slim`` tree exposed TF-slim's
``nets_factory.get_network_fn(name)`` so scripts could pick any zoo
model by flag (SURVEY.md §2.4 "v1-era legacy"). This is that surface for
the rebuild's families: pass ``--model resnet50`` (etc.) in a driver
script and train without writing model code.

Every entry resolves to a :class:`ZooEntry` carrying the flax module, an
example input maker (for ``model.init``), the mesh sharding rules, and a
loss builder with the right signature family:

- image classifiers (``kind='image'``): batches ``{'image','label'}``,
  loss ``(params, batch_stats, batch) -> (loss, new_batch_stats)``
- token models (``kind='tokens'``): batches ``{'tokens'}`` (Llama) or
  model-specific (BERT — see its example), loss from the model module
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np


@dataclasses.dataclass(frozen=True)
class ZooEntry:
    name: str
    kind: str  # 'image' | 'tokens' | 'segmentation'
    model: Any  # flax module
    make_input: Callable[[int], dict]  # batch_size -> example numpy batch
    param_shardings: Callable  # (params, mesh) -> sharding tree
    make_loss: Callable[[], Callable]  # () -> loss fn for the kind
    has_batch_stats: bool = False


def _image_entry(name, model, shardings, loss_builder, size, classes):
    def make_input(b):
        rng = np.random.default_rng(0)
        return {
            "image": rng.random((b, size, size, 3)).astype(np.float32),
            "label": rng.integers(0, classes, size=b).astype(np.int32),
        }

    return ZooEntry(
        name=name,
        kind="image",
        model=model,
        make_input=make_input,
        param_shardings=shardings,
        make_loss=lambda: loss_builder(model),
        has_batch_stats=True,
    )


def _build_resnet(variant, tiny, num_classes):
    from tensorflowonspark_tpu.models import resnet

    cfg = (
        resnet.ResNetConfig.tiny(num_classes=num_classes)
        if tiny
        else getattr(resnet.ResNetConfig, variant)(num_classes=num_classes)
    )
    return _image_entry(
        variant,
        resnet.ResNet(cfg),
        resnet.resnet_param_shardings,
        resnet.loss_fn,
        32 if tiny else 224,
        num_classes,
    )


def _build_inception(tiny, num_classes):
    from tensorflowonspark_tpu.models import inception

    cfg = (
        inception.InceptionConfig.tiny(num_classes=num_classes)
        if tiny
        else inception.InceptionConfig.v3(num_classes=num_classes)
    )
    return _image_entry(
        "inception_v3",
        inception.InceptionV3(cfg),
        inception.inception_param_shardings,
        inception.loss_fn,
        64 if tiny else 299,
        num_classes,
    )


def _build_vgg(variant, tiny, num_classes):
    from tensorflowonspark_tpu.models import vgg

    cfg = (
        vgg.VGGConfig.tiny(num_classes=num_classes)
        if tiny
        else getattr(vgg.VGGConfig, variant)(num_classes=num_classes)
    )
    return _image_entry(
        variant,
        vgg.VGG(cfg),
        vgg.vgg_param_shardings,
        vgg.loss_fn,
        32 if tiny else 224,
        num_classes,
    )


def _build_vit(tiny, num_classes):
    from tensorflowonspark_tpu.models import vit

    cfg = (
        vit.ViTConfig.tiny(num_classes=num_classes)
        if tiny
        else vit.ViTConfig.b16(num_classes=num_classes)
    )
    entry = _image_entry(
        "vit_b16",
        vit.ViT(cfg),
        vit.vit_param_shardings,
        vit.loss_fn,
        cfg.image_size,
        num_classes,
    )
    # ViT has no BatchNorm; its loss passes the (empty) stats through
    return dataclasses.replace(entry, has_batch_stats=False)


def _build_unet(tiny, num_classes):
    from tensorflowonspark_tpu.models import unet

    cfg = (
        unet.UNetConfig.tiny()
        if tiny
        else unet.UNetConfig(num_classes=num_classes)
    )
    model = unet.UNet(cfg)

    def make_input(b):
        rng = np.random.default_rng(0)
        s = 16 if tiny else 128
        return {
            "image": rng.random((b, s, s, 3)).astype(np.float32),
            "mask": rng.integers(0, cfg.num_classes, size=(b, s, s)).astype(
                np.int32
            ),
        }

    return ZooEntry(
        name="unet",
        kind="segmentation",
        model=model,
        make_input=make_input,
        param_shardings=unet.unet_param_shardings,
        make_loss=lambda: unet.loss_fn(model),
    )


def _build_bert(tiny):
    from tensorflowonspark_tpu.models import bert

    cfg = bert.BertConfig.tiny() if tiny else bert.BertConfig()
    model = bert.BertForMLM(cfg)

    def make_input(b):
        rng = np.random.default_rng(0)
        s = min(cfg.max_seq_len, 32 if tiny else 128)
        return {
            "tokens": rng.integers(0, cfg.vocab_size, size=(b, s)).astype(
                np.int32
            ),
            "targets": rng.integers(0, cfg.vocab_size, size=(b, s)).astype(
                np.int32
            ),
        }

    def make_loss():
        import optax

        def loss(params, batch):
            logits = model.apply({"params": params}, batch["tokens"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["targets"]
            ).mean()

        return loss

    return ZooEntry(
        name="bert_base",
        kind="tokens",
        model=model,
        make_input=make_input,
        param_shardings=bert.bert_param_shardings,
        make_loss=make_loss,
    )


def _build_llama(variant, tiny):
    from tensorflowonspark_tpu.models import llama as L

    if tiny:
        cfg = L.LlamaConfig.tiny(
            sliding_window=8 if variant == "mistral_7b" else None,
            attention_bias=variant == "qwen2_7b",
        )
    elif variant == "llama2_7b":
        cfg = L.LlamaConfig.llama2_7b()
    elif variant == "llama3_8b":
        cfg = L.LlamaConfig.llama3_8b()
    elif variant == "mistral_7b":
        cfg = L.LlamaConfig.mistral_7b()
    elif variant == "qwen2_7b":
        cfg = L.LlamaConfig.qwen2_7b()
    else:  # llama_1b (the single-chip benchmark config)
        cfg = L.LlamaConfig.llama_1b()
    model = L.Llama(cfg)

    def make_input(b):
        rng = np.random.default_rng(0)
        s = min(cfg.max_seq_len, 32 if tiny else 1024)
        return {
            "tokens": rng.integers(0, cfg.vocab_size, size=(b, s + 1)).astype(
                np.int32
            )
        }

    def make_loss():
        token_loss = L.llama_loss_fn(model)
        return lambda p, batch: token_loss(p, batch["tokens"])

    return ZooEntry(
        name=variant,
        kind="tokens",
        model=model,
        make_input=make_input,
        param_shardings=L.llama_param_shardings,
        make_loss=make_loss,
    )


def _build_falcon_h1(tiny):
    from tensorflowonspark_tpu.models import falcon_h1 as F

    # full size: the defaults are Falcon-H1-34B's published config
    cfg = F.FalconH1Config.tiny() if tiny else F.FalconH1Config()
    model = F.FalconH1(cfg)

    def make_input(b):
        rng = np.random.default_rng(0)
        s = min(cfg.max_seq_len, 32 if tiny else 1024)
        return {
            "tokens": rng.integers(0, cfg.vocab_size, size=(b, s + 1)).astype(
                np.int32
            )
        }

    def make_loss():
        token_loss = F.falcon_h1_loss_fn(model)
        return lambda p, batch: token_loss(p, batch["tokens"])

    return ZooEntry(
        name="falcon_h1_34b",
        kind="tokens",
        model=model,
        make_input=make_input,
        param_shardings=F.falcon_h1_param_shardings,
        make_loss=make_loss,
    )


def _build_pangu_moe(tiny):
    from tensorflowonspark_tpu.models import pangu_moe as P

    # full size: the defaults are openPangu-Ultra-MoE-718B's published
    # config, every routed expert held (no one chip holds that)
    cfg = P.PanguMoEConfig.tiny() if tiny else P.PanguMoEConfig()
    model = P.PanguMoE(cfg)

    def make_input(b):
        rng = np.random.default_rng(0)
        s = min(cfg.max_seq_len, 32 if tiny else 1024)
        return {
            "tokens": rng.integers(0, cfg.vocab_size, size=(b, s + 1)).astype(
                np.int32
            )
        }

    def make_loss():
        token_loss = P.pangu_moe_loss_fn(model)
        return lambda p, batch: token_loss(p, batch["tokens"])

    return ZooEntry(
        name="pangu_ultra_moe_718b",
        kind="tokens",
        model=model,
        make_input=make_input,
        param_shardings=P.pangu_moe_param_shardings,
        make_loss=make_loss,
    )


def _build_solar_open2(tiny):
    from tensorflowonspark_tpu.models import solar_open2 as S

    # full size: the defaults are Solar-Open2-250B's published config,
    # every routed expert held (no one chip holds that)
    cfg = S.SolarOpen2Config.tiny() if tiny else S.SolarOpen2Config()
    model = S.SolarOpen2(cfg)

    def make_input(b):
        rng = np.random.default_rng(0)
        s = min(cfg.max_seq_len, 32 if tiny else 1024)
        return {
            "tokens": rng.integers(0, cfg.vocab_size, size=(b, s + 1)).astype(
                np.int32
            )
        }

    def make_loss():
        token_loss = S.solar_open2_loss_fn(model)
        return lambda p, batch: token_loss(p, batch["tokens"])

    return ZooEntry(
        name="solar_open2_250b",
        kind="tokens",
        model=model,
        make_input=make_input,
        param_shardings=S.solar_open2_param_shardings,
        make_loss=make_loss,
    )


_BUILDERS: dict[str, Callable[..., ZooEntry]] = {
    "resnet18": lambda tiny, nc: _build_resnet("resnet18", tiny, nc),
    "resnet34": lambda tiny, nc: _build_resnet("resnet34", tiny, nc),
    "resnet50": lambda tiny, nc: _build_resnet("resnet50", tiny, nc),
    "resnet101": lambda tiny, nc: _build_resnet("resnet101", tiny, nc),
    "inception_v3": lambda tiny, nc: _build_inception(tiny, nc),
    "vgg11": lambda tiny, nc: _build_vgg("vgg11", tiny, nc),
    "vgg16": lambda tiny, nc: _build_vgg("vgg16", tiny, nc),
    "vit_b16": lambda tiny, nc: _build_vit(tiny, nc),
    "unet": lambda tiny, nc: _build_unet(tiny, nc),
    "bert_base": lambda tiny, nc: _build_bert(tiny),
    "llama_1b": lambda tiny, nc: _build_llama("llama_1b", tiny),
    "llama2_7b": lambda tiny, nc: _build_llama("llama2_7b", tiny),
    "llama3_8b": lambda tiny, nc: _build_llama("llama3_8b", tiny),
    "mistral_7b": lambda tiny, nc: _build_llama("mistral_7b", tiny),
    "qwen2_7b": lambda tiny, nc: _build_llama("qwen2_7b", tiny),
    "falcon_h1_34b": lambda tiny, nc: _build_falcon_h1(tiny),
    "pangu_ultra_moe_718b": lambda tiny, nc: _build_pangu_moe(tiny),
    "solar_open2_250b": lambda tiny, nc: _build_solar_open2(tiny),
}


def names() -> list[str]:
    return sorted(_BUILDERS)


def build(name: str, tiny: bool = False, num_classes: int = 1000) -> ZooEntry:
    """Resolve a zoo model by name (the ``nets_factory`` surface).

    ``tiny=True`` swaps in each family's CI-size config; ``num_classes``
    applies to the image families.
    """
    if name not in _BUILDERS:
        raise KeyError(
            f"unknown zoo model {name!r}; available: {', '.join(names())}"
        )
    return _BUILDERS[name](tiny, num_classes)
