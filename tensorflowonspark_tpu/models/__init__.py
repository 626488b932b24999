"""Model zoo.

The reference's "models" were its examples (SURVEY.md §2.4: MNIST keras/
estimator, U-Net segmentation, cifar10/inception legacy); the rebuild's
baseline configs add ResNet-50, BERT-base, and Llama-2 (BASELINE.json). All
models are flax.linen modules designed for bf16 MXU math and mesh sharding
(see each model's ``param_shardings``).
"""

from tensorflowonspark_tpu.models import mnist  # noqa: F401
from tensorflowonspark_tpu.models.bert import (  # noqa: F401
    Bert,
    BertConfig,
    BertForClassification,
    BertForMLM,
    bert_param_shardings,
)
from tensorflowonspark_tpu.models.falcon_h1 import (  # noqa: F401
    FalconH1,
    FalconH1Config,
    falcon_h1_param_shardings,
)
from tensorflowonspark_tpu.models.inception import (  # noqa: F401
    InceptionConfig,
    InceptionV3,
    inception_param_shardings,
)
from tensorflowonspark_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    Llama,
    llama_param_shardings,
)
from tensorflowonspark_tpu.models.speculative import (  # noqa: F401
    speculative_accept,
    speculative_generate,
)
from tensorflowonspark_tpu.models.laguna import (  # noqa: F401
    Laguna,
    LagunaConfig,
    laguna_param_shardings,
)
from tensorflowonspark_tpu.models.pangu_moe import (  # noqa: F401
    PanguMoE,
    PanguMoEConfig,
    pangu_moe_param_shardings,
)
from tensorflowonspark_tpu.models.phi4_flash import (  # noqa: F401
    Phi4Flash,
    Phi4FlashConfig,
    phi4_flash_param_shardings,
)
from tensorflowonspark_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNetConfig,
    resnet_param_shardings,
)
from tensorflowonspark_tpu.models.solar_open2 import (  # noqa: F401
    SolarOpen2,
    SolarOpen2Config,
    solar_open2_param_shardings,
)
from tensorflowonspark_tpu.models.unet import (  # noqa: F401
    UNet,
    UNetConfig,
    unet_param_shardings,
)
from tensorflowonspark_tpu.models.vgg import (  # noqa: F401
    VGG,
    VGGConfig,
    vgg_param_shardings,
)
from tensorflowonspark_tpu.models import zoo  # noqa: F401
