"""paddle_tpu.models — reference model families, TPU-first.

The flagship pretrain path (llama.py) is functional JAX: params are a pytree,
the train step is one jitted SPMD program over the hybrid mesh. Eager
``nn.Layer`` wrappers exist for the vision models (lenet.py, resnet.py),
mirroring the reference's python/paddle/vision/models/.
"""
import importlib

from . import llama
from . import qwen2_moe
from .llama import LlamaConfig
from .qwen2_moe import Qwen2MoeConfig
from .lenet import LeNet
from .vgg import VGG, vgg11, vgg13, vgg16, vgg19
from .alexnet import AlexNet, alexnet
from .squeezenet import SqueezeNet, squeezenet1_0, squeezenet1_1
from .mobilenet import (MobileNetV1, MobileNetV2, MobileNetV3Small,
                        MobileNetV3Large, mobilenet_v1, mobilenet_v2,
                        mobilenet_v3_small, mobilenet_v3_large)
from .densenet import (DenseNet, densenet121, densenet161, densenet169,
                       densenet201, densenet264)
from .shufflenetv2 import (ShuffleNetV2, shufflenet_v2_x0_25,
                           shufflenet_v2_x0_33, shufflenet_v2_x0_5,
                           shufflenet_v2_x1_0, shufflenet_v2_x1_5,
                           shufflenet_v2_x2_0, shufflenet_v2_swish)
from .googlenet import GoogLeNet, googlenet
from .inceptionv3 import InceptionV3, inception_v3
from .ernie import (ErnieConfig, ErnieModel, ErnieForSequenceClassification,
                    ErnieForPretraining)


# A serving family is a module that exposes ONE record, ``SERVING``
# (``models/layer_walk.py: ServingFamily``: its layer walk, its cache
# and what the engine may do with it); the tick around the walk is
# ``models/serving_tick.py``'s, for every family. Family (module) name
# -> its config class; this is the one place a name becomes a module.
SERVING_FAMILIES = {"llama": "LlamaConfig",
                    "qwen2_moe": "Qwen2MoeConfig",
                    "lfm2_moe": "Lfm2MoeConfig",
                    "granite_hybrid": "GraniteHybridConfig",
                    "longcat_flash": "LongcatFlashConfig",
                    "mimo_v2_flash": "MimoV2FlashConfig"}


def resolve_family(model, cfg=None):
    """The serving family's module: ``model`` is a module-like object
    (returned as it is), a family name, or None (then the family whose
    config class ``cfg`` is an instance of, by name)."""
    if model is not None and not isinstance(model, str):
        return model
    name = model or type(cfg).__name__
    for family, cfg_cls in SERVING_FAMILIES.items():
        if name in (family, cfg_cls):
            return importlib.import_module(f"{__name__}.{family}")
    raise ValueError(
        f"cannot infer serving model from {name!r}; pass one of "
        f"{sorted(SERVING_FAMILIES)} or a module exposing SERVING "
        "(a models.layer_walk.ServingFamily)")
