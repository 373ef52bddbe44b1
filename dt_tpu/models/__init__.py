"""Model zoo.

Coverage target (SURVEY.md §2.5/§2.6): the reference's
``example/image-classification/symbols/`` (lenet, mlp, alexnet, vgg, resnet,
inception-v3, googlenet, mobilenet) and ``python/mxnet/gluon/model_zoo/vision``
(resnet v1/v2, vgg±bn, alexnet, densenet, squeezenet, inception, mobilenet)
plus the RNN word-LM (``example/rnn/word_lm``).  All flax.linen, NHWC,
``dtype``-parametric (bf16 compute / f32 params for TPU).

``create(name, **kwargs)`` mirrors ``get_model`` /
``import_module(args.network)`` dispatch in the reference examples.
"""

from typing import Any, Callable, Dict

from dt_tpu.models.lenet import LeNet as LeNet
from dt_tpu.models.mlp import MLP as MLP
from dt_tpu.models.alexnet import AlexNet as AlexNet
from dt_tpu.models.vgg import VGG as VGG
from dt_tpu.models.resnet import ResNet as ResNet, CifarResNet as CifarResNet
from dt_tpu.models.inception import InceptionV3 as InceptionV3
from dt_tpu.models.mobilenet import MobileNetV1 as MobileNetV1, MobileNetV2 as MobileNetV2
from dt_tpu.models.densenet import DenseNet as DenseNet
from dt_tpu.models.squeezenet import SqueezeNet as SqueezeNet
from dt_tpu.models.googlenet import GoogLeNet as GoogLeNet
from dt_tpu.models.inception_v4 import (InceptionBN as InceptionBN,
                                        InceptionV4 as InceptionV4)
from dt_tpu.models.inception_resnet_v2 import (
    InceptionResNetV2 as InceptionResNetV2)
from dt_tpu.models.resnext import ResNeXt as ResNeXt
from dt_tpu.models.lstm_lm import LSTMLanguageModel as LSTMLanguageModel
from dt_tpu.models.transformer import TransformerLM as TransformerLM
from dt_tpu.models.transformer import (
    PipelinedTransformerLM as PipelinedTransformerLM)
from dt_tpu.models.hybrid_lm import HybridLM as HybridLM
from dt_tpu.models.routed_lm import RoutedLM as RoutedLM
from dt_tpu.models.pattern_lm import PatternLM as PatternLM
from dt_tpu.models.ssd import (SSD as SSD, ssd_loss as ssd_loss,
                               ssd_detect as ssd_detect)
from dt_tpu.models.rcnn import (FasterRCNNMini as FasterRCNNMini,
                                rcnn_loss as rcnn_loss,
                                rcnn_detect as rcnn_detect)

_REGISTRY: Dict[str, Callable[..., Any]] = {}


def register(name: str, factory: Callable[..., Any]):
    _REGISTRY[name] = factory
    return factory


def create(name: str, **kwargs):
    """Create a model by the reference's network names: lenet, mlp, alexnet,
    vgg11/13/16/19[_bn], resnet18/34/50/101/152[_v2], resnet20/56/110 (CIFAR),
    inception-v3, inception-bn, inception-v4, inception-resnet-v2, googlenet,
    resnext50/101/152, mobilenet[_v2], densenet121/161/169/201, squeezenet,
    lstm_lm, transformer_lm, hybrid_lm (state-space and attention layers by
    a pattern), routed_lm (rotary attention and routed experts, trained
    by diffusion over blocks or next-token, there also over the keys a
    learned index picks), pattern_lm (blocks of one part each by a pattern
    string: Mamba-2, attention, experts in a latent, each a held share)."""
    key = name.lower().replace("-", "_")
    if key in _REGISTRY:
        return _REGISTRY[key](**kwargs)
    raise ValueError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")


def _setup_registry():
    register("lenet", lambda **kw: LeNet(**kw))
    register("mlp", lambda **kw: MLP(**kw))
    register("alexnet", lambda **kw: AlexNet(**kw))
    for d in (11, 13, 16, 19):
        register(f"vgg{d}", lambda d=d, **kw: VGG(depth=d, batch_norm=False, **kw))
        register(f"vgg{d}_bn", lambda d=d, **kw: VGG(depth=d, batch_norm=True, **kw))
    for d in (18, 34, 50, 101, 152):
        register(f"resnet{d}", lambda d=d, **kw: ResNet(depth=d, version=1, **kw))
        register(f"resnet{d}_v2", lambda d=d, **kw: ResNet(depth=d, version=2, **kw))
    for d in (20, 56, 110):
        register(f"resnet{d}_cifar", lambda d=d, **kw: CifarResNet(depth=d, **kw))
        register(f"resnet{d}", lambda d=d, **kw: CifarResNet(depth=d, **kw))
    register("inception_v3", lambda **kw: InceptionV3(**kw))
    register("googlenet", lambda **kw: GoogLeNet(**kw))
    register("inception_bn", lambda **kw: InceptionBN(**kw))
    register("inception_v4", lambda **kw: InceptionV4(**kw))
    register("inception_resnet_v2", lambda **kw: InceptionResNetV2(**kw))
    for d in (50, 101, 152):
        register(f"resnext{d}", lambda d=d, **kw: ResNeXt(depth=d, **kw))
    register("mobilenet", lambda **kw: MobileNetV1(**kw))
    register("mobilenet_v2", lambda **kw: MobileNetV2(**kw))
    for d in (121, 161, 169, 201):
        register(f"densenet{d}", lambda d=d, **kw: DenseNet(depth=d, **kw))
    register("squeezenet", lambda **kw: SqueezeNet(**kw))
    register("lstm_lm", lambda **kw: LSTMLanguageModel(**kw))
    register("transformer_lm", lambda **kw: TransformerLM(**kw))
    register("transformer_lm_pipelined",
             lambda **kw: PipelinedTransformerLM(**kw))
    register("hybrid_lm", lambda **kw: HybridLM(**kw))
    register("routed_lm", lambda **kw: RoutedLM(**kw))
    register("pattern_lm", lambda **kw: PatternLM(**kw))
    register("ssd", lambda **kw: SSD(**kw))
    register("faster_rcnn", lambda **kw: FasterRCNNMini(**kw))


_setup_registry()
