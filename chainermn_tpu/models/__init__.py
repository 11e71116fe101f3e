from chainermn_tpu.models.afmoe import AfmoeConfig, AfmoeMoE
from chainermn_tpu.models.alexnet import AlexNet
from chainermn_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config
from chainermn_tpu.models.googlenet import GoogLeNet, GoogLeNetBN
from chainermn_tpu.models.lfm2 import LFM2Config, LFM2MoE
from chainermn_tpu.models.mellum import MellumConfig, MellumMoE
from chainermn_tpu.models.mlp import MLP
from chainermn_tpu.models.nin import NIN
from chainermn_tpu.models.resnet import (
    REMAT_POLICIES,
    BasicBlock,
    BottleneckBlock,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.models.vgg import VGG, VGG16
from chainermn_tpu.models.vit import ViT, ViT_B16, ViT_S16

__all__ = [
    "TransformerLM",
    "LFM2Config",
    "LFM2MoE",
    "AfmoeConfig",
    "AfmoeMoE",
    "MellumConfig",
    "MellumMoE",
    "DeepseekV3Config",
    "DeepseekV3",
    "MLP",
    "AlexNet",
    "NIN",
    "GoogLeNet",
    "GoogLeNetBN",
    "REMAT_POLICIES",
    "BasicBlock",
    "BottleneckBlock",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "VGG",
    "VGG16",
    "ViT",
    "ViT_S16",
    "ViT_B16",
]
