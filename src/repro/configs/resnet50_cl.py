"""The paper's own models: ResNet-50 / ResNet-18 / GhostNet-style CNN classifiers.

These drive the faithful reproduction of the paper's Figs. 5-7 (class-incremental
ImageNet-1K, 4 tasks) at CPU scale: the benchmark harness trains reduced variants on a
synthetic class-incremental image stream with the paper's exact CL hyperparameters
(b=56, r=7, c=14, |B| as a % of the stream).
"""
from dataclasses import dataclass
from typing import Tuple

ARCH_ID = "resnet50-cl"


@dataclass(frozen=True)
class CNNConfig:
    name: str
    variant: str  # resnet18 | resnet50 | ghostnet
    num_classes: int = 1000
    width: int = 64
    stage_blocks: Tuple[int, ...] = (3, 4, 6, 3)
    bottleneck: bool = True
    image_size: int = 224
    channels: int = 3
    # cifar: one 3x3 stride-1 conv (32 px inputs keep their resolution);
    # imagenet: the published 7x7 stride-2 conv + 3x3 stride-2 max pool, so a
    # 224 px input enters the first stage at 56 px
    stem: str = "cifar"


def full() -> CNNConfig:
    return CNNConfig(name="resnet50-cl", variant="resnet50", stage_blocks=(3, 4, 6, 3),
                     bottleneck=True, stem="imagenet")


def resnet18() -> CNNConfig:
    return CNNConfig(name="resnet18-cl", variant="resnet18", stage_blocks=(2, 2, 2, 2),
                     bottleneck=False, stem="imagenet")


def ghostnet() -> CNNConfig:
    return CNNConfig(name="ghostnet50-cl", variant="ghostnet", stage_blocks=(2, 2, 4, 2),
                     bottleneck=False, stem="imagenet")


def reduced(num_classes: int = 40) -> CNNConfig:
    """Tiny ResNet for CPU CL experiments (32x32 synthetic images)."""
    return CNNConfig(name="resnet-tiny-cl", variant="resnet18", num_classes=num_classes,
                     width=16, stage_blocks=(1, 1, 1), bottleneck=False, image_size=32)
