"""The paper's own model families (§8.1): modified VGG-11 on CIFAR-10
(9,750,922 params) and modified ResNet-18 on FEMNIST (11,192,746 params),
and ViT-S/16 on CIFAR-10 at 224 px as federated learning runs it.

The benchmark's cells run these at their published widths. The CPU-scale
reduced variants (width_mult < 1, smaller images) serve the tests, the
examples, the CPU micro-benchmarks and the benchmark's CPU selftests.
"""
from repro.configs.base import CNNConfig, ViTConfig

PAPER_VGG11_CIFAR10 = CNNConfig(
    name="paper-vgg11-cifar10",
    arch="vgg",
    in_channels=3,
    image_size=32,
    num_classes=10,
    width_mult=1.0,
    source="paper §8.1 (modified VGG-11, 9.75M params, CIFAR-10)",
)

PAPER_RESNET18_FEMNIST = CNNConfig(
    name="paper-resnet18-femnist",
    arch="resnet",
    in_channels=1,
    image_size=28,
    num_classes=62,
    width_mult=1.0,
    source="paper §8.1 (modified ResNet-18, 11.19M params, FEMNIST)",
)

# ViT-S/16 (ViT: arXiv:2010.11929) at the DeiT-S widths (arXiv:2012.12877),
# federated on CIFAR-10 upsampled to 224 x 224 as Qu et al., "Rethinking
# Architecture Design for Tackling Data Heterogeneity in Federated
# Learning" (CVPR 2022, arXiv:2106.06047) run it: 197 tokens, d = 21,669,514
PAPER_VIT_S16_CIFAR10 = ViTConfig(
    name="vit-s16-cifar10",
    in_channels=3,
    image_size=32,
    resize_to=224,
    patch_size=16,
    hidden=384,
    heads=6,
    mlp_dim=1536,
    depth=12,
    num_classes=10,
    source="arXiv:2010.11929 (ViT), widths arXiv:2012.12877 (DeiT-S), "
           "federated CIFAR-10 at 224 px arXiv:2106.06047",
)

# CPU-scale stand-ins used by benchmarks (same families, reduced width).
BENCH_CNN_CIFAR = CNNConfig(
    name="bench-vgg-small", arch="vgg", in_channels=3, image_size=16,
    num_classes=10, width_mult=0.125,
    source="reduced VGG family for CPU-scale reproduction",
)
BENCH_CNN_FEMNIST = CNNConfig(
    name="bench-resnet-small", arch="resnet", in_channels=1, image_size=14,
    num_classes=62, width_mult=0.25,
    source="reduced ResNet family for CPU-scale reproduction",
)
BENCH_MLP = CNNConfig(
    name="bench-mlp", arch="mlp", in_channels=1, image_size=8,
    num_classes=10, width_mult=1.0,
    source="tiny MLP for fast benchmark sweeps",
)
BENCH_VIT_TINY = ViTConfig(
    name="bench-vit-tiny", in_channels=3, image_size=32, resize_to=64,
    patch_size=8, hidden=64, heads=2, mlp_dim=128, depth=2,
    num_classes=10, source="reduced ViT for CPU-scale tests",
)
