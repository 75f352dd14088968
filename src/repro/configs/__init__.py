from repro.configs.base import (ChannelConfig, CNNConfig,
                                CompressionSchedule, ModelConfig,
                                MoEConfig, PFELSConfig, SSMConfig, ViTConfig)
from repro.configs.registry import ARCHS, get_config, list_archs, reduced_config
from repro.configs.shapes import (DECODE_32K, LONG_500K, PREFILL_32K, SHAPES,
                                  TRAIN_4K, InputShape)

__all__ = [
    "ChannelConfig", "CNNConfig", "CompressionSchedule", "ModelConfig",
    "MoEConfig", "PFELSConfig", "ViTConfig",
    "SSMConfig", "ARCHS", "get_config", "list_archs", "reduced_config",
    "SHAPES", "InputShape", "TRAIN_4K", "PREFILL_32K", "DECODE_32K",
    "LONG_500K",
]
