from repro.configs.base import (
    ArchConfig,
    EncoderConfig,
    InputShape,
    LayerSpec,
    MLAConfig,
    MoEConfig,
    RoutingConfig,
    SHAPES,
    SSMConfig,
    YarnConfig,
)
from repro.configs.registry import ARCHS, applicable_shapes, get_arch, get_shape

__all__ = [
    "ArchConfig",
    "EncoderConfig",
    "InputShape",
    "LayerSpec",
    "MLAConfig",
    "MoEConfig",
    "RoutingConfig",
    "SHAPES",
    "SSMConfig",
    "YarnConfig",
    "ARCHS",
    "applicable_shapes",
    "get_arch",
    "get_shape",
]
