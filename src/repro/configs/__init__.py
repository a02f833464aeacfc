"""Architecture configs: one module per assigned arch + shared registry."""
from .base import (LONG_500K, DECODE_32K, PREFILL_32K, SHAPES, TRAIN_4K,
                   AudioConfig, MLAConfig, ModelConfig, MoEConfig,
                   RopeScaling, RunConfig, ShapeConfig, SSMConfig,
                   VisionConfig, reduced)
from .registry import ARCHS, get

__all__ = ["ARCHS", "get", "ModelConfig", "ShapeConfig", "RunConfig",
           "MoEConfig", "SSMConfig", "VisionConfig", "AudioConfig",
           "MLAConfig", "RopeScaling",
           "SHAPES", "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
           "reduced"]
