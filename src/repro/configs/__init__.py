from .base import (ARCH_IDS, ARCHS, MLAConfig, ModelConfig, MoEConfig, RopeScaling,
                   all_archs, get)

__all__ = ["ARCH_IDS", "ARCHS", "MLAConfig", "ModelConfig", "MoEConfig", "RopeScaling",
           "all_archs", "get"]
