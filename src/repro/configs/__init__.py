from .base import (
    ARCH_IDS,
    LayerSpec,
    ModelConfig,
    get_config,
    list_configs,
    register,
)

__all__ = [
    "ARCH_IDS", "LayerSpec", "ModelConfig", "get_config", "list_configs", "register",
]
