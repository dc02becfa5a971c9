"""Published architecture configs (import side-effect: registration)."""

from repro.configs.base import ArchConfig, CONFIGS, SHAPES, ShapeSpec, get_config

# Registration imports — one module per assigned architecture + the paper's own.
from repro.configs import (  # noqa: F401
    deepseek_v2_lite,
    granite_3_8b,
    mesh_paper,
    mistral_large_123b,
    olmoe_1b_7b,
    phi3_medium_14b,
    pixtral_12b,
    qwen2_7b,
    qwen2_moe_a27b,
    rwkv6_1b6,
    whisper_medium,
    zamba2_1b2,
)

ASSIGNED_ARCHS = (
    "olmoe-1b-7b",
    "qwen2-moe-a2.7b",
    "granite-3-8b",
    "phi3-medium-14b",
    "qwen2-7b",
    "mistral-large-123b",
    "rwkv6-1.6b",
    "whisper-medium",
    "zamba2-1.2b",
    "pixtral-12b",
)

__all__ = [
    "ArchConfig",
    "ShapeSpec",
    "SHAPES",
    "CONFIGS",
    "get_config",
    "ASSIGNED_ARCHS",
]
