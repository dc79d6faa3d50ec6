"""Importing this package registers all architecture configs."""
from repro.configs.base import (  # noqa: F401
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    SHAPES,
    TRAIN_4K,
    ModelConfig,
    ShapeConfig,
    get_config,
    list_archs,
    shape_applicable,
)

# Register all architectures (import side effects).
from repro.configs import (  # noqa: F401
    chords_dit,
    gemma_7b,
    internlm2_1_8b,
    nemotron_3_nano,
    olmoe_1b_7b,
    qwen1_5_0_5b,
    qwen1_5_32b,
    qwen2_moe_a2_7b,
    qwen2_vl_7b,
    seamless_m4t_medium,
    xlstm_1_3b,
    zamba2_2_7b,
)

ASSIGNED_ARCHS = (
    "qwen1.5-0.5b",
    "qwen1.5-32b",
    "gemma-7b",
    "internlm2-1.8b",
    "zamba2-2.7b",
    "xlstm-1.3b",
    "seamless-m4t-medium",
    "qwen2-moe-a2.7b",
    "olmoe-1b-7b",
    "qwen2-vl-7b",
)
