"""Architecture configs: 10 assigned + 3 paper GPT-2 sizes."""

from repro.configs.base import (
    ARCH_IDS,
    INPUT_SHAPES,
    NANO,
    PAPER_ARCH_IDS,
    InputShape,
    ModelConfig,
    TopologyConfig,
    arch_supports_shape,
    load_arch,
)
