from .llama import (
    generate_sample,
    LLAMA3_1B,
    LLAMA3_8B,
    LLAMA_DEBUG,
    LlamaConfig,
    flops_per_token,
    forward,
    generate_greedy,
    init_params,
    loss_fn,
)

from . import deepseek_v3, granite_moe_hybrid, mixtral, vit
from .paged import PagedEngine
from .speculative import generate_speculative
from .mixtral import (
    MIXTRAL_8X7B,
    MIXTRAL_DEBUG,
    MixtralConfig,
    mixtral_shardings,
)
from .mixtral import generate_greedy as mixtral_generate_greedy
from .deepseek_v3 import DeepseekV3Config
from .granite_moe_hybrid import GraniteMoeHybridConfig

__all__ = [
    "LlamaConfig", "LLAMA3_8B", "LLAMA3_1B", "LLAMA_DEBUG", "init_params",
    "forward", "loss_fn", "generate_greedy", "generate_sample", "flops_per_token",
    "mixtral", "MixtralConfig", "MIXTRAL_8X7B", "MIXTRAL_DEBUG",
    "generate_speculative", "PagedEngine",
    "mixtral_shardings", "mixtral_generate_greedy",
    "granite_moe_hybrid", "GraniteMoeHybridConfig",
    "deepseek_v3", "DeepseekV3Config",
]
