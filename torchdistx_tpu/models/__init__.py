from .deepseek_v3 import DeepseekV3, DeepseekV3Config, deepseek_v3_configs
from .gpt2 import GPT2, GPT2Config, gpt2_configs
from .jamba import Jamba, JambaConfig, jamba_configs
from .llama import Llama, LlamaConfig, llama_configs
from .mixtral import Mixtral, MixtralConfig, mixtral_configs
from .qwen3_next import Qwen3Next, Qwen3NextConfig, qwen3_next_configs
from .resnet import ResNet, resnet18, resnet50, resnet101
from .t5 import T5, T5Config, t5_configs
from .vit import ViT, ViTConfig, vit_configs

__all__ = [
    "DeepseekV3",
    "DeepseekV3Config",
    "deepseek_v3_configs",
    "Jamba",
    "JambaConfig",
    "jamba_configs",
    "Llama",
    "LlamaConfig",
    "llama_configs",
    "Mixtral",
    "MixtralConfig",
    "mixtral_configs",
    "Qwen3Next",
    "Qwen3NextConfig",
    "qwen3_next_configs",
    "GPT2",
    "GPT2Config",
    "gpt2_configs",
    "ResNet",
    "resnet18",
    "resnet50",
    "resnet101",
    "T5",
    "T5Config",
    "t5_configs",
    "ViT",
    "ViTConfig",
    "vit_configs",
]
