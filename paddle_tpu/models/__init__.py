"""Flagship model definitions (Llama-family decoder for the BASELINE
configs; vision models live in paddle_tpu.vision.models)."""
from .deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM
from .dit import DiT, DiTConfig, dit_b_4, dit_xl_2
from .granite_hybrid import GraniteHybridConfig, GraniteHybridForCausalLM
from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel
from .qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "Qwen3NextConfig", "Qwen3NextForCausalLM",
    "GraniteHybridConfig", "GraniteHybridForCausalLM",
    "DeepseekV3Config", "DeepseekV3ForCausalLM",
    "DiT", "DiTConfig", "dit_xl_2", "dit_b_4",
]
