from .deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM  # noqa: F401
from .gpt2 import GPT2Config, GPT2LMHeadModel  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM  # noqa: F401
from .mixtral import MixtralConfig, MixtralForCausalLM  # noqa: F401
from .transformer import (TransformerConfig, TransformerForMaskedLM,  # noqa: F401
                          TransformerLMHeadModel)
from .zaya import ZayaConfig, ZayaForCausalLM  # noqa: F401
