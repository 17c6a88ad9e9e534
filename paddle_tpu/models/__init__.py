from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, gpt_config,
    GPT2_124M, GPT2_350M, GPT3_1_3B, GPT3_6_7B, GPT3_13B,
)
from .mlp import MNISTMLP  # noqa: F401
from .gpt_parallel import (  # noqa: F401
    ParallelGPTForCausalLM, ParallelGPTModel, ParallelGPTBlock,
)
from .gpt_pipeline import GPTForCausalLMPipe  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, llama_config,
)
from .llama_parallel import (  # noqa: F401
    ParallelLlamaForCausalLM, ParallelLlamaModel,
)
