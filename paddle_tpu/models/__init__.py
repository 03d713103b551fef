"""Model zoo (LLM family; vision models live in paddle_tpu.vision.models)."""

from .generation import beam_search, generate  # noqa: F401
from .gpt import (GPTConfig, GPTBlock, GPTModel, GPTForCausalLM,  # noqa: F401
                  gpt_tiny, gpt_small, gpt3_6_7b)
from .trainer import GPTHybridTrainer, GPTMoEHybridTrainer  # noqa: F401
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM,  # noqa: F401
                    LlamaAttention, LlamaMLP, LlamaDecoderLayer,
                    llama_shard_fn, llama_tiny, llama_7b)
from .ouro import (OuroConfig, OuroStack, OuroModel,  # noqa: F401
                   OuroForCausalLM, ouro_tiny)
from .jamba import (JambaConfig, JambaModel, JambaForCausalLM,  # noqa: F401
                    JambaAttention, JambaMambaMixer, JambaDecoderLayer,
                    jamba_tiny)
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3Model,  # noqa: F401
                          DeepseekV3ForCausalLM, DeepseekV3Attention,
                          DeepseekV3DecoderLayer, deepseek_v3_tiny)
from .gpt_moe import (GPTMoEConfig, GPTMoEForCausalLM,  # noqa: F401
                      gpt_moe_tiny)
from .bert import (BertConfig, BertModel, BertForMaskedLM,  # noqa: F401
                   BertForSequenceClassification, bert_tiny)
from .t5 import (T5Config, T5Model, T5ForConditionalGeneration,  # noqa: F401
                 t5_tiny)
