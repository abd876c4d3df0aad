"""Flagship model families (GPT / LLaMA / Jamba / Laguna / ZAYA1 /
Qwen3-Next / Ouro / DeepSeek-V2 / Mellum2 / BERT).

The reference keeps language models out-of-tree (PaddleNLP) but its
north-star benchmarks are GPT-3/LLaMA hybrid-parallel training
(BASELINE.json configs 2-4); vision models live in paddle.vision.models.
Here the LM families are first-class so the framework's parallelism and
benchmarks are self-contained.
"""
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, GPTPretrainingCriterion,
    gpt_tiny, gpt2_small, gpt3_1p3b, gpt3_6p7b,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaModel, LlamaForCausalLM, llama_tiny, llama2_7b,
    llama2_13b,
)
from .jamba import (  # noqa: F401
    JambaConfig, JambaModel, JambaForCausalLM, JambaMambaMixer,
    JambaAttention, JambaMLP, JambaDecoderLayer, jamba_tiny,
)
from .laguna import (  # noqa: F401
    LagunaConfig, LagunaModel, LagunaForCausalLM, LagunaAttention,
    LagunaDecoderLayer, laguna_tiny, observe_expert_load,
)
from .zaya import (  # noqa: F401
    ZayaConfig, ZayaModel, ZayaForCausalLM, ZayaDecoderLayer, zaya_tiny,
)
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig, Qwen3NextModel, Qwen3NextForCausalLM,
    Qwen3NextAttention, Qwen3NextDecoderLayer, qwen3_next_tiny,
)
from .ouro import (  # noqa: F401
    OuroConfig, OuroModel, OuroForCausalLM, OuroAttention,
    OuroDecoderLayer, OuroPretrainingCriterion, exit_distribution,
    ouro_tiny,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForMaskedLM, bert_tiny, bert_base,
)
from .generation import generate  # noqa: F401


_MELLUM2 = ("Mellum2Config", "Mellum2ForCausalLM", "mellum2_tiny")
_DEEPSEEK_V2 = ("DeepseekV2Config", "DeepseekV2Model", "DeepseekV2ForCausalLM",
                "DeepseekV2DecoderLayer", "DeepseekV2PretrainingCriterion",
                "deepseek_v2_tiny")


def __getattr__(name):
    """`models.deepseek_v2`'s and `models.mellum2`'s names, imported when
    first asked for: a program that builds another family pays nothing
    for these."""
    if name in _MELLUM2:
        from . import mellum2
        return getattr(mellum2, name)
    if name in _DEEPSEEK_V2:
        from . import deepseek_v2
        return getattr(deepseek_v2, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
