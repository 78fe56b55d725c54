"""Model registry: HF architecture string → model class.

Role parity: reference `vllm/model_executor/models/__init__.py:12-44`
(~25 architectures). Families land here as they are built; Llama covers
every config that uses the llama layer recipe (Mistral, Yi, InternLM...)
via HF config introspection.
"""
from typing import Dict, Type

from intellillm_tpu.models.axk1 import AXK1ForCausalLM
from intellillm_tpu.models.baichuan import (BaiChuanForCausalLM,
                                            BaichuanForCausalLM)
from intellillm_tpu.models.bailing_hybrid import BailingHybridForCausalLM
from intellillm_tpu.models.bloom import BloomForCausalLM
from intellillm_tpu.models.chatglm import ChatGLMForCausalLM
from intellillm_tpu.models.deepseek import DeepseekForCausalLM
from intellillm_tpu.models.falcon import FalconForCausalLM
from intellillm_tpu.models.falcon_h1 import FalconH1ForCausalLM
from intellillm_tpu.models.gpt2 import GPT2LMHeadModel
from intellillm_tpu.models.gpt_bigcode import GPTBigCodeForCausalLM
from intellillm_tpu.models.gpt_neox import GPTNeoXForCausalLM
from intellillm_tpu.models.gptj import GPTJForCausalLM
from intellillm_tpu.models.decilm import DeciLMForCausalLM
from intellillm_tpu.models.internlm import InternLMForCausalLM
from intellillm_tpu.models.lfm2_moe import Lfm2MoeForCausalLM
from intellillm_tpu.models.llama import LlamaForCausalLM
from intellillm_tpu.models.granitemoehybrid import (
    GraniteMoeHybridForCausalLM)
from intellillm_tpu.models.mixtral import MixtralForCausalLM
from intellillm_tpu.models.mpt import MPTForCausalLM
from intellillm_tpu.models.opt import OPTForCausalLM
from intellillm_tpu.models.phi import PhiForCausalLM
from intellillm_tpu.models.phi4flash import Phi4FlashForCausalLM
from intellillm_tpu.models.qwen import QWenLMHeadModel
from intellillm_tpu.models.qwen2 import Qwen2ForCausalLM
from intellillm_tpu.models.smallthinker import SmallThinkerForCausalLM
from intellillm_tpu.models.solar_open2 import SolarOpen2ForCausalLM
from intellillm_tpu.models.stablelm import StableLMForCausalLM
from intellillm_tpu.models.xing4 import Xing4ForCausalLM

_MODEL_REGISTRY: Dict[str, Type] = {
    "LlamaForCausalLM": LlamaForCausalLM,
    "LLaMAForCausalLM": LlamaForCausalLM,
    "MistralForCausalLM": LlamaForCausalLM,
    "YiForCausalLM": LlamaForCausalLM,
    "InternLMForCausalLM": InternLMForCausalLM,  # llama + q/k/v/o biases
    "DeciLMForCausalLM": DeciLMForCausalLM,      # variable GQA, degrouped

    "OPTForCausalLM": OPTForCausalLM,
    "GPT2LMHeadModel": GPT2LMHeadModel,
    "MixtralForCausalLM": MixtralForCausalLM,
    # Reference mixtral_quant.py arch name: GPTQ/AWQ checkpoints load as
    # per-expert packed-int4 stacks (models/mixtral.py load_weights E()).
    "QuantMixtralForCausalLM": MixtralForCausalLM,
    "Qwen2ForCausalLM": Qwen2ForCausalLM,
    "BloomForCausalLM": BloomForCausalLM,
    "GPTNeoXForCausalLM": GPTNeoXForCausalLM,
    "GPTJForCausalLM": GPTJForCausalLM,
    "PhiForCausalLM": PhiForCausalLM,
    "FalconForCausalLM": FalconForCausalLM,
    "RWForCausalLM": FalconForCausalLM,
    "GPTBigCodeForCausalLM": GPTBigCodeForCausalLM,
    "MPTForCausalLM": MPTForCausalLM,
    "MptForCausalLM": MPTForCausalLM,
    "StableLmForCausalLM": StableLMForCausalLM,
    "StableLMEpochForCausalLM": StableLMForCausalLM,
    "AquilaForCausalLM": LlamaForCausalLM,      # llama recipe + naming
    "AquilaModel": LlamaForCausalLM,
    "YiForCausalLM": LlamaForCausalLM,          # llama recipe + naming
    "BaiChuanForCausalLM": BaiChuanForCausalLM,  # 7B (rope)
    "BaichuanForCausalLM": BaichuanForCausalLM,  # 13B (ALiBi) / Baichuan2
    "QWenLMHeadModel": QWenLMHeadModel,
    "ChatGLMModel": ChatGLMForCausalLM,
    "ChatGLMForConditionalGeneration": ChatGLMForCausalLM,
    "DeepseekForCausalLM": DeepseekForCausalLM,
    "AXK1ForCausalLM": AXK1ForCausalLM,          # latent attention + EP share
    "Lfm2MoeForCausalLM": Lfm2MoeForCausalLM,    # short-conv state + GQA
    # window + full attention layers (two cache groups), ReGLU experts
    "SmallThinkerForCausalLM": SmallThinkerForCausalLM,
    # delta-rule linear attention (matrix state) + gated NoPE GQA, EP share
    "SolarOpen2ForCausalLM": SolarOpen2ForCausalLM,
    # Mamba-2 state-space scan (matrix state) + NoPE GQA, EP share
    "GraniteMoeHybridForCausalLM": GraniteMoeHybridForCausalLM,
    # latent attention under a four-stream hyper-connection residual, EP share
    "Xing4ForCausalLM": Xing4ForCausalLM,
    # a Mamba-2 scan (two groups of B and C) beside RoPE GQA in every layer
    "FalconH1ForCausalLM": FalconH1ForCausalLM,
    # Mamba-1 scans + window layers, then GMUs and cross layers over ONE
    # full-attention layer's pool; differential attention
    "Phi4FlashForCausalLM": Phi4FlashForCausalLM,
    # delta-rule linear attention (matrix state) + latent attention (a
    # latent pool) in one sequence, experts routed in groups, EP share
    "BailingHybridForCausalLM": BailingHybridForCausalLM,
}


def register_model(arch: str, cls: Type) -> None:
    _MODEL_REGISTRY[arch] = cls


def get_model_class(architectures) -> Type:
    for arch in architectures:
        if arch in _MODEL_REGISTRY:
            return _MODEL_REGISTRY[arch]
    raise ValueError(
        f"Model architectures {architectures} are not supported for now. "
        f"Supported architectures: {sorted(_MODEL_REGISTRY)}")
