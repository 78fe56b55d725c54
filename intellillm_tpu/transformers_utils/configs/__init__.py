"""Config shims for trust-remote-code model families.

Role parity: reference `vllm/transformers_utils/configs/` (aquila,
baichuan, chatglm, falcon/RW, mpt, qwen, yi). These checkpoints ship
their config class via `auto_map` custom code; the shims let the engine
load them without executing remote code. Falcon/MPT need no shim here —
current transformers versions parse them natively.
"""
from intellillm_tpu.transformers_utils.configs.aquila import AquilaConfig
from intellillm_tpu.transformers_utils.configs.axk1 import AXK1Config
from intellillm_tpu.transformers_utils.configs.baichuan import BaichuanConfig
from intellillm_tpu.transformers_utils.configs.bailing_hybrid import (
    BailingHybridConfig)
from intellillm_tpu.transformers_utils.configs.chatglm import ChatGLMConfig
from intellillm_tpu.transformers_utils.configs.decilm import DeciLMConfig
from intellillm_tpu.transformers_utils.configs.deepseek import DeepseekConfig
from intellillm_tpu.transformers_utils.configs.falcon_h1 import (
    FalconH1Config)
from intellillm_tpu.transformers_utils.configs.granitemoehybrid import (
    GraniteMoeHybridConfig)
from intellillm_tpu.transformers_utils.configs.internlm import InternLMConfig
from intellillm_tpu.transformers_utils.configs.lfm2_moe import Lfm2MoeConfig
from intellillm_tpu.transformers_utils.configs.phi4flash import (
    Phi4FlashConfig)
from intellillm_tpu.transformers_utils.configs.qwen import QWenConfig
from intellillm_tpu.transformers_utils.configs.smallthinker import (
    SmallThinkerConfig)
from intellillm_tpu.transformers_utils.configs.solar_open2 import (
    SolarOpen2Config)
from intellillm_tpu.transformers_utils.configs.xing4 import Xing4Config
from intellillm_tpu.transformers_utils.configs.yi import YiConfig

_CONFIG_REGISTRY = {
    "aquila": AquilaConfig,
    "axk1": AXK1Config,
    "baichuan": BaichuanConfig,
    "bailing_hybrid": BailingHybridConfig,
    "chatglm": ChatGLMConfig,
    "deci": DeciLMConfig,
    "deepseek": DeepseekConfig,
    "falcon_h1": FalconH1Config,
    "granitemoehybrid": GraniteMoeHybridConfig,
    "internlm": InternLMConfig,
    "lfm2_moe": Lfm2MoeConfig,
    "phi4flash": Phi4FlashConfig,
    "qwen": QWenConfig,
    "smallthinker": SmallThinkerConfig,
    "solar_open2": SolarOpen2Config,
    "xing4_0": Xing4Config,
    "Yi": YiConfig,
    "yi": YiConfig,
}

__all__ = [
    "AquilaConfig", "AXK1Config", "BaichuanConfig", "BailingHybridConfig",
    "ChatGLMConfig", "DeciLMConfig",
    "DeepseekConfig", "FalconH1Config", "GraniteMoeHybridConfig", "InternLMConfig",
    "Lfm2MoeConfig", "Phi4FlashConfig", "QWenConfig",
    "SmallThinkerConfig", "SolarOpen2Config", "Xing4Config", "YiConfig",
    "_CONFIG_REGISTRY",
]
