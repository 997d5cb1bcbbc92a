"""Model layer.

Capability parity: reference `src/llm_training/models/` — `BaseModel`
(init_weights + parallelize hooks), `HFCompatModel` (HF config merge +
state-dict round-trip), and the concrete `Llama` / `Phi3` / `HFCausalLM`
families. Here, models are flax.linen Modules whose parameters carry
*logical axis* metadata; the TP/FSDP "plans" of the reference
(`llama_model.py:197-268`) are the logical→mesh rule table in
`llm_training_tpu.parallel.sharding`.
"""

from llm_training_tpu.models.afmoe import Afmoe, AfmoeConfig
from llm_training_tpu.models.bamba import Bamba, BambaConfig
from llm_training_tpu.models.base import BaseModelConfig, CausalLMOutput, RouterStats
from llm_training_tpu.models.deepseek import Deepseek, DeepseekConfig
from llm_training_tpu.models.ernie45_moe import Ernie45Moe, Ernie45MoeConfig
from llm_training_tpu.models.gemma import Gemma, GemmaConfig
from llm_training_tpu.models.gigachat35 import GigaChat35, GigaChat35Config
from llm_training_tpu.models.glm4_moe import Glm4Moe, Glm4MoeConfig
from llm_training_tpu.models.gpt_oss import GptOss, GptOssConfig
from llm_training_tpu.models.hf_causal_lm import HFCausalLM, HFCausalLMConfig
from llm_training_tpu.models.hunyuan_moe import HunYuanMoe, HunYuanMoeConfig
from llm_training_tpu.models.llama import Llama, LlamaConfig
from llm_training_tpu.models.longcat_flash import LongcatFlash, LongcatFlashConfig
from llm_training_tpu.models.minimax import MiniMax, MiniMaxConfig
from llm_training_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from llm_training_tpu.models.phi3 import Phi3, Phi3Config
from llm_training_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig
from llm_training_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
from llm_training_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config

__all__ = [
    "Afmoe",
    "AfmoeConfig",
    "Bamba",
    "BambaConfig",
    "BaseModelConfig",
    "CausalLMOutput",
    "RouterStats",
    "Deepseek",
    "DeepseekConfig",
    "Ernie45Moe",
    "Ernie45MoeConfig",
    "Gemma",
    "GemmaConfig",
    "GigaChat35",
    "GigaChat35Config",
    "Glm4Moe",
    "Glm4MoeConfig",
    "GptOss",
    "GptOssConfig",
    "HFCausalLM",
    "HFCausalLMConfig",
    "HunYuanMoe",
    "HunYuanMoeConfig",
    "Llama",
    "LlamaConfig",
    "LongcatFlash",
    "LongcatFlashConfig",
    "MiniMax",
    "MiniMaxConfig",
    "OlmoHybrid",
    "OlmoHybridConfig",
    "Phi3",
    "Phi3Config",
    "Phi4Flash",
    "Phi4FlashConfig",
    "Qwen3Next",
    "Qwen3NextConfig",
    "SolarOpen2",
    "SolarOpen2Config",
]
