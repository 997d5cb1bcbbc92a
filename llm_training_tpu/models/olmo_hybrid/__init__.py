from llm_training_tpu.models.olmo_hybrid.config import OlmoHybridConfig
from llm_training_tpu.models.olmo_hybrid.model import OlmoHybrid

__all__ = ["OlmoHybrid", "OlmoHybridConfig"]
