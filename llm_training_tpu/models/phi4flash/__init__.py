from llm_training_tpu.models.phi4flash.config import Phi4FlashConfig
from llm_training_tpu.models.phi4flash.model import Phi4Flash

__all__ = ["Phi4Flash", "Phi4FlashConfig"]
