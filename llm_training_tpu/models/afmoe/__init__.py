from llm_training_tpu.models.afmoe.config import AfmoeConfig
from llm_training_tpu.models.afmoe.model import Afmoe

__all__ = ["Afmoe", "AfmoeConfig"]
