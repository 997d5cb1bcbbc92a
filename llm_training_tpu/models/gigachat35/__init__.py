from llm_training_tpu.models.gigachat35.config import GigaChat35Config
from llm_training_tpu.models.gigachat35.model import GigaChat35

__all__ = ["GigaChat35", "GigaChat35Config"]
