from llm_training_tpu.models.longcat_flash.config import LongcatFlashConfig
from llm_training_tpu.models.longcat_flash.model import LongcatFlash

__all__ = ["LongcatFlash", "LongcatFlashConfig"]
