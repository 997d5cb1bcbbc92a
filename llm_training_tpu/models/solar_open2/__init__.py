from llm_training_tpu.models.solar_open2.config import SolarOpen2Config
from llm_training_tpu.models.solar_open2.model import SolarOpen2

__all__ = ["SolarOpen2", "SolarOpen2Config"]
