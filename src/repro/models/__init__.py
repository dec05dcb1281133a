from repro.models.config import (MLAConfig, MoEConfig, ModelConfig,
                                 RWKVConfig, SSMConfig, YarnScaling,
                                 repeat_pattern)
from repro.models.model import Model

__all__ = ["MLAConfig", "MoEConfig", "ModelConfig", "RWKVConfig", "SSMConfig",
           "YarnScaling", "Model", "repeat_pattern"]
