from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.inference.config import InferenceV2Config
from deepspeed_tpu.inference.v2.ragged_engine import (RaggedInferenceEngineV2,
                                                      Request)

__all__ = ["RaggedInferenceEngineV2", "Request", "InferenceV2Config",
           "HostStageStats"]
