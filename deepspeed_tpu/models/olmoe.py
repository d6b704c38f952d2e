"""OLMoE model family (allenai/OLMoE-1B-7B; arXiv:2409.02060).

Many small experts: 64 SwiGLU experts of width 1024, the 8 largest softmax
router values used as they are (``norm_topk_prob`` false), no shared
expert; multi-head attention with **QK-norm** — a learned RMSNorm over the
whole q and the whole k projection, before the head split and RoPE
(``LlamaConfig.qk_norm``).  Everything else is the Llama stack.

The block is :class:`~deepspeed_tpu.models.qwen2_moe.Qwen2MoeBlock` without
its shared expert and biases, so this file is a config and presets, as
``models/mistral.py`` is.  The config carries the SOURCE's key for the
expert count (``num_experts``); ``intermediate_size`` is one expert's width,
as HF's ``OlmoeMLP`` reads it.
"""
from __future__ import annotations

import dataclasses

from deepspeed_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                            Qwen2MoeForCausalLM,
                                            Qwen2MoeModel, count_params,
                                            flops_per_token)

__all__ = ["OlmoeConfig", "OlmoeModel", "OlmoeForCausalLM", "get_config",
           "count_params", "flops_per_token"]


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(Qwen2MoeConfig):
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    attention_bias: bool = False
    qk_norm: bool = True

    def __post_init__(self):
        super().__post_init__()
        # the shared block reads the Mixtral lineage's name
        object.__setattr__(self, "num_local_experts", self.num_experts)


PRESETS = {
    "olmoe-1b-7b": dict(vocab_size=50304, hidden_size=2048,
                        intermediate_size=1024, num_hidden_layers=16,
                        num_attention_heads=16, num_key_value_heads=16,
                        num_experts=64, num_experts_per_tok=8,
                        rope_theta=10000.0, rms_norm_eps=1e-5,
                        max_position_embeddings=4096),
    "tinyolmoe": dict(vocab_size=96, hidden_size=32, intermediate_size=16,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, num_experts=8,
                      num_experts_per_tok=2, max_position_embeddings=64),
}


def get_config(preset: str, **overrides) -> OlmoeConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return OlmoeConfig(**kw)


class OlmoeModel(Qwen2MoeModel):
    config: OlmoeConfig


class OlmoeForCausalLM(Qwen2MoeForCausalLM):
    config: OlmoeConfig
