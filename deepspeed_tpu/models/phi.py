"""Phi model family (phi-1 / phi-1.5 / phi-2).

Reference serves Phi through FastGen v2
(``inference/v2/model_implementations/phi/containers.py``): parallel
attention + MLP sharing one input LayerNorm (Falcon-style residual),
separate q/k/v/dense projections ALL with biases, PARTIAL rotary
(``partial_rotary_factor`` of each head's dims, 0.4 for phi-2), a
gelu_new MLP with biases, final LayerNorm, and an LM head WITH bias.

Attention reuses :class:`deepspeed_tpu.models.llama.LlamaAttention`
(the ``attention_bias`` / ``attention_out_bias`` /
``partial_rotary_factor`` knobs), so Phi decodes through the ragged v2
engine like the Llama family.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import LlamaAttention, LlamaConfig, _tp_kwargs


@dataclasses.dataclass(frozen=True)
class PhiConfig(LlamaConfig):
    layer_norm_eps: float = 1e-5
    attention_bias: bool = True
    attention_out_bias: bool = True
    partial_rotary_factor: float = 0.4


PRESETS = {
    "phi-1.5": dict(vocab_size=51200, hidden_size=2048,
                    intermediate_size=8192, num_hidden_layers=24,
                    num_attention_heads=32, num_key_value_heads=32,
                    max_position_embeddings=2048, rope_theta=10000.0,
                    partial_rotary_factor=0.5),
    "phi-2": dict(vocab_size=51200, hidden_size=2560,
                  intermediate_size=10240, num_hidden_layers=32,
                  num_attention_heads=32, num_key_value_heads=32,
                  max_position_embeddings=2048, rope_theta=10000.0,
                  partial_rotary_factor=0.4),
    "tinyphi": dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=64,
                    partial_rotary_factor=0.5),
}


def get_config(preset: str, **overrides) -> PhiConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    kw.setdefault("dtype", jnp.bfloat16)
    return PhiConfig(**kw)


class PhiMLP(nn.Module):
    config: PhiConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = dict(use_bias=True, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)
        h = nn.Dense(cfg.intermediate_size, name="fc1", **dense,
                     **_tp_kwargs(cfg, "col"))(x)
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(
            cfg.dtype)
        return nn.Dense(cfg.hidden_size, name="fc2", **dense,
                        **_tp_kwargs(cfg, "row"))(h)


class PhiBlock(nn.Module):
    config: PhiConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        h = nn.LayerNorm(name="input_layernorm",
                         epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=jnp.float32)(x)
        attn = LlamaAttention(cfg, name="self_attn")(h, positions,
                                                     deterministic,
                                                     ragged_meta)
        # parallel residual: x + attn(ln(x)) + mlp(ln(x))
        return x + attn + PhiMLP(cfg, name="mlp")(h)


class ScanPhiBlock(nn.Module):
    config: PhiConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = PhiBlock(self.config, name="block")(x, positions,
                                                self.deterministic)
        return (x, positions), None


class PhiModel(nn.Module):
    config: PhiConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.inference.common import sampled_rows
        from deepspeed_tpu.models.gpt2 import _maybe_remat
        from deepspeed_tpu.parallel.tensor_parallel import tp_embed_kwargs

        cfg = self.config
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.arange(S)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens",
                     **tp_embed_kwargs(cfg.tensor_parallel))(input_ids)
        if cfg.scan_layers:
            block_cls = _maybe_remat(ScanPhiBlock, cfg)
            vaxes = {"params": 0}
            if cfg.decode:
                vaxes["cache"] = 0
            (x, _), _ = nn.scan(
                block_cls,
                variable_axes=vaxes,
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, deterministic, name="layers")((x, positions), None)
        else:
            block_cls = _maybe_remat(PhiBlock, cfg)
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, name=f"layers_{i}")(x, positions,
                                                       deterministic,
                                                       ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return nn.LayerNorm(name="final_layernorm",
                            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                            param_dtype=jnp.float32)(x)


class PhiForCausalLM(nn.Module):
    config: PhiConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = PhiModel(cfg, name="model")(input_ids, positions,
                                        deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=True, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_tp_kwargs(cfg, "col"))(x)


class PhiLMLoss(nn.Module):
    """``module(batch) -> scalar`` next-token CE (engine contract)."""

    config: PhiConfig

    @nn.compact
    def __call__(self, batch):
        from deepspeed_tpu.models.gpt2 import next_token_loss

        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logits = PhiForCausalLM(self.config, name="lm")(input_ids)
        return next_token_loss(logits, input_ids)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: PhiConfig, seq_len: Optional[int] = None) -> float:
    E, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    Dh, H = cfg.head_dim, cfg.num_attention_heads
    per_layer = 4 * E * H * Dh + 2 * E * I
    n = L * per_layer + cfg.vocab_size * E
    s = seq_len or cfg.max_position_embeddings
    attn = 12 * L * H * Dh * s
    return 6.0 * n + attn
