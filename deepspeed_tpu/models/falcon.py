"""Falcon model family (7B lineage: parallel attention + MLP, MQA).

Reference serves Falcon through FastGen v2
(``inference/v2/model_implementations/falcon/container.py``): fused
``query_key_value`` (q heads, then k, then v — split on load like the
reference's FusedQKVParameter), rotary embeddings, multi-query attention
(``num_kv_heads=1``; the 40B+ lineage's GQA is the same knob), a GELU
MLP, and the 7B architecture's PARALLEL residual: one input LayerNorm
feeds both attention and MLP, whose outputs add into the residual
together.

Attention reuses :class:`deepspeed_tpu.models.llama.LlamaAttention`
verbatim — rotary + GQA + the flash / cached / paged ragged decode paths
are architecture-independent — so Falcon decodes through the ragged v2
engine like the Llama family.  The loader handles the 7B contiguous qkv
layout and the ``new_decoder_architecture`` (40B+) per-kv-group
interleave, and rejects the falcon-rw lineage's per-head interleave
loudly.
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
class FalconConfig(LlamaConfig):
    # falcon uses LayerNorm (with bias), GELU MLP at 4*hidden, and the
    # parallel-residual block; num_key_value_heads=1 is the 7B MQA
    layer_norm_epsilon: float = 1e-5
    parallel_attn: bool = True
    new_decoder_architecture: bool = False   # 40B+: separate mlp LN


PRESETS = {
    "falcon-7b": dict(vocab_size=65024, hidden_size=4544,
                      intermediate_size=4 * 4544, num_hidden_layers=32,
                      num_attention_heads=71, num_key_value_heads=1,
                      max_position_embeddings=2048, rope_theta=10000.0),
    "falcon-40b": dict(vocab_size=65024, hidden_size=8192,
                       intermediate_size=4 * 8192, num_hidden_layers=60,
                       num_attention_heads=128, num_key_value_heads=8,
                       max_position_embeddings=2048, rope_theta=10000.0,
                       new_decoder_architecture=True),
    "tinyfalcon": dict(vocab_size=96, hidden_size=32,
                       intermediate_size=128, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=1,
                       max_position_embeddings=64),
}


def get_config(preset: str, **overrides) -> FalconConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    kw.setdefault("dtype", jnp.bfloat16)
    return FalconConfig(**kw)


class FalconMLP(nn.Module):
    config: FalconConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)
        h = nn.Dense(cfg.intermediate_size, name="dense_h_to_4h", **dense,
                     **_tp_kwargs(cfg, "col"))(x)
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=False).astype(
            cfg.dtype)
        return nn.Dense(cfg.hidden_size, name="dense_4h_to_h", **dense,
                        **_tp_kwargs(cfg, "row"))(h)


class FalconBlock(nn.Module):
    config: FalconConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        ln = dict(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                  param_dtype=jnp.float32)
        h_attn = nn.LayerNorm(name="input_layernorm", **ln)(x)
        if cfg.new_decoder_architecture:
            h_mlp = nn.LayerNorm(name="ln_mlp", **ln)(x)
        else:
            h_mlp = h_attn
        attn = LlamaAttention(cfg, name="self_attention")(
            h_attn, positions, deterministic, ragged_meta)
        if cfg.parallel_attn:
            # 7B parallel residual: x + attn(ln(x)) + mlp(ln(x))
            return x + attn + FalconMLP(cfg, name="mlp")(h_mlp)
        x = x + attn
        h = nn.LayerNorm(name="post_attention_layernorm", **ln)(x)
        return x + FalconMLP(cfg, name="mlp")(h)


class ScanFalconBlock(nn.Module):
    config: FalconConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = FalconBlock(self.config, name="block")(x, positions,
                                                   self.deterministic)
        return (x, positions), None


class FalconModel(nn.Module):
    config: FalconConfig

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
                     param_dtype=cfg.param_dtype, name="word_embeddings",
                     **tp_embed_kwargs(cfg.tensor_parallel))(input_ids)
        if cfg.scan_layers:
            block_cls = _maybe_remat(ScanFalconBlock, cfg)
            vaxes = {"params": 0}
            if cfg.decode:
                vaxes["cache"] = 0
            (x, _), _ = nn.scan(
                block_cls,
                variable_axes=vaxes,
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, deterministic, name="h")((x, positions), None)
        else:
            block_cls = _maybe_remat(FalconBlock, cfg)
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, name=f"h_{i}")(x, positions,
                                                  deterministic,
                                                  ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return nn.LayerNorm(name="ln_f", epsilon=cfg.layer_norm_epsilon,
                            dtype=cfg.dtype, param_dtype=jnp.float32)(x)


class FalconForCausalLM(nn.Module):
    config: FalconConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = FalconModel(cfg, name="transformer")(input_ids, positions,
                                                 deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_tp_kwargs(cfg, "col"))(x)


class FalconLMLoss(nn.Module):
    """``module(batch) -> scalar`` next-token CE (engine contract)."""

    config: FalconConfig

    @nn.compact
    def __call__(self, batch):
        from deepspeed_tpu.models.gpt2 import next_token_loss

        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logits = FalconForCausalLM(self.config, name="lm")(input_ids)
        return next_token_loss(logits, input_ids)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: FalconConfig,
                    seq_len: Optional[int] = None) -> float:
    E, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    Dh, H, Hkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    per_layer = (E * H * Dh + 2 * E * Hkv * Dh + H * Dh * E + 2 * E * I)
    n = L * per_layer + cfg.vocab_size * E
    s = seq_len or cfg.max_position_embeddings
    attn = 12 * L * H * Dh * s
    return 6.0 * n + attn
