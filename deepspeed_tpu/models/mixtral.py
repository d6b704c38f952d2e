"""Mixtral model family (sparse-MoE Llama).

BASELINE.md target #5 (Mixtral 8x7B expert-parallel MoE + ZeRO-3).  Reuses
the Llama attention/norm/RoPE stack (models/llama.py) and swaps the dense
MLP for the routed :class:`deepspeed_tpu.moe.MoE` layer; per-layer aux
losses thread through the scan carry and the LM-loss wrapper folds them
into the objective with ``router_aux_loss_coef`` (the reference collects
``l_aux`` off each ``MoE`` layer instead — ``sharded_moe.py:533``,
engine-side aggregation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import (LlamaAttention, LlamaConfig, RMSNorm,
                                        _tp_kwargs)
from deepspeed_tpu.moe.layer import MoE


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02
    capacity_factor: float = 1.25
    min_capacity: int = 4
    drop_tokens: bool = True
    expert_parallel: bool = True
    # "auto" resolves per-topology: sorted (grouped-GEMM-style gathers)
    # when experts are device-local, einsum (GSPMD all-to-all) on a >1-way
    # expert mesh axis — see moe/layer.py dispatch_impl
    dispatch_impl: str = "auto"


PRESETS = {
    "mixtral-8x7b": dict(hidden_size=4096, intermediate_size=14336,
                         num_hidden_layers=32, num_attention_heads=32,
                         num_key_value_heads=8, vocab_size=32000,
                         num_local_experts=8, num_experts_per_tok=2,
                         rope_theta=1e6, max_position_embeddings=32768),
    "tinymixtral": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, max_position_embeddings=64,
                        num_local_experts=4, num_experts_per_tok=2),
}


def get_config(preset: str, **overrides) -> MixtralConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return MixtralConfig(**kw)


def live_rows(ragged_meta) -> Optional[jax.Array]:
    """Which rows of a serving engine's token batch are tokens (padding of
    a fused tick and inactive decode slots are not); None outside one."""
    return None if ragged_meta is None else ragged_meta.get("live")


def moe_layer(cfg: MixtralConfig, name: str, **overrides) -> MoE:
    """The routed FFN of an expert family's block.  A serving config
    (``decode`` or ``paged_decode``) is DROPLESS: a served token's logits
    may not depend on who shares its tick, so no capacity applies there."""
    kw = dict(hidden_size=cfg.hidden_size,
              num_experts=cfg.num_local_experts,
              intermediate_size=cfg.intermediate_size,
              k=cfg.num_experts_per_tok,
              capacity_factor=cfg.capacity_factor,
              min_capacity=cfg.min_capacity,
              drop_tokens=cfg.drop_tokens,
              activation="swiglu",
              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
              expert_parallel=cfg.expert_parallel,
              tensor_parallel=cfg.tensor_parallel,
              dispatch_impl=("grouped" if cfg.decode or cfg.paged_decode
                             else cfg.dispatch_impl))
    kw.update(overrides)
    return MoE(name=name, **kw)


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x)
        x = x + LlamaAttention(cfg, name="self_attn")(h, positions,
                                                      deterministic,
                                                      ragged_meta)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm")(x)
        y, l_aux = moe_layer(cfg, "block_sparse_moe")(
            h, live=live_rows(ragged_meta))
        return x + y, l_aux


class ScanMixtralBlock(nn.Module):
    config: MixtralConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, positions, aux = carry
        x, l_aux = MixtralBlock(self.config, name="block")(
            x, positions, self.deterministic)
        return (x, positions, aux + l_aux), None


class MixtralModel(nn.Module):
    """Returns (hidden_states, mean-per-layer aux loss)."""

    config: MixtralConfig

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
        aux0 = jnp.asarray(0.0, jnp.float32)

        if cfg.scan_layers:
            block_cls = _maybe_remat(ScanMixtralBlock, cfg)
            vaxes = {"params": 0}
            if cfg.decode:
                vaxes["cache"] = 0         # per-layer KV buffers, stacked
            (x, _, aux), _ = nn.scan(
                block_cls,
                variable_axes=vaxes,
                split_rngs={"params": True, "dropout": True, "gating": True},
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, deterministic, name="layers")((x, positions, aux0), None)
        else:
            aux = aux0
            for i in range(cfg.num_hidden_layers):
                x, l_aux = _maybe_remat(MixtralBlock, cfg)(
                    cfg, name=f"layers_{i}")(x, positions, deterministic,
                                             ragged_meta)
                aux = aux + l_aux
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return x, aux / cfg.num_hidden_layers


class MixtralForCausalLM(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x, aux = MixtralModel(cfg, name="model")(input_ids, positions,
                                                 deterministic, ragged_meta)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="lm_head",
                          **_tp_kwargs(cfg, "col"))(x)
        return logits, aux


class MixtralLMLoss(nn.Module):
    """``module(batch) -> scalar``: next-token CE + router aux loss."""

    config: MixtralConfig

    @nn.compact
    def __call__(self, batch):
        from deepspeed_tpu.models.gpt2 import next_token_loss

        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logits, aux = MixtralForCausalLM(self.config, name="lm")(input_ids)
        return (next_token_loss(logits, input_ids) +
                self.config.router_aux_loss_coef * aux)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: MixtralConfig,
                    seq_len: Optional[int] = None) -> float:
    """Fwd+bwd FLOPs/token counting only ACTIVE params (top-k experts)."""
    E, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    Dh, H, Hkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    per_layer = (E * H * Dh + 2 * E * Hkv * Dh + H * Dh * E
                 + cfg.num_experts_per_tok * 3 * E * I)
    n = L * per_layer + cfg.vocab_size * E
    s = seq_len or cfg.max_position_embeddings
    attn = 12 * L * H * Dh * s
    return 6.0 * n + attn
