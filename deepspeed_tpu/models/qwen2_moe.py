"""Qwen2-MoE model family (Qwen1.5-MoE / Qwen2-57B-A14B).

Reference serves this family through FastGen v2
(``inference/v2/model_implementations/qwen_v2_moe/model.py``,
``container.py``): Qwen2 attention (qkv biases) + a sparse MoE FFN whose
top-k gates are NOT renormalized (HF ``norm_topk_prob=False``) plus a
dense SHARED expert blended by a per-token sigmoid gate:

    y = moe(h) + sigmoid(shared_gate(h)) * shared_mlp(h)

TPU-first composition: attention/norms reuse ``models/llama.py`` (the
``attention_bias`` knob), the routed FFN is the
:class:`deepspeed_tpu.moe.MoE` layer (expert axis sharding, linear
all-to-all dispatch multi-chip), and the shared expert is a plain SwiGLU
MLP that stays dense on every rank — exactly the reference's
``shared_moe_*`` containers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import (LlamaAttention, LlamaMLP, RMSNorm,
                                        _tp_kwargs)
from deepspeed_tpu.models.mixtral import (MixtralConfig, live_rows,
                                          moe_layer)


@dataclasses.dataclass(frozen=True)
class Qwen2MoeConfig(MixtralConfig):
    # experts use their own (small) intermediate size; the shared expert
    # its own (large) one — HF Qwen2MoeConfig moe_intermediate_size /
    # shared_expert_intermediate_size
    moe_intermediate_size: int = 0          # 0 -> intermediate_size
    shared_expert_intermediate_size: int = 0  # 0 -> no shared expert
    norm_topk_prob: bool = False
    attention_bias: bool = True             # qkv biases (Qwen2 lineage)

    @property
    def expert_intermediate(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size


PRESETS = {
    # Qwen1.5-MoE-A2.7B
    "qwen1.5-moe-a2.7b": dict(
        vocab_size=151936, hidden_size=2048, intermediate_size=5632,
        moe_intermediate_size=1408, shared_expert_intermediate_size=5632,
        num_hidden_layers=24, num_attention_heads=16,
        num_key_value_heads=16, num_local_experts=60,
        num_experts_per_tok=4, rope_theta=1e6,
        max_position_embeddings=8192, rms_norm_eps=1e-6),
    # Qwen2-57B-A14B
    "qwen2-57b-a14b": dict(
        vocab_size=151936, hidden_size=3584, intermediate_size=18944,
        moe_intermediate_size=2560, shared_expert_intermediate_size=20480,
        num_hidden_layers=28, num_attention_heads=28,
        num_key_value_heads=4, num_local_experts=64,
        num_experts_per_tok=8, rope_theta=1e6,
        max_position_embeddings=32768, rms_norm_eps=1e-6),
    "tinyqwen2moe": dict(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=48, shared_expert_intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rms_norm_eps=1e-6),
}


def get_config(preset: str, **overrides) -> Qwen2MoeConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return Qwen2MoeConfig(**kw)


class Qwen2MoeBlock(nn.Module):
    config: Qwen2MoeConfig

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
        y, l_aux = moe_layer(
            cfg, "mlp", intermediate_size=cfg.expert_intermediate,
            normalize_weights=cfg.norm_topk_prob)(
                h, live=live_rows(ragged_meta))
        if cfg.shared_expert_intermediate_size:
            shared_cfg = dataclasses.replace(
                cfg, intermediate_size=cfg.shared_expert_intermediate_size)
            shared = LlamaMLP(shared_cfg, name="shared_expert")(h)
            gate = nn.Dense(1, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            name="shared_expert_gate")(h)
            y = y + jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                cfg.dtype) * shared
        return x + y, l_aux


class ScanQwen2MoeBlock(nn.Module):
    config: Qwen2MoeConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, positions, aux = carry
        x, l_aux = Qwen2MoeBlock(self.config, name="block")(
            x, positions, self.deterministic)
        return (x, positions, aux + l_aux), None


class Qwen2MoeModel(nn.Module):
    """Returns (hidden_states, mean-per-layer aux loss)."""

    config: Qwen2MoeConfig

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
            block_cls = _maybe_remat(ScanQwen2MoeBlock, cfg)
            vaxes = {"params": 0}
            if cfg.decode:
                vaxes["cache"] = 0
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
                x, l_aux = _maybe_remat(Qwen2MoeBlock, cfg)(
                    cfg, name=f"layers_{i}")(x, positions, deterministic,
                                             ragged_meta)
                aux = aux + l_aux
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return x, aux / cfg.num_hidden_layers


class Qwen2MoeForCausalLM(nn.Module):
    config: Qwen2MoeConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x, aux = Qwen2MoeModel(cfg, name="model")(input_ids, positions,
                                                  deterministic, ragged_meta)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="lm_head",
                          **_tp_kwargs(cfg, "col"))(x)
        return logits, aux


class Qwen2MoeLMLoss(nn.Module):
    """``module(batch) -> scalar``: next-token CE + router aux loss."""

    config: Qwen2MoeConfig

    @nn.compact
    def __call__(self, batch):
        from deepspeed_tpu.models.gpt2 import next_token_loss

        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logits, aux = Qwen2MoeForCausalLM(self.config, name="lm")(input_ids)
        return (next_token_loss(logits, input_ids) +
                self.config.router_aux_loss_coef * aux)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: Qwen2MoeConfig,
                    seq_len: Optional[int] = None) -> float:
    """Fwd+bwd FLOPs/token counting ACTIVE params (top-k experts + the
    always-on shared expert)."""
    E, L = cfg.hidden_size, cfg.num_hidden_layers
    Dh, H, Hkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    per_layer = (E * H * Dh + 2 * E * Hkv * Dh + H * Dh * E
                 + cfg.num_experts_per_tok * 3 * E * cfg.expert_intermediate
                 + 3 * E * cfg.shared_expert_intermediate_size + E)
    n = L * per_layer + cfg.vocab_size * E
    s = seq_len or cfg.max_position_embeddings
    attn = 12 * L * H * Dh * s
    return 6.0 * n + attn
