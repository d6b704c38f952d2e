"""GPT-J model family (EleutherAI 6B lineage).

Reference injects GPT-J through its v1 policy + v2 container
(``module_inject/containers/gptj.py``, FastGen
``inference/v2/model_implementations``): parallel residual — one
``ln_1`` feeds both attention and MLP, whose outputs add into the
residual together — PARTIAL rotary embeddings (``rotary_dim`` of each
256-wide head, 64 for 6B), bias-free attention projections, a biased
GELU MLP (``fc_in``/``fc_out``), and a biased ``lm_head``.

Attention reuses :class:`deepspeed_tpu.models.llama.LlamaAttention`
(``partial_rotary_factor`` covers ``rotary_dim``), so GPT-J trains and
serves through every Llama-family path.  GPT-J checkpoints use the
INTERLEAVED (rotate-every-two) rotary layout; the HF loader permutes the
q/k projection rows of the rotary block into the half (NeoX) layout this
module computes — the attention scores are permutation-invariant, so
logits match exactly (``module_inject/hf_loader.py:_convert_gptj``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import LlamaAttention, LlamaConfig, _tp_kwargs


@dataclasses.dataclass(frozen=True)
class GPTJConfig(LlamaConfig):
    layer_norm_epsilon: float = 1e-5
    rotary_dim: int = 64


PRESETS = {
    "gptj-6b": dict(vocab_size=50400, hidden_size=4096,
                    intermediate_size=16384, num_hidden_layers=28,
                    num_attention_heads=16, num_key_value_heads=16,
                    max_position_embeddings=2048, rotary_dim=64),
    "tinygptj": dict(vocab_size=96, hidden_size=32, intermediate_size=128,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, max_position_embeddings=64,
                     rotary_dim=4),
}


def get_config(preset: str, **overrides) -> GPTJConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    kw.setdefault("dtype", jnp.bfloat16)
    head_dim = kw["hidden_size"] // kw["num_attention_heads"]
    kw.setdefault("partial_rotary_factor", kw["rotary_dim"] / head_dim)
    return GPTJConfig(**kw)


class GPTJMLP(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = dict(use_bias=True, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)
        h = nn.Dense(cfg.intermediate_size, name="fc_in", **dense,
                     **_tp_kwargs(cfg, "col"))(x)
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(
            cfg.dtype)
        return nn.Dense(cfg.hidden_size, name="fc_out", **dense,
                        **_tp_kwargs(cfg, "row"))(h)


class GPTJBlock(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        h = nn.LayerNorm(name="ln_1", epsilon=cfg.layer_norm_epsilon,
                         dtype=cfg.dtype, param_dtype=jnp.float32)(x)
        attn = LlamaAttention(cfg, name="attn")(h, positions,
                                                deterministic, ragged_meta)
        # parallel residual: x + attn(ln(x)) + mlp(ln(x))
        return x + attn + GPTJMLP(cfg, name="mlp")(h)


class ScanGPTJBlock(nn.Module):
    config: GPTJConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = GPTJBlock(self.config, name="block")(x, positions,
                                                 self.deterministic)
        return (x, positions), None


class GPTJModel(nn.Module):
    config: GPTJConfig

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
                     param_dtype=cfg.param_dtype, name="wte",
                     **tp_embed_kwargs(cfg.tensor_parallel))(input_ids)
        if cfg.scan_layers:
            block_cls = _maybe_remat(ScanGPTJBlock, cfg)
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
            block_cls = _maybe_remat(GPTJBlock, cfg)
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, name=f"h_{i}")(x, positions,
                                                  deterministic,
                                                  ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return nn.LayerNorm(name="ln_f", epsilon=cfg.layer_norm_epsilon,
                            dtype=cfg.dtype, param_dtype=jnp.float32)(x)


class GPTJForCausalLM(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = GPTJModel(cfg, name="transformer")(input_ids, positions,
                                               deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=True, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_tp_kwargs(cfg, "col"))(x)


class GPTJLMLoss(nn.Module):
    """``module(batch) -> scalar`` next-token CE (engine contract)."""

    config: GPTJConfig

    @nn.compact
    def __call__(self, batch):
        from deepspeed_tpu.models.gpt2 import next_token_loss

        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logits = GPTJForCausalLM(self.config, name="lm")(input_ids)
        return next_token_loss(logits, input_ids)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: GPTJConfig,
                    seq_len: Optional[int] = None) -> float:
    E, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    Dh, H = cfg.head_dim, cfg.num_attention_heads
    per_layer = 4 * E * H * Dh + 2 * E * I
    n = L * per_layer + 2 * cfg.vocab_size * E
    s = seq_len or cfg.max_position_embeddings
    attn = 12 * L * H * Dh * s
    return 6.0 * n + attn
