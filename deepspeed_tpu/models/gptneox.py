"""GPT-NeoX model family (EleutherAI 20B / Pythia lineage).

Reference injects GPT-NeoX through its v1 policy
(``module_inject/containers/gptneox.py`` GPTNEOXLayerPolicy: fused
``query_key_value`` attention, Megatron-style TP split) — the last
member of the reference's gptj/gptneox parallel-residual class.  The
architecture: twin LayerNorms per block feeding attention and MLP
separately with ONE shared residual stream when
``use_parallel_residual`` (the 20B/Pythia default; sequential residuals
otherwise), partial HALF-LAYOUT rotary (``rotary_pct`` of each head —
natively our layout, no load-time permutation needed, unlike GPT-J's
interleaved checkpoints), biases everywhere, untied ``embed_out``.

Attention reuses :class:`deepspeed_tpu.models.llama.LlamaAttention`
(``attention_bias`` + ``attention_out_bias`` + ``partial_rotary_factor``
cover the NeoX shape), so GPT-NeoX trains and serves through every
Llama-family path: engine, v1 inference, AutoTP, ZeRO.
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
class GPTNeoXConfig(LlamaConfig):
    layer_norm_eps: float = 1e-5
    rotary_pct: float = 0.25
    use_parallel_residual: bool = True


PRESETS = {
    "gpt-neox-20b": dict(vocab_size=50432, hidden_size=6144,
                         intermediate_size=24576, num_hidden_layers=44,
                         num_attention_heads=64, num_key_value_heads=64,
                         max_position_embeddings=2048, rotary_pct=0.25),
    "pythia-1.4b": dict(vocab_size=50304, hidden_size=2048,
                        intermediate_size=8192, num_hidden_layers=24,
                        num_attention_heads=16, num_key_value_heads=16,
                        max_position_embeddings=2048, rotary_pct=0.25),
    "pythia-6.9b": dict(vocab_size=50432, hidden_size=4096,
                        intermediate_size=16384, num_hidden_layers=32,
                        num_attention_heads=32, num_key_value_heads=32,
                        max_position_embeddings=2048, rotary_pct=0.25),
    "tinyneox": dict(vocab_size=96, hidden_size=32, intermediate_size=128,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, max_position_embeddings=64,
                     rotary_pct=0.25),
}


def get_config(preset: str, **overrides) -> GPTNeoXConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    kw.setdefault("dtype", jnp.bfloat16)
    kw.setdefault("attention_bias", True)
    kw.setdefault("attention_out_bias", True)
    kw.setdefault("partial_rotary_factor", kw.get("rotary_pct", 0.25))
    return GPTNeoXConfig(**kw)


class GPTNeoXMLP(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = dict(use_bias=True, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)
        h = nn.Dense(cfg.intermediate_size, name="dense_h_to_4h", **dense,
                     **_tp_kwargs(cfg, "col"))(x)
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=False).astype(
            cfg.dtype)
        return nn.Dense(cfg.hidden_size, name="dense_4h_to_h", **dense,
                        **_tp_kwargs(cfg, "row"))(h)


class GPTNeoXBlock(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        ln = dict(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                  param_dtype=jnp.float32)
        attn = LlamaAttention(cfg, name="attention")(
            nn.LayerNorm(name="input_layernorm", **ln)(x), positions,
            deterministic, ragged_meta)
        if cfg.use_parallel_residual:
            # x + attn(ln1(x)) + mlp(ln2(x)) — the 20B/Pythia layout
            mlp = GPTNeoXMLP(cfg, name="mlp")(
                nn.LayerNorm(name="post_attention_layernorm", **ln)(x))
            return x + attn + mlp
        h = x + attn
        return h + GPTNeoXMLP(cfg, name="mlp")(
            nn.LayerNorm(name="post_attention_layernorm", **ln)(h))


class ScanGPTNeoXBlock(nn.Module):
    config: GPTNeoXConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = GPTNeoXBlock(self.config, name="block")(x, positions,
                                                    self.deterministic)
        return (x, positions), None


class GPTNeoXModel(nn.Module):
    config: GPTNeoXConfig

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
                     param_dtype=cfg.param_dtype, name="embed_in",
                     **tp_embed_kwargs(cfg.tensor_parallel))(input_ids)
        if cfg.scan_layers:
            block_cls = _maybe_remat(ScanGPTNeoXBlock, cfg)
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
            block_cls = _maybe_remat(GPTNeoXBlock, cfg)
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, name=f"layers_{i}")(x, positions,
                                                       deterministic,
                                                       ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return nn.LayerNorm(name="final_layer_norm",
                            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                            param_dtype=jnp.float32)(x)


class GPTNeoXForCausalLM(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = GPTNeoXModel(cfg, name="gpt_neox")(input_ids, positions,
                                               deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="embed_out",
                        **_tp_kwargs(cfg, "col"))(x)


class GPTNeoXLMLoss(nn.Module):
    """``module(batch) -> scalar`` next-token CE (engine contract)."""

    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, batch):
        from deepspeed_tpu.models.gpt2 import next_token_loss

        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logits = GPTNeoXForCausalLM(self.config, name="lm")(input_ids)
        return next_token_loss(logits, input_ids)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: GPTNeoXConfig,
                    seq_len: Optional[int] = None) -> float:
    E, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    Dh, H = cfg.head_dim, cfg.num_attention_heads
    per_layer = 4 * E * H * Dh + 2 * E * I
    n = L * per_layer + 2 * cfg.vocab_size * E
    s = seq_len or cfg.max_position_embeddings
    attn = 12 * L * H * Dh * s
    return 6.0 * n + attn
