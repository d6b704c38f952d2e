"""Llama model family (flax, TPU-first).

The framework's flagship dense LLM (BASELINE.md north star: Llama-2-7B
ZeRO-3 at >=45% MFU on v5p-128).  Architecture follows the Llama-2/-3
lineage the reference serves through its inference model registry
(``deepspeed/inference/v2/model_implementations/llama_v2``) and its AutoTP
policies (``module_inject/auto_tp.py``): RMSNorm, rotary position
embeddings, grouped-query attention, SwiGLU MLP, untied LM head.

TPU-first choices mirror models/gpt2.py: ``nn.scan`` over blocks (O(1)
compile depth; one layer's params live at a time under ZeRO-3), ``nn.remat``
activation checkpointing, bf16 matmuls on the MXU, the Pallas flash
attention kernel, and Megatron TP via flax partitioning metadata
(q/k/v/gate/up column-parallel, o/down row-parallel — the same
classification the reference's AutoTP applies by name).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_position_embeddings: int = 4096
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32          # < heads => GQA
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = True
    remat_policy: str = "full"             # see gpt2.remat_policy_fn
    use_flash_attention: bool = True
    tensor_parallel: bool = False
    # sequence parallelism: "none", "ulysses" (all-to-all), "ring" (ppermute)
    sequence_parallel: str = "none"
    pipeline_stages: int = 1               # see gpt2.GPT2Config
    pipeline_microbatches: int = 0
    # inference: thread a KV cache through attention (flax "cache"
    # collection); max_cache_len=0 -> max_position_embeddings
    decode: bool = False
    # ragged/continuous-batching decode (FastGen v2): per-sequence [B, S]
    # positions drive cache write offsets; explicit opt-in — shared slots
    # at different lengths make position-derived writes load-bearing
    ragged_decode: bool = False
    max_cache_len: int = 0
    # paged/blocked KV (FastGen v2 blocked_allocator + ragged kernels):
    # the KV cache is [num_pages, page_size, 2*Hkv, Dh] pages addressed by
    # a per-sequence page table; attention is the vLLM-TPU ragged paged
    # kernel over ONE fused token batch mixing decode tokens and prefill
    # chunks.  Requires scan_layers=False (the fused step threads dynamic
    # metadata the scan carry cannot) and a `ragged_meta` call kwarg.
    paged_decode: bool = False
    kv_page_size: int = 64
    kv_num_pages: int = 0                  # 0 -> engine must set it
    # paged KV pool storage format: "none" (model dtype), "fp8" (e4m3) or
    # "int8" — per-(row, head) scales, dequantized transiently at
    # attention (reference fp_quantizer KV configs)
    kv_cache_dtype: str = "none"
    # family knobs shared with Mistral/Qwen2 (both are Llama-shaped):
    # qkv-projection biases (Qwen2) and sliding-window attention
    # (Mistral) — None disables the window
    attention_bias: bool = False
    sliding_window: Optional[int] = None
    # Phi family: bias on the attention out-projection, and rotary over
    # only the first partial_rotary_factor * head_dim dims (phi-2: 0.4)
    attention_out_bias: bool = False
    partial_rotary_factor: float = 1.0
    # OLMoE: a learned RMSNorm over the WHOLE q and the whole k projection
    # (not per head), before the head split and RoPE
    qk_norm: bool = False
    # LFM2: with ``qk_norm``, the RMSNorm is over EACH HEAD's values (one
    # scale of ``head_dim`` shared by the heads), after the head split
    qk_norm_per_head: bool = False
    # serving: "w8a8" makes every Dense consume per-channel int8 kernels
    # natively (dynamic activation quant + int8 MXU dot) — set by the
    # inference engines when quantize_weights engages, never for training
    weight_quant: str = "none"
    # how attention's and the MLP's matrices are drawn; None is flax's own
    # (lecun normal, drawn IN ``param_dtype``).  models/ouro.py sets it
    kernel_init: Optional[Callable] = None

    def __post_init__(self):
        assert self.sequence_parallel in ("none", "ulysses", "ring"), (
            f"sequence_parallel={self.sequence_parallel!r}: expected 'none', "
            "'ulysses' or 'ring'")
        if self.decode:
            assert self.sequence_parallel == "none", (
                "decode mode does not compose with sequence parallelism")
            assert self.pipeline_stages <= 1, (
                "decode mode does not compose with pipeline parallelism")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


PRESETS = {
    "llama2-7b": dict(hidden_size=4096, intermediate_size=11008,
                      num_hidden_layers=32, num_attention_heads=32,
                      num_key_value_heads=32),
    "llama2-13b": dict(hidden_size=5120, intermediate_size=13824,
                       num_hidden_layers=40, num_attention_heads=40,
                       num_key_value_heads=40),
    "llama2-70b": dict(hidden_size=8192, intermediate_size=28672,
                       num_hidden_layers=80, num_attention_heads=64,
                       num_key_value_heads=8),
    "llama3-8b": dict(vocab_size=128256, hidden_size=4096,
                      intermediate_size=14336, num_hidden_layers=32,
                      num_attention_heads=32, num_key_value_heads=8,
                      rope_theta=500000.0, max_position_embeddings=8192),
    # TinyLlama-1.1B shape: the single-chip stand-in for the 7B bench
    "llama-1b": dict(hidden_size=2048, intermediate_size=5632,
                     num_hidden_layers=22, num_attention_heads=32,
                     num_key_value_heads=4),
    "tinyllama": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64),
}


def get_config(preset: str, **overrides) -> LlamaConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return LlamaConfig(**kw)


def _tp_kwargs(cfg: LlamaConfig, kind: str):
    from deepspeed_tpu.parallel.tensor_parallel import tp_dense_kwargs

    return tp_dense_kwargs(cfg.tensor_parallel, kind)


def _init_kwargs(cfg: LlamaConfig):
    return {} if cfg.kernel_init is None else {"kernel_init": cfg.kernel_init}


def _wq_kwargs(cfg: LlamaConfig):
    from deepspeed_tpu.inference.quantization import \
        weight_quant_dense_kwargs

    return weight_quant_dense_kwargs(getattr(cfg, "weight_quant", "none"))


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    scale_init: float = 1.0          # the first value of every scale

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.constant(self.scale_init),
                           (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(self.dtype)


def rotary_embedding(x: jax.Array, positions: jax.Array,
                     theta: float) -> jax.Array:
    """Apply RoPE.  x: [B, H, S, D] (D even); positions: [S] or [B, S]."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float32) / D))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [...,S,D/2]
    if angles.ndim == 2:             # [S, D/2] -> broadcast over B, H
        angles = angles[None, None]
    else:                            # [B, S, D/2] -> broadcast over H
        angles = angles[:, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        B, S, E = x.shape
        H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_wq_kwargs(cfg),
                     **_init_kwargs(cfg))
        # Qwen2: biases on q/k/v only, never on o_proj
        qkv = dict(dense, use_bias=cfg.attention_bias)
        q = nn.Dense(H * Dh, name="q_proj", **qkv,
                     **_tp_kwargs(cfg, "col"))(x)
        k = nn.Dense(Hkv * Dh, name="k_proj", **qkv,
                     **_tp_kwargs(cfg, "col"))(x)
        v = nn.Dense(Hkv * Dh, name="v_proj", **qkv,
                     **_tp_kwargs(cfg, "col"))(x)
        per_head = cfg.qk_norm and cfg.qk_norm_per_head
        if cfg.qk_norm and not per_head:
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)

        q = q.reshape(B, S, H, Dh)
        if per_head:
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        q = q.transpose(0, 2, 1, 3)
        k = k.reshape(B, S, Hkv, Dh)
        if per_head:
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        k = k.transpose(0, 2, 1, 3)
        v = v.reshape(B, S, Hkv, Dh).transpose(0, 2, 1, 3)
        rot = int(Dh * cfg.partial_rotary_factor)
        if rot >= Dh:
            q = rotary_embedding(q, positions, cfg.rope_theta)
            k = rotary_embedding(k, positions, cfg.rope_theta)
        else:
            # partial rotary (Phi family): rope the first `rot` dims,
            # pass the rest through untouched
            q = jnp.concatenate(
                [rotary_embedding(q[..., :rot], positions, cfg.rope_theta),
                 q[..., rot:]], axis=-1)
            k = jnp.concatenate(
                [rotary_embedding(k[..., :rot], positions, cfg.rope_theta),
                 k[..., rot:]], axis=-1)

        if cfg.paged_decode:
            # blocked-KV continuous batching: one fused token batch over
            # the paged cache (reference ragged_ops kernels + blocked
            # allocator) — see inference/paged/kv_pages.py
            from deepspeed_tpu.inference.paged import paged_update_and_attend

            assert ragged_meta is not None, (
                "paged_decode models require the engine's ragged_meta")
            assert B == 1, "paged token batches are [1, T]"
            y = paged_update_and_attend(self, q, k, v, ragged_meta, cfg)
            y = y.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
            return nn.Dense(E, name="o_proj",
                            **dict(dense, use_bias=cfg.attention_out_bias),
                            **_tp_kwargs(cfg, "row"))(y)

        if cfg.decode:
            from deepspeed_tpu.inference.kv_cache import (cached_attention,
                                                          update_kv_cache)

            max_len = cfg.max_cache_len or cfg.max_position_embeddings
            # ragged path (FastGen v2 continuous batching, explicit
            # config opt-in): rows write at their own [B, S] position
            # offsets and every call — decode step or chunked-prefill
            # chunk — attends to the cache under the positions mask
            ragged = cfg.ragged_decode
            if ragged:
                assert (positions is not None and positions.ndim == 2 and
                        positions.shape[0] == B), (
                    "ragged_decode requires per-sequence [B, S] positions")
            wp = positions[:, 0] if ragged else None
            k_full, v_full, _ = update_kv_cache(self, k, v, max_len,
                                                write_positions=wp)
            if S == 1 or ragged:
                y = cached_attention(q, k_full, v_full, positions,
                                     window=cfg.sliding_window)
                y = y.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
                return nn.Dense(E, name="o_proj",
                                **dict(dense,
                                       use_bias=cfg.attention_out_bias),
                                **_tp_kwargs(cfg, "row"))(y)
            # full-prefill: cache written above; attend within the chunk

        window = cfg.sliding_window
        if window is not None and S > window and \
                cfg.sequence_parallel != "none":
            # the SP paths all-to-all/ring over the FULL sequence; the
            # local-window mask below would silently attend within shards
            raise NotImplementedError(
                "sliding-window attention does not compose with sequence "
                "parallelism yet — raise sliding_window above the "
                "sequence length or disable sequence_parallel")
        if window is not None and S > window:
            # Mistral sliding window binds: causal AND within-window mask
            # via the reference kernel (the flash kernel has no window
            # support; window-bound shapes are rare in training)
            from deepspeed_tpu.ops.flash_attention import mha_reference

            pos = jnp.arange(S)
            keep = (pos[None, :] <= pos[:, None]) & \
                   (pos[None, :] > pos[:, None] - window)
            bias = jnp.where(keep, 0.0, -1e30)[None, None]
            y = mha_reference(q, k, v, causal=False, bias=bias)
        elif cfg.sequence_parallel == "ulysses":
            from deepspeed_tpu.sequence import ulysses_attention

            y = ulysses_attention(q, k, v, causal=True)
        elif cfg.sequence_parallel == "ring":
            from deepspeed_tpu.sequence import ring_attention

            y = ring_attention(q, k, v, causal=True)
        elif cfg.use_flash_attention:
            from deepspeed_tpu.ops.flash_attention import flash_attention

            y = flash_attention(q, k, v, causal=True)
        else:
            from deepspeed_tpu.ops.flash_attention import mha_reference

            y = mha_reference(q, k, v, causal=True)
        y = y.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
        return nn.Dense(E, name="o_proj",
                        **dict(dense, use_bias=cfg.attention_out_bias),
                        **_tp_kwargs(cfg, "row"))(y)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.runtime.zero import pin_activation

        cfg = self.config
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_wq_kwargs(cfg),
                     **_init_kwargs(cfg))
        gate = nn.Dense(cfg.intermediate_size, name="gate_proj", **dense,
                        **_tp_kwargs(cfg, "col"))(x)
        up = nn.Dense(cfg.intermediate_size, name="up_proj", **dense,
                      **_tp_kwargs(cfg, "col"))(x)
        # the widest activation stays on its batch shard: under ZeRO-3 its
        # trailing dim is the one the weights are split over, and left alone
        # the partitioner ran the backward tensor-parallel over ``data``
        return nn.Dense(cfg.hidden_size, name="down_proj", **dense,
                        **_tp_kwargs(cfg, "row"))(
                            pin_activation(nn.silu(gate) * up))


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.runtime.zero import pin_activation

        cfg = self.config
        # the residual stream enters every block on its batch shard
        x = pin_activation(x)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x)
        x = x + LlamaAttention(cfg, name="self_attn")(h, positions,
                                                      deterministic,
                                                      ragged_meta)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm")(x)
        return x + LlamaMLP(cfg, name="mlp")(h)


class PipeLlamaBlock(nn.Module):
    """GPipe block adapter: ``(x, positions) -> x``."""

    config: LlamaConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x, positions):
        return LlamaBlock(self.config, name="block")(x, positions,
                                                     self.deterministic)


class ScanLlamaBlock(nn.Module):
    config: LlamaConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = LlamaBlock(self.config, name="block")(x, positions,
                                                  self.deterministic)
        return (x, positions), None


class LlamaModel(nn.Module):
    config: LlamaConfig
    # every matmul kernel in this module tree consumes w8a8
    # QuantizedWeight leaves natively (see _wq_kwargs) — serving engines
    # key the int8-MXU path off this class flag.  ClassVar keeps flax's
    # dataclass transform from turning it into a constructor field
    w8a8_native: ClassVar[bool] = True

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.arange(S)
        if cfg.paged_decode:
            assert not cfg.scan_layers and cfg.pipeline_stages == 1, (
                "paged_decode requires unrolled layers (the fused step "
                "threads dynamic ragged metadata the scan carry cannot)")
        from deepspeed_tpu.inference.common import sampled_rows
        from deepspeed_tpu.parallel.tensor_parallel import tp_embed_kwargs

        embed_kwargs = tp_embed_kwargs(cfg.tensor_parallel)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens",
                     **embed_kwargs)(input_ids)

        from deepspeed_tpu.models.gpt2 import _maybe_remat

        if cfg.pipeline_stages > 1:
            from deepspeed_tpu.parallel.pipeline import GPipe

            x = GPipe(
                PipeLlamaBlock, (cfg, deterministic),
                n_layer=cfg.num_hidden_layers,
                n_stages=cfg.pipeline_stages,
                n_micro=cfg.pipeline_microbatches or cfg.pipeline_stages,
                remat_policy=cfg.remat_policy if cfg.remat else "none",
                name="layers")(x, positions)
        elif cfg.scan_layers:
            block_cls = _maybe_remat(ScanLlamaBlock, cfg)
            vaxes = {"params": 0}
            if cfg.decode:
                vaxes["cache"] = 0         # per-layer KV buffers, stacked
            (x, _), _ = nn.scan(
                block_cls,
                variable_axes=vaxes,
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, deterministic, name="layers")((x, positions), None)
        else:
            block_cls = _maybe_remat(LlamaBlock, cfg)
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, name=f"layers_{i}")(x, positions,
                                                       deterministic,
                                                       ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class LlamaForCausalLM(nn.Module):
    config: LlamaConfig
    # class flag, not a dataclass field (see LlamaModel)
    w8a8_native: ClassVar[bool] = True

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = LlamaModel(cfg, name="model")(input_ids, positions, deterministic,
                                          ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_tp_kwargs(cfg, "col"), **_wq_kwargs(cfg))(x)


class LlamaLMLoss(nn.Module):
    """Loss-returning wrapper matching the engine's flax-module contract:
    ``module(batch) -> scalar`` next-token cross entropy in fp32."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, batch):
        from deepspeed_tpu.models.gpt2 import next_token_loss

        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logits = LlamaForCausalLM(self.config, name="lm")(input_ids)
        return next_token_loss(logits, input_ids)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: LlamaConfig, seq_len: Optional[int] = None) -> float:
    """Fwd+bwd FLOPs/token (PaLM convention), for MFU."""
    E, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    Dh, H, Hkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    per_layer = (E * H * Dh + 2 * E * Hkv * Dh + H * Dh * E  # qkvo
                 + 3 * E * I)                                # gate/up/down
    n = L * per_layer + cfg.vocab_size * E                   # + lm head
    s = seq_len or cfg.max_position_embeddings
    attn = 12 * L * H * Dh * s
    return 6.0 * n + attn
