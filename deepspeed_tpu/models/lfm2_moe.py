"""LFM2-MoE model family (LiquidAI LFM2-8B-A1B / LFM2-24B-A2B,
``model_type: lfm2_moe``).

A hybrid stack: ``layer_types`` says, layer by layer, whether the token
mixer is a **gated short convolution** (``"conv"``) or grouped-query
attention (``"full_attention"``); the first ``num_dense_layers`` layers
carry a dense SwiGLU MLP, every later one a routed expert layer.  With
``N`` RMSNorm and ``h`` the residual stream, layer ``l`` is::

    h <- h + Op_l(N_op(h));   h <- h + FF_l(N_ffn(h))

- **Convolution block** (``conv_L_cache`` 3 taps, no bias anywhere):
  ``[B, C, X] = W_in x`` (split in that order); ``u_t = B_t * X_t``;
  ``c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t`` (depthwise, causal, ``u``
  zero before the sequence starts); ``y_t = W_out (C_t * c_t)``.  No
  activation.  What a served sequence carries from tick to tick is
  ``(u_{t-2}, u_{t-1})``: per-slot state beside the paged K/V pool
  (``inference/paged/conv_state.py:ragged_short_conv``), owned by
  ``inference/v2/kv_state.py`` as the pages are.
- **Attention**: ``models/llama.py``'s, with an RMSNorm over EACH HEAD of
  q and k before RoPE (``qk_norm_per_head``); heads of 64 here, which the
  paged pool stores zero-padded to 128 (``inference/paged/kv_pages.py``).
- **Expert layer**: ``moe/layer.py``'s dropless form with the sigmoid
  router: ``s = sigmoid(W_g x)`` in float32, the chosen set the top k of
  ``s + b`` with ``b`` a per-expert selection bias (``use_expert_bias``),
  the weights ``s_i / (sum_chosen s + 1e-6)`` without the bias
  (``norm_topk_prob``), times ``routed_scaling_factor``; no shared expert.
- ``logits = E N_final(h)`` with ``E`` the embedding matrix
  (``tie_embedding``).

Only the serving form is here: the expert layer is dropless whatever the
call, and the dense decode cache is refused (it has nowhere to keep the
convolution's state; the paged engine does).  Attention, RoPE, RMSNorm and
the dense MLP are ``models/llama.py``'s, the expert layer is
``models/mixtral.py:moe_layer``.

**First values** (what ``init`` draws; a checkpoint brings its own): every
matrix lecun-normal, drawn in float32 and rounded to ``param_dtype``
(``models/ouro.py:in_float32``); the embedding at flax's own (std
``1/sqrt(hidden)``), so the tied head gives logits of standard deviation
1; the convolution's taps lecun-normal over their 3 inputs; every norm
scale 1.  Two are the family's own, found on the chip at the published
widths (PERF.md section 6, PR 35), because a model served AS DRAWN has to
be one whose check can tell a sound program from a wrong one:

- **the experts' down-projections start at** :data:`EXPERT_DOWN_INIT_SCALE`
  **(0.3) of lecun-normal's size.**  A renormalised top-4 gives a chosen
  expert a weight of a quarter, so a token whose fourth and fifth scores
  lie within bfloat16's noise of each other, and which the program routes
  otherwise than the float32 reference does, moves that layer's output by
  a large part of one expert's; eight layers of multiplicative gates
  after it amplify the difference.  At lecun's size the SOUND program
  then read up to 1.27 in logits of standard deviation 1 beside the e4m3
  reference's 1.2-2.5, and a program without the selection bias read no
  worse than the sound one.  At 0.3 a swapped expert is a fifteenth of a
  layer's update: the sound program reads under 0.1, e4m3 still over 1.
- **the selection bias is NOT zero** (HF starts it at zero, a checkpoint
  carries what load balancing left in it, and under a zero bias every
  bias mistake is invisible): it is drawn in float32 at standard
  deviation :data:`EXPERT_BIAS_INIT_STD` (0.03), at which it changes the
  chosen set of six routings in ten
  (``tests/unit/models/test_lfm2_moe.py`` measures the share).  At 0.01
  (a quarter of the routings) a program that leaves the bias out of the
  selection read 0.15-0.27, too near the sound program's own readings for
  a margin to stand between them; at 0.03 it reads 0.25-0.4.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import (LlamaAttention, LlamaMLP, RMSNorm,
                                        _init_kwargs, count_params)
from deepspeed_tpu.models.mixtral import (MixtralConfig, live_rows,
                                          moe_layer)
from deepspeed_tpu.models.ouro import in_float32

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM",
           "get_config", "count_params"]

# first values (see the module docstring)
EXPERT_BIAS_INIT_STD = 0.03
EXPERT_DOWN_INIT_SCALE = 0.3

CONV, ATTENTION = "conv", "full_attention"


class _FrozenDict(dict):
    """The source's ``rope_parameters`` object as a config field: a dict
    to read, and hashable, because flax hashes a module's fields."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(MixtralConfig):
    # the source's keys (``intermediate_size`` is the DENSE layers' width)
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    conv_L_cache: int = 3
    conv_bias: bool = False
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_parameters: Any = dataclasses.field(
        default_factory=lambda: _FrozenDict(rope_theta=1000000.0,
                                            rope_type="default"))
    # not in config.json: the family's dense siblings tie the head
    tie_embedding: bool = True
    # the stack is mixed: nothing to scan over; one chip holds it
    scan_layers: bool = False
    expert_parallel: bool = False
    kernel_init: Callable = in_float32(nn.initializers.lecun_normal())

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        put("layer_types", tuple(self.layer_types))
        put("rope_parameters", _FrozenDict(self.rope_parameters))
        assert len(self.layer_types) == self.num_hidden_layers, (
            f"layer_types names {len(self.layer_types)} layers, "
            f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        assert not unknown, f"unknown layer_types {sorted(unknown)}"
        rope_type = self.rope_parameters.get("rope_type", "default")
        if rope_type != "default":
            raise NotImplementedError(
                f"rope_parameters.rope_type={rope_type!r}: only the plain "
                "rotary embedding is implemented")
        if self.conv_bias:
            raise NotImplementedError(
                "conv_bias=True: the published LFM2 models have no bias in "
                "the convolution block, and neither has this one")
        assert not self.scan_layers, (
            "layers of two kinds and two feed-forwards do not scan")
        assert self.conv_L_cache >= 2, self.conv_L_cache
        # what the shared blocks read, under the names they read it by
        put("rms_norm_eps", float(self.norm_eps))
        put("rope_theta", float(self.rope_parameters["rope_theta"]))
        put("num_local_experts", int(self.num_experts))
        put("qk_norm", True)
        put("qk_norm_per_head", True)
        put("sliding_window", None)
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps keys and values only; a "
                "short-convolution layer's state lives per slot beside the "
                "paged pool — serve this model through "
                "RaggedInferenceEngineV2")


_LFM2_24B_TYPES = ((CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9
                   + (ATTENTION, CONV))

PRESETS = {
    "lfm2-24b-a2b": dict(
        vocab_size=65536, hidden_size=2048, intermediate_size=11776,
        moe_intermediate_size=1536, num_hidden_layers=40,
        layer_types=_LFM2_24B_TYPES, num_dense_layers=2,
        num_attention_heads=32, num_key_value_heads=8, num_experts=64,
        num_experts_per_tok=4, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1.0, norm_eps=1e-5, conv_L_cache=3,
        max_position_embeddings=128000),
    # heads of 64, so that it takes the padded route's twin on the CPU
    "tinylfm2": dict(
        vocab_size=96, hidden_size=128, intermediate_size=96,
        moe_intermediate_size=48, num_hidden_layers=6,
        layer_types=(CONV, CONV, ATTENTION, CONV, CONV, CONV),
        num_dense_layers=2, num_attention_heads=2, num_key_value_heads=1,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=128),
}


def get_config(preset: str, **overrides) -> Lfm2MoeConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return Lfm2MoeConfig(**kw)


def _taps_init(key, shape, dtype=jnp.float32):
    """``[channels, taps]``: lecun-normal over a channel's taps, drawn in
    float32."""
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(shape[1])).astype(dtype)


class Lfm2ShortConv(nn.Module):
    """The gated short convolution (the module docstring's equations)."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = x.shape
        K = cfg.conv_L_cache
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        gate_in, gate_out, xx = jnp.split(
            nn.Dense(3 * E, name="in_proj", **dense)(x), 3, axis=-1)
        u = gate_in * xx
        taps = self.param("conv_weight", _taps_init, (E, K),
                          cfg.param_dtype)
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import ragged_short_conv

            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
            conv = ragged_short_conv(self, u[0], taps,
                                     positions.reshape(-1), ragged_meta)[None]
        else:
            # whole sequences from their first token: nothing to carry
            t = taps.astype(jnp.float32)
            conv = sum(
                jnp.pad(u, ((0, 0), (d, 0), (0, 0)))[:, :S].astype(
                    jnp.float32) * t[:, K - 1 - d] for d in range(K))
            conv = conv.astype(u.dtype)
        return nn.Dense(E, name="out_proj", **dense)(gate_out * conv)


class Lfm2MoeBlock(nn.Module):
    config: Lfm2MoeConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg, i = self.config, self.layer_idx
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        h = norm("operator_norm")(x)
        if cfg.layer_types[i] == CONV:
            x = x + Lfm2ShortConv(cfg, name="conv")(h, positions,
                                                    ragged_meta)
        else:
            x = x + LlamaAttention(cfg, name="self_attn")(
                h, positions, deterministic, ragged_meta)
        h = norm("ffn_norm")(x)
        if i < cfg.num_dense_layers:
            return x + LlamaMLP(cfg, name="feed_forward")(h)
        y, _ = moe_layer(
            cfg, "feed_forward", intermediate_size=cfg.moe_intermediate_size,
            normalize_weights=cfg.norm_topk_prob, scoring="sigmoid",
            expert_bias=cfg.use_expert_bias,
            expert_bias_init_std=EXPERT_BIAS_INIT_STD,
            down_init_scale=EXPERT_DOWN_INIT_SCALE,
            routed_scaling_factor=cfg.routed_scaling_factor,
            dispatch_impl="grouped")(h, live=live_rows(ragged_meta))
        return x + y


class Lfm2MoeModel(nn.Module):
    """Hidden states after the final norm (of the rows a fused tick
    samples, where its metadata names them); with ``head``, the logits of
    the tied head (the embedding matrix is this module's)."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None, head: bool = False):
        from deepspeed_tpu.inference.common import sampled_rows

        cfg = self.config
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed_tokens",
                         embedding_init=in_float32(
                             nn.initializers.variance_scaling(
                                 1.0, "fan_in", "normal", out_axis=0)))
        x = embed(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = Lfm2MoeBlock(cfg, i, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return embed.attend(x) if head else x


class Lfm2MoeForCausalLM(nn.Module):
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = Lfm2MoeModel(cfg, name="model")(
            input_ids, positions, deterministic, ragged_meta,
            head=cfg.tie_embedding)
        if cfg.tie_embedding:
            return x
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_init_kwargs(cfg))(x)
