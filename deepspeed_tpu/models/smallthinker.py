"""SmallThinker model family (PowerInfer SmallThinker-21BA3B-Instruct,
``model_type: smallthinker``): WINDOW attention layers and FULL attention
layers in one model (``sliding_window_layout``; published: one full layer,
then three window layers, thirteen times), plain grouped-query attention
(published: 28 query heads over FOUR KV heads, a query group of seven),
rotary embedding where ``rope_layout`` reads 1 (published: the window layers
and no other), and in EVERY layer a softmax router that reads **the layer's
input itself, before the input norm and before attention**, over ReGLU
experts.

With ``N(x) = x * rsqrt(mean(x^2) + eps) * w`` (float32 inside) and ``h`` the
residual stream, ``h0 = E[token]``::

    g = W_r h                                  (float32; the router's input)
    a = h + W_o Attn_l(N1(h))
    y = a + sum_{j in top-k(g)} w_j W_down^j (relu(W_gate^j x) * W_up^j x),
        x = N2(a)
    logits = W_head N(h_L)

- **Attn_l(u)**: ``q = W_q u`` (``num_attention_heads`` of ``head_dim``), ``k,
  v = W_k u, W_v u`` (``num_key_value_heads``), no bias, no norm of q or k,
  no gate; where ``rope_layout[l]`` reads 1 the rotary embedding on q and k
  (``rope_theta``, every dimension, halves rotated); where
  ``sliding_window_layout[l]`` reads 1 a causal window, row ``p`` attends
  keys ``p - sliding_window_size + 1 .. p``, else every key ``<= p``; softmax
  at ``1 / sqrt(head_dim)`` in float32.  Served, a window layer's keys and
  values live in the window layers' OWN page pool behind their own page table
  (``inference/paged/window_pages.py``), a full layer's in the first pool
  (``kv_pages.py``); both are stored at the model's own KV head count.
- **The chosen** ``moe_num_active_primary_experts`` are the largest of ``g``
  (ties to the lower expert); their weights ``softmax(g)`` at the chosen over
  its sum there (``moe_primary_router_apply_softmax`` and ``norm_topk_prob``
  both true; either false is refused by name).  ``moe/layer.py``'s dropless
  form with ``activation="reglu"``, routed by one call BEFORE the layer's
  attention and dispatched by a second after it.  No shared expert, no
  scaling factor, no capacity: no token is ever dropped.

Only inference forms are here (no auxiliary loss).  **First values** (what
``init`` draws; a checkpoint brings its own): every matrix lecun-normal, drawn
in float32 and rounded to ``param_dtype``; norm scales 1; the embedding at
:data:`EMBED_INIT_STD` a value and the experts' down-projections at
:data:`EXPERT_DOWN_INIT_SCALE` of lecun-normal's size (``models/keye_vl2.py``'s
two, for its reasons).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import (RMSNorm, _init_kwargs, count_params,
                                        rotary_embedding)
from deepspeed_tpu.models.mixtral import (MixtralConfig, live_rows,
                                          moe_layer)
from deepspeed_tpu.models.ouro import in_float32

__all__ = ["SmallThinkerConfig", "SmallThinkerModel",
           "SmallThinkerForCausalLM", "get_config", "count_params"]

EMBED_INIT_STD = 1.0
EXPERT_DOWN_INIT_SCALE = 0.3
# what ``inference/paged`` reads a layer's kind by
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(MixtralConfig):
    # the source's keys
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # one entry a layer, 1 or 0 ((): the published period, a full layer
    # without rotary embedding and then three window layers with it)
    rope_layout: Tuple[int, ...] = ()
    sliding_window_layout: Tuple[int, ...] = ()
    sliding_window_size: int = 4096
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    tie_word_embeddings: bool = False
    # serving: the pages of the window layers' own pool (the engine sets it)
    kv_window_num_pages: int = 0
    # the stack is mixed: nothing to scan over; one chip holds every expert
    scan_layers: bool = False
    expert_parallel: bool = False
    kernel_init: Callable = in_float32(nn.initializers.lecun_normal())

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        n = self.num_hidden_layers
        period = lambda: tuple(int(i % 4 != 0) for i in range(n))
        for key in ("rope_layout", "sliding_window_layout"):
            layout = tuple(int(v) for v in getattr(self, key)) or period()
            assert len(layout) == n and set(layout) <= {0, 1}, (key, layout)
            put(key, layout)
        assert not self.scan_layers, "layers of two kinds do not scan"
        for key, want in (("rope_scaling", None),
                          ("moe_primary_router_apply_softmax", True),
                          ("norm_topk_prob", True),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only the published "
                    f"SmallThinker form ({want!r}) is implemented")
        # what the shared blocks and ``inference/paged`` read, under the
        # names they read it by
        put("layer_types", tuple(WINDOW if w else FULL
                                 for w in self.sliding_window_layout))
        put("sliding_window", int(self.sliding_window_size))
        put("num_local_experts", int(self.moe_num_primary_experts))
        put("num_experts_per_tok", int(self.moe_num_active_primary_experts))
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps one window for the whole "
                "model; serve this model through RaggedInferenceEngineV2")


PRESETS = {
    "smallthinker-21b-a3b": dict(
        vocab_size=151936, hidden_size=2560, num_hidden_layers=52,
        num_attention_heads=28, num_key_value_heads=4, head_dim=128,
        max_position_embeddings=16384),
    # two periods; a window of 12 so that a prompt of 64 is several windows
    # deep; four KV heads under a query group of three
    "tinysmallthinker": dict(
        vocab_size=96, hidden_size=64, moe_ffn_hidden_size=32,
        num_hidden_layers=8, sliding_window_size=12, num_attention_heads=12,
        num_key_value_heads=4, head_dim=16, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, max_position_embeddings=512),
}


def get_config(preset: str, **overrides) -> SmallThinkerConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return SmallThinkerConfig(**kw)


class SmallThinkerAttention(nn.Module):
    """Plain grouped-query attention; a window where the layer's entry of
    ``sliding_window_layout`` reads 1, the rotary embedding where its entry
    of ``rope_layout`` does."""

    config: SmallThinkerConfig
    layer_idx: int

    @nn.compact
    def __call__(self, u, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = u.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        kind = cfg.layer_types[self.layer_idx]
        window = cfg.sliding_window if kind == WINDOW else None
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        heads = lambda name, n: nn.Dense(n * D, name=name, **dense)(
            u).reshape(B, S, n, D).transpose(0, 2, 1, 3)
        # the device's time of a window layer's attention and of a full
        # layer's, apart (``telemetry/device_scopes.py:_SPLIT_SCOPES``)
        with jax.named_scope("attn_window" if window else "attn_full"):
            q, k, v = (heads("q_proj", H), heads("k_proj", Hkv),
                       heads("v_proj", Hkv))
            if cfg.rope_layout[self.layer_idx]:
                q = rotary_embedding(q, positions, cfg.rope_theta)
                k = rotary_embedding(k, positions, cfg.rope_theta)
            if cfg.paged_decode:
                from deepspeed_tpu.inference.paged import \
                    paged_update_and_attend

                assert ragged_meta is not None and B == 1, (
                    "paged token batches are [1, T] with the engine's "
                    "ragged_meta")
                y = paged_update_and_attend(self, q, k, v, ragged_meta, cfg,
                                            layer_kind=kind)
            else:
                qg = q.reshape(B, Hkv, H // Hkv, S, D)
                s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                               preferred_element_type=jnp.float32
                               ) / np.sqrt(D)
                at = jnp.arange(S)
                seen = at[None, :] <= at[:, None]
                if window:
                    seen &= at[None, :] > at[:, None] - window
                p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
                y = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(cfg.dtype),
                               v).reshape(B, H, S, D)
            y = y.transpose(0, 2, 1, 3).reshape(B, S, H * D)
            return nn.Dense(E, name="o_proj", **dense)(y)


class SmallThinkerBlock(nn.Module):
    config: SmallThinkerConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        experts = moe_layer(
            cfg, "block_sparse_moe", activation="reglu",
            intermediate_size=cfg.moe_ffn_hidden_size,
            normalize_weights=cfg.norm_topk_prob,
            down_init_scale=EXPERT_DOWN_INIT_SCALE, dispatch_impl="grouped")
        # the router reads the layer's input as it comes, unnormed, and is
        # issued before the layer's attention
        routing = experts(x, route_only=True)
        x = x + SmallThinkerAttention(cfg, self.layer_idx, name="self_attn")(
            norm("input_layernorm")(x), positions, ragged_meta)
        y, _ = experts(norm("post_attention_layernorm")(x),
                       live=live_rows(ragged_meta), routing=routing)
        return x + y


class SmallThinkerModel(nn.Module):
    """Hidden states after the final norm (of the rows a fused tick
    samples, where its metadata names them)."""

    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.inference.common import sampled_rows

        cfg = self.config
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens",
                     embedding_init=in_float32(nn.initializers.normal(
                         EMBED_INIT_STD)))(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = SmallThinkerBlock(cfg, i, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class SmallThinkerForCausalLM(nn.Module):
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = SmallThinkerModel(cfg, name="model")(
            input_ids, positions, deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_init_kwargs(cfg))(x)
