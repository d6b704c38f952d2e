"""Trinity model family (arcee-ai Trinity-Large-Preview, ``model_type:
afmoe``): WINDOW attention layers and FULL attention layers in one model
(``layer_types``; published: three ``sliding_attention`` layers, then one
``full_attention``, fifteen times), gated grouped-query attention with a norm
of q and k a head, rotary embedding on the window layers ONLY, four norms a
layer, leading dense layers and then a sigmoid router over experts of which
THIS CHIP HOLDS A SHARE, beside one shared expert.

With ``N(x) = x * rsqrt(mean(x^2) + eps) * w`` (float32 inside) and ``h`` the
residual stream::

    h0 = E[token] * sqrt(hidden_size)                  (mup_enabled)
    a  = h + N2(Attn_l(N1(h)));   y = a + N4(FF_l(N3(a)))
    logits = W_head N(h_L)

(``input_layernorm``, ``post_attention_layernorm``, ``pre_mlp_layernorm``,
``post_mlp_layernorm``: the mixer's and the feed-forward's OUTPUT is normed
before it joins the residual.)

- **Attn_l(u)**: ``q = W_q u`` (``num_attention_heads`` of ``head_dim``), ``k,
  v = W_k u, W_v u`` (``num_key_value_heads``), no bias; ``q, k <- Nq(q),
  Nk(k)``, an RMS norm over each head's ``head_dim`` with one learned scale
  each; on a ``sliding_attention`` layer the rotary embedding on q and k
  (``rope_theta``, every dimension, halves rotated) and a causal window: row
  ``p`` attends keys ``p - sliding_window + 1 .. p``; on a ``full_attention``
  layer NO rotary embedding and every key ``<= p``; softmax at ``1 /
  sqrt(head_dim)`` in float32; the output gated elementwise over heads x
  head_dim by ``sigmoid(W_g u)``; then ``W_o``.  Served, a window layer's
  keys and values live in the window layers' OWN page pool behind their own
  page table (``inference/paged/window_pages.py``), a full layer's in the
  first pool (``kv_pages.py``).
- **FF_l**, ``l < num_dense_layers``: a SwiGLU of ``intermediate_size``.
- **FF_l** otherwise: ``moe/layer.py``'s dropless form with the sigmoid
  router: ``s = sigmoid(W_r u)`` in float32 over ALL ``num_experts *
  expert_shards`` experts, the chosen ``num_experts_per_tok`` the largest of
  ``s + e`` (a selection bias), the weights the chosen ``s`` over their sum
  ``+ 1e-20`` (``route_norm``) times ``route_scale``; beside it
  ``num_shared_experts`` shared experts as ONE SwiGLU, ungated and unscaled.
  **``num_experts`` is the number of experts HELD here**: share
  ``expert_shard`` of the ``expert_shards`` chips that share each layer (the
  published model is ``num_experts=256, expert_shards=1``); a copy routed to
  an absent expert adds nothing, and nothing stands in for the exchange
  (``models/solar_open2.py``).

Only inference forms are here (no auxiliary loss, no capacity).  **First
values** (what ``init`` draws; a checkpoint brings its own): every matrix
lecun-normal, drawn in float32 and rounded to ``param_dtype``; every norm
scale 1 (the published "depth-scaled" first value of the output norms is a
first value and no term of the forward pass); ``e`` zero; the embedding at
:data:`EMBED_INIT_STD` a value AFTER the ``sqrt(hidden_size)`` factor (drawn
at ``EMBED_INIT_STD / sqrt(hidden_size)`` where ``mup_enabled``: every layer
adds two outputs of unit size, each normed before it joins, so a residual
stream that starts 55 times as large carries the token's own embedding to
the head and nothing of the layers; a sweep at a reduced width read every
mutation of the reference inside e4m3's own gap at 1.0 before the factor,
PERF.md section 6 PR 59) and the routed experts' down-projections at
:data:`EXPERT_DOWN_INIT_SCALE` of lecun-normal's size
(``models/solar_open2.py``'s, for its reason).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import (LlamaMLP, RMSNorm, _init_kwargs,
                                        count_params, rotary_embedding)
from deepspeed_tpu.models.mixtral import (MixtralConfig, live_rows,
                                          moe_layer)
from deepspeed_tpu.models.ouro import in_float32

__all__ = ["TrinityConfig", "TrinityModel", "TrinityForCausalLM",
           "get_config", "count_params"]

EMBED_INIT_STD = 1.0
EXPERT_DOWN_INIT_SCALE = 0.3
# the DeepSeek-V3 family's code adds this to the sum of the chosen scores
ROUTED_WEIGHT_EPS = 1e-20
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class TrinityConfig(MixtralConfig):
    # the source's keys
    head_dim: int = 128
    layer_types: Tuple[str, ...] = ()   # (): from global_attn_every_n_layers
    global_attn_every_n_layers: int = 4
    sliding_window: Optional[int] = 4096
    num_dense_layers: int = 6
    num_experts: int = 256              # the experts HELD here
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    moe_intermediate_size: int = 3072
    mup_enabled: bool = True
    route_norm: bool = True
    route_scale: float = 2.448
    score_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    rope_scaling: Any = None
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # the chips that share each layer, and which share this is: the router
    # is ``num_experts * expert_shards`` wide (no keys of the source)
    expert_shards: int = 1
    expert_shard: int = 0
    # serving: the pages of the window layers' own pool (the engine sets it)
    kv_window_num_pages: int = 0
    # the stack is mixed: nothing to scan over; one chip holds its share
    scan_layers: bool = False
    expert_parallel: bool = False
    kernel_init: Callable = in_float32(nn.initializers.lecun_normal())

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        kinds = tuple(self.layer_types) or tuple(
            FULL if (i + 1) % self.global_attn_every_n_layers == 0 else WINDOW
            for i in range(self.num_hidden_layers))
        put("layer_types", kinds)
        assert len(kinds) == self.num_hidden_layers and set(kinds) <= {
            WINDOW, FULL}, kinds
        assert not self.scan_layers, "layers of two kinds do not scan"
        for key, want in (("rope_scaling", None), ("n_group", 1),
                          ("topk_group", 1), ("score_func", "sigmoid"),
                          ("hidden_act", "silu"), ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only the published "
                    f"Trinity form ({want!r}) is implemented")
        if WINDOW in kinds and not self.sliding_window:
            raise NotImplementedError(
                "sliding_window=None with sliding_attention layers: a "
                "window layer needs its window")
        assert 0 <= self.expert_shard < self.expert_shards, (
            self.expert_shard, self.expert_shards)
        # what the shared blocks read, under the name they read it by
        put("num_local_experts", int(self.num_experts))
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps one window for the whole "
                "model; serve this model through RaggedInferenceEngineV2")


PRESETS = {
    "trinity-large-preview": dict(
        vocab_size=200192, hidden_size=3072, intermediate_size=12288,
        num_hidden_layers=60, num_attention_heads=48, num_key_value_heads=8,
        head_dim=128, max_position_embeddings=262144),
    # one dense layer and one period; a window of 12 so that a prompt of 64
    # is several windows deep; 3 of 12 experts held
    "tinytrinity": dict(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5,
        layer_types=(WINDOW, WINDOW, FULL, WINDOW, WINDOW),
        num_dense_layers=1, sliding_window=12, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts=3, expert_shards=4,
        expert_shard=1, num_experts_per_tok=4, max_position_embeddings=512),
}


def get_config(preset: str, **overrides) -> TrinityConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return TrinityConfig(**kw)


class TrinityAttention(nn.Module):
    """Gated grouped-query attention with a norm of q and k a head; a window
    and the rotary embedding where ``kind`` is ``sliding_attention``."""

    config: TrinityConfig
    kind: str

    @nn.compact
    def __call__(self, u, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = u.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        window = cfg.sliding_window if self.kind == WINDOW else None
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        proj = lambda name, n: nn.Dense(n, name=name, **dense)(u)
        heads = lambda a, n: a.reshape(B, S, n, D)
        # the device's time of a window layer's attention and of a full
        # layer's, apart (``telemetry/device_scopes.py:_SPLIT_SCOPES``)
        with jax.named_scope("attn_window" if window else "attn_full"):
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(
                heads(proj("q_proj", H * D), H)).transpose(0, 2, 1, 3)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(
                heads(proj("k_proj", Hkv * D), Hkv)).transpose(0, 2, 1, 3)
            v = heads(proj("v_proj", Hkv * D), Hkv).transpose(0, 2, 1, 3)
            gate = jax.nn.sigmoid(
                proj("gate_proj", H * D).astype(jnp.float32))
            if window:
                q = rotary_embedding(q, positions, cfg.rope_theta)
                k = rotary_embedding(k, positions, cfg.rope_theta)
            if cfg.paged_decode:
                from deepspeed_tpu.inference.paged import \
                    paged_update_and_attend

                assert ragged_meta is not None and B == 1, (
                    "paged token batches are [1, T] with the engine's "
                    "ragged_meta")
                y = paged_update_and_attend(self, q, k, v, ragged_meta, cfg,
                                            layer_kind=self.kind)
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
            y = (y.astype(jnp.float32) * gate).astype(cfg.dtype)
            return nn.Dense(E, name="o_proj", **dense)(y)


class TrinityBlock(nn.Module):
    config: TrinityConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        y = TrinityAttention(cfg, cfg.layer_types[self.layer_idx],
                             name="self_attn")(
            norm("input_layernorm")(x), positions, ragged_meta)
        x = x + norm("post_attention_layernorm")(y)
        h = norm("pre_mlp_layernorm")(x)
        if self.layer_idx < cfg.num_dense_layers:
            return x + norm("post_mlp_layernorm")(LlamaMLP(cfg, name="mlp")(h))
        live = live_rows(ragged_meta)
        y, _ = moe_layer(
            cfg, "mlp", intermediate_size=cfg.moe_intermediate_size,
            normalize_weights=cfg.route_norm, scoring="sigmoid",
            expert_bias=True, down_init_scale=EXPERT_DOWN_INIT_SCALE,
            routed_scaling_factor=cfg.route_scale,
            routed_weight_eps=ROUTED_WEIGHT_EPS,
            expert_shards=cfg.expert_shards, expert_shard=cfg.expert_shard,
            dispatch_impl="grouped")(h, live=live)
        if cfg.num_shared_experts:
            # ungated, unscaled, on every live row, and computed WHOLE on
            # every chip that shares the layer; padding rows and inactive
            # slots stay zeros as the routed path leaves them
            with jax.named_scope("moe.shared"):
                shared = LlamaMLP(dataclasses.replace(
                    cfg, intermediate_size=(cfg.num_shared_experts
                                            * cfg.moe_intermediate_size)),
                    name="shared_experts")(h)
                if live is not None:
                    shared = jnp.where(live.reshape(h.shape[:-1])[..., None],
                                       shared, 0)
                y = y + shared
        return x + norm("post_mlp_layernorm")(y)


class TrinityModel(nn.Module):
    """Hidden states after the final norm (of the rows a fused tick
    samples, where its metadata names them)."""

    config: TrinityConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.inference.common import sampled_rows

        cfg = self.config
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        factor = np.sqrt(cfg.hidden_size) if cfg.mup_enabled else 1.0
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens",
                     embedding_init=in_float32(nn.initializers.normal(
                         EMBED_INIT_STD / factor)))(input_ids)
        if cfg.mup_enabled:
            x = (x.astype(jnp.float32) * factor).astype(cfg.dtype)
        for i in range(cfg.num_hidden_layers):
            x = TrinityBlock(cfg, i, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class TrinityForCausalLM(nn.Module):
    config: TrinityConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = TrinityModel(cfg, name="model")(
            input_ids, positions, deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_init_kwargs(cfg))(x)
