"""Solar-Open2 model family (upstage Solar-Open2-250B, ``model_type:
solar_open2``): Kimi Delta Attention and gated softmax attention without
rotary embedding, layer by layer, every layer's feed-forward a sigmoid router
over experts of which THIS CHIP HOLDS A SHARE, beside one shared expert.

``gqa_layers`` names the softmax layers (published: 0, 4, .., 44: one, then
three KDA layers, twelve times).  Every layer is pre-norm.  With ``N``
RMSNorm (learned scale) and ``x`` the residual stream::

    h = x + Mixer(N_a(x));   y = h + FF(N_f(h));   logits = W_head N(x)

- **KDA** (Kimi Linear, arXiv:2510.26692), ``linear_attn_config.num_heads``
  heads with keys and values of ``linear_attn_config.head_dim``, ``u =
  N_a(x)``: ``q~, k~, v~ = W_q u, W_k u, W_v u`` (no bias); each channel
  convolved along its sequence, causally, with ``short_conv_kernel_size``
  taps of its own, then SiLU; a head at a time ``q = q' / sqrt(|q'|^2 + 1e-6)
  / sqrt(d)``, ``k = k' / sqrt(|k'|^2 + 1e-6)``; ``b = 2 sigmoid(W_b u)``
  (the 2 is ``kda_allow_neg_eigval``); **the decay is a vector a key
  channel**, ``g = -exp(A_log) * softplus(W_f2 W_f1 u + dt_bias)`` in
  float32 (``kda_use_full_proj: false``: the low-rank pair, ``W_f1`` to the
  head width; ``A_log`` one number a head, ``dt_bias`` one a channel), ``a =
  exp(g)``; the state ``S`` of a head (``d x d``, float32, zero at position
  0): ``S' = diag(a) S``, ``d_t = b (v - S'^T k)``, ``S = S' + k d_t^T``, ``o
  = S^T q``; ``y = W_o [N_o(o) * sigmoid(W_g2 W_g1 u + c_g)]`` with ``N_o``
  over each head's ``d`` (one learned scale shared by the heads).  What a
  served sequence carries is the convolution's last inputs and ``S``: two
  per-slot leaves beside the pages (``inference/paged/kda_state.py``).
- **GQA**, ``num_attention_heads`` query heads of ``head_dim`` over
  ``num_key_value_heads`` KV heads: ``q, k, v = W_q u, W_k u, W_v u`` (no
  bias, no QK-norm), causal softmax at ``1 / sqrt(head_dim)``, **no rotary
  embedding** (``use_rope: false``; ``rope_theta`` and
  ``partial_rotary_factor`` are kept as published and unused), ``y = W_o [o
  * sigmoid(W_g u)]`` elementwise over heads x head_dim (``use_gqa_gate``).
  Served, its keys and values are per-head pages
  (``inference/paged/kv_pages.py``).
- **FF**: ``moe/layer.py``'s dropless form with the sigmoid router: ``s =
  sigmoid(W_r z)`` in float32 over ALL ``n_routed_experts * expert_shards``
  experts, the chosen set the top ``num_experts_per_tok`` of ``s + e`` (a
  selection bias), the weights the chosen ``s`` over their sum ``+ 1e-20``
  (``norm_topk_prob``) times ``routed_scaling_factor``; beside it
  ``n_shared_experts`` shared experts as ONE SwiGLU, ungated and unscaled.
  **``n_routed_experts`` is the number of experts HELD here**: share
  ``expert_shard`` of ``expert_shards`` chips that share each layer (the
  published model is ``n_routed_experts=320, expert_shards=1``).  A copy
  routed to an absent expert adds nothing: that part of the layer's result
  is another chip's, and nothing here stands in for it or for the exchange.

Only the serving form and whole sequences from their first token are here;
the dense decode cache is refused (it has nowhere to keep the state).

**First values** (what ``init`` draws; a checkpoint brings its own): every
matrix lecun-normal, drawn in float32 and rounded to ``param_dtype``; every
norm scale 1; ``e`` and ``c_g`` zero; the taps lecun-normal over a channel's
taps; ``A_log = log U(1, 16)`` a head and ``dt_bias`` the inverse softplus of
``exp U(log 0.001, log 0.1)`` a channel (flash-linear-attention's own);
``W_f2`` at ``olmo_hybrid.DECAY_PROJ_INIT_SCALE`` (0.5) of lecun-normal's
size, so a token moves its decay's exponent by about ``e^(+-0.5)``; the
embedding at :data:`EMBED_INIT_STD` (1.0) a value and the routed experts'
down-projections at :data:`EXPERT_DOWN_INIT_SCALE` (0.3) of lecun-normal's
size (``models/keye_vl2.py``'s two, for its reasons: PERF.md section 6, PR
47).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.lfm2_moe import _FrozenDict
from deepspeed_tpu.models.llama import (LlamaMLP, RMSNorm, _init_kwargs,
                                        count_params)
from deepspeed_tpu.models.mixtral import (MixtralConfig, live_rows,
                                          moe_layer)
from deepspeed_tpu.models.olmo_hybrid import (ShortConv, _decay_proj_init,
                                              _dt_bias_init)
from deepspeed_tpu.models.ouro import in_float32
from deepspeed_tpu.ops import kda

__all__ = ["SolarOpen2Config", "SolarOpen2Model", "SolarOpen2ForCausalLM",
           "get_config", "count_params"]

EMBED_INIT_STD = 1.0
EXPERT_DOWN_INIT_SCALE = 0.3
# the DeepSeek-V3 family's code adds this to the sum of the chosen scores
ROUTED_WEIGHT_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(MixtralConfig):
    # the source's keys (``intermediate_size`` is kept as published and
    # unused: ``first_k_dense_replace`` 0 leaves no dense layer)
    head_dim: int = 128
    linear_attn_config: Any = dataclasses.field(
        default_factory=lambda: _FrozenDict(
            short_conv_kernel_size=4, head_dim=128, num_heads=64,
            num_kv_heads=None))
    gqa_layers: Tuple[int, ...] = ()
    use_rope: bool = False
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    moe_intermediate_size: int = 1280
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320         # the experts HELD here
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    num_experts_per_tok: int = 8
    tie_word_embeddings: bool = False
    # the chips that share each layer, and which share this is: the router
    # is ``n_routed_experts * expert_shards`` wide (no keys of the source)
    expert_shards: int = 1
    expert_shard: int = 0
    # the stack is mixed: nothing to scan over; one chip holds its share
    scan_layers: bool = False
    expert_parallel: bool = False
    kernel_init: Callable = in_float32(nn.initializers.lecun_normal())

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        put("gqa_layers", tuple(int(i) for i in self.gqa_layers))
        put("linear_attn_config", _FrozenDict(self.linear_attn_config))
        assert not self.scan_layers, "layers of two kinds do not scan"
        for key, want in (("use_rope", False), ("use_gqa_gate", True),
                          ("kda_use_full_proj", False),
                          ("first_k_dense_replace", 0),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only the published "
                    f"Solar-Open2 form ({want!r}) is implemented")
        la = self.linear_attn_config
        if la.get("num_kv_heads") not in (None, la["num_heads"]):
            raise NotImplementedError(
                f"linear_attn_config.num_kv_heads={la['num_kv_heads']!r}: a "
                "KDA layer's state is a matrix a head (null: as many value "
                "heads as key heads), and only that form is implemented")
        assert 0 <= self.expert_shard < self.expert_shards, (
            self.expert_shard, self.expert_shards)
        # what the shared blocks read, under the names they read it by
        put("num_local_experts", int(self.n_routed_experts))
        put("sliding_window", None)
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps keys and values only; a KDA "
                "layer's convolution rows and matrix state live per slot "
                "beside the paged pool — serve this model through "
                "RaggedInferenceEngineV2")

    @property
    def linear_conv_kernel_dim(self) -> int:
        """The convolution's taps, under the name
        ``models/olmo_hybrid.py:ShortConv`` reads them by."""
        return int(self.linear_attn_config["short_conv_kernel_size"])


PRESETS = {
    "solar-open2-250b": dict(
        vocab_size=196608, hidden_size=4096, intermediate_size=10240,
        num_hidden_layers=48, gqa_layers=tuple(range(0, 48, 4)),
        num_attention_heads=64, num_key_value_heads=8, head_dim=128,
        max_position_embeddings=1048576),
    # one period; keys of 16 over two heads (a grid step's two heads are no
    # whole lane tile: the twins), 3 of 12 experts held
    "tinysolar": dict(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4, gqa_layers=(0,),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                num_heads=2, num_kv_heads=None),
        n_routed_experts=3, expert_shards=4, expert_shard=1,
        num_experts_per_tok=4, max_position_embeddings=512),
}


def get_config(preset: str, **overrides) -> SolarOpen2Config:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return SolarOpen2Config(**kw)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(1, 16)`` a head (flash-linear-attention's KDA)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class KimiDeltaAttention(nn.Module):
    """The delta rule over a matrix state whose rows decay each by a factor
    of their own (the module docstring's equations)."""

    config: SolarOpen2Config

    @nn.compact
    def __call__(self, u, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = u.shape
        H = int(cfg.linear_attn_config["num_heads"])
        d = int(cfg.linear_attn_config["head_dim"])
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        proj = lambda name, n, x=u, **kw: nn.Dense(      # noqa: E731
            n, name=name, **dict(dense, **kw))(x)
        if cfg.paged_decode:
            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
        q, k, v = ShortConv(cfg, name="conv")(
            (proj("q_proj", H * d), proj("k_proj", H * d),
             proj("v_proj", H * d)), positions, ragged_meta, heads=H)
        with jax.named_scope("gates"):
            f32 = dict(dtype=jnp.float32)
            beta = jax.nn.sigmoid(proj("b_proj", H, **f32))
            if cfg.kda_allow_neg_eigval:
                beta = 2.0 * beta
            a_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (H * d,),
                                 jnp.float32)
            # the decay, a key channel: [B, S, H * d] as its projection
            # leaves it (a head a lane block)
            g = -jnp.repeat(jnp.exp(a_log), d) * jax.nn.softplus(proj(
                "f_b_proj", H * d, proj("f_a_proj", d, **f32),
                kernel_init=_decay_proj_init, **f32) + dt_bias)
            gate = jax.nn.sigmoid(proj(
                "g_b_proj", H * d, proj("g_a_proj", d), use_bias=True
            ).astype(jnp.float32))
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import ragged_kda

            o = ragged_kda(self, q[0], k[0], v[0], g[0], beta[0],
                           positions.reshape(-1), ragged_meta)[None]
        else:
            zero = jnp.zeros((H, d, d), jnp.float32)
            o = jax.vmap(lambda *a: kda.recurrence(*a, zero)[0])(
                q, k, v, g.reshape(B, S, H, d), beta).astype(cfg.dtype)
        o = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="o_norm")(o)
        y = (o * gate.reshape(B, S, H, d)).astype(cfg.dtype)
        return nn.Dense(E, name="o_proj", **dense)(y.reshape(B, S, H * d))


class GatedAttention(nn.Module):
    """Grouped-query attention without rotary embedding, its output gated
    elementwise (the module docstring's equations)."""

    config: SolarOpen2Config

    @nn.compact
    def __call__(self, u, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = u.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        proj = lambda name, n: nn.Dense(n, name=name, **dense)(u)
        heads = lambda a, n: a.reshape(B, S, n, D).transpose(0, 2, 1, 3)
        q = heads(proj("q_proj", H * D), H)
        k = heads(proj("k_proj", Hkv * D), Hkv)
        v = heads(proj("v_proj", Hkv * D), Hkv)
        with jax.named_scope("gates"):
            gate = jax.nn.sigmoid(proj("g_proj", H * D).astype(jnp.float32))
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import paged_update_and_attend

            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
            y = paged_update_and_attend(self, q, k, v, ragged_meta, cfg)
        else:
            qg = q.reshape(B, Hkv, H // Hkv, S, D)
            s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                           preferred_element_type=jnp.float32) / np.sqrt(D)
            causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            y = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(cfg.dtype),
                           v).reshape(B, H, S, D)
        y = y.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        y = (y.astype(jnp.float32) * gate).astype(cfg.dtype)
        return nn.Dense(E, name="o_proj", **dense)(y)


class SolarOpen2Block(nn.Module):
    config: SolarOpen2Config
    layer_idx: int

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        u = norm("input_layernorm")(x)
        if self.layer_idx in cfg.gqa_layers:
            x = x + GatedAttention(cfg, name="self_attn")(
                u, positions, ragged_meta)
        else:
            x = x + KimiDeltaAttention(cfg, name="linear_attn")(
                u, positions, ragged_meta)
        h = norm("post_attention_layernorm")(x)
        live = live_rows(ragged_meta)
        y, _ = moe_layer(
            cfg, "mlp", intermediate_size=cfg.moe_intermediate_size,
            normalize_weights=cfg.norm_topk_prob, scoring="sigmoid",
            expert_bias=True, down_init_scale=EXPERT_DOWN_INIT_SCALE,
            routed_scaling_factor=cfg.routed_scaling_factor,
            routed_weight_eps=ROUTED_WEIGHT_EPS,
            expert_shards=cfg.expert_shards, expert_shard=cfg.expert_shard,
            dispatch_impl="grouped")(h, live=live)
        if cfg.n_shared_experts:
            # ungated, unscaled, on every live row, and computed WHOLE on
            # every chip that shares the layer; padding rows and inactive
            # slots stay zeros as the routed path leaves them
            with jax.named_scope("moe.shared"):
                shared = LlamaMLP(dataclasses.replace(
                    cfg, intermediate_size=(cfg.n_shared_experts
                                            * cfg.moe_intermediate_size)),
                    name="shared_experts")(h)
                if live is not None:
                    shared = jnp.where(live.reshape(h.shape[:-1])[..., None],
                                       shared, 0)
                y = y + shared
        return x + y


class SolarOpen2Model(nn.Module):
    """Hidden states after the final norm (of the rows a fused tick
    samples, where its metadata names them)."""

    config: SolarOpen2Config

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
            x = SolarOpen2Block(cfg, i, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class SolarOpen2ForCausalLM(nn.Module):
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = SolarOpen2Model(cfg, name="model")(
            input_ids, positions, deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_init_kwargs(cfg))(x)
