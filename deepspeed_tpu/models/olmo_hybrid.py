"""Olmo-Hybrid model family (allenai Olmo-Hybrid-7B, ``model_type:
olmo_hybrid``): gated delta rule and full attention, layer by layer.

``layer_types`` says, layer by layer, whether the token mixer is
``"linear_attention"`` (a Gated DeltaNet, arXiv:2412.06464) or
``"full_attention"`` (Olmo 3's attention without rotary embedding); the
published stack is three of the first then one of the second, eight times.
The two kinds place their norms differently.  With ``N`` RMSNorm (learned
scale) and ``x`` the residual stream::

    linear_attention:  h = x + GDN(N_a(x));     y = h + MLP(N_f(h))
    full_attention:    h = x + N_1(Attn(x));    y = h + N_2(MLP(h))
    logits = W_head N(x)                         (untied)

- **GDN**, ``linear_num_key_heads`` heads with keys of
  ``linear_key_head_dim`` and values of ``linear_value_head_dim``, ``u =
  N_a(x)``: ``q~, k~, v~ = W_q u, W_k u, W_v u`` (no bias); each channel
  convolved along its sequence, causally, with ``linear_conv_kernel_dim``
  taps of its own, then SiLU; a head at a time ``q = q' / sqrt(|q'|^2 + 1e-6)
  / sqrt(dk)``, ``k = k' / sqrt(|k'|^2 + 1e-6)``; ``b = 2 sigmoid(W_b u)``
  (the 2 is ``linear_allow_neg_eigval``), ``g = -exp(A_log) softplus(W_a u +
  dt_bias)`` in float32, ``a = exp(g)``; the state ``S`` of a head (``dk x
  dv``, float32, zero at position 0): ``S' = a S``, ``d = b (v - S'^T k)``,
  ``S = S' + k d^T``, ``o = S^T q``; ``y = W_o [N_o(o) * silu(W_g u)]`` with
  ``N_o`` over each head's ``dv`` (one learned scale shared by the heads).
  What a served sequence carries is the convolution's last inputs and ``S``:
  two per-slot leaves beside the pages
  (``inference/paged/delta_state.py:ragged_gated_delta``).
- **Attn**, ``num_attention_heads`` heads of 128 over as many KV heads: ``q =
  N_q(W_q x)``, ``k = N_k(W_k x)`` with the norms over the WHOLE projection
  (not a head at a time), ``v = W_v x``, causal softmax at ``1 / sqrt(128)``,
  **no rotary embedding** (``rope_parameters.rope_theta: null``), no window,
  ``W_o``.  Served, its keys and values are per-head pages
  (``inference/paged/kv_pages.py``), **thirty heads stored as thirty-two**
  (``kv_heads_stored``): zero heads are appended to q, k and v before the call
  and their outputs dropped before ``W_o``.
- ``MLP(z) = W_d (silu(W_g z) * W_u z)``, the same in both kinds of layer.

Only the serving form and whole sequences from their first token are here;
the dense decode cache is refused (it has nowhere to keep the state).

**First values** (what ``init`` draws; a checkpoint brings its own): every
matrix lecun-normal, drawn in float32 and rounded to ``param_dtype``; every
norm scale 1; the taps lecun-normal over a channel's taps; ``A_log = log
U(0, 16)`` and ``dt_bias`` the inverse softplus of ``exp U(log 0.001, log
0.1)``, flash-linear-attention's own, so a head's time constant ``1 / (1 -
a)`` at ``W_a u = 0`` runs from under a token to thousands (``A dt`` from
1.6 down to 1e-4 and less); ``W_a`` at :data:`DECAY_PROJ_INIT_SCALE` (0.5)
of lecun-normal's size, so a token moves its decay's exponent by a factor of
``e^(+-0.5)`` and not the head's time constant by orders; **the embedding
at** :data:`EMBED_INIT_STD` **(1.0) a value**, where flax draws ``1 /
sqrt(hidden)``: at flax's size the streams of different tokens collapse onto
one another under mixers over thousands of seeded keys
(``models/keye_vl2.py``; PERF.md section 6, PR 47).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.lfm2_moe import _FrozenDict, _taps_init
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaMLP, RMSNorm,
                                        _init_kwargs, count_params)
from deepspeed_tpu.models.ouro import in_float32
from deepspeed_tpu.ops import gated_delta_rule

__all__ = ["OlmoHybridConfig", "OlmoHybridModel", "OlmoHybridForCausalLM",
           "get_config", "count_params"]

LINEAR, FULL = "linear_attention", "full_attention"
EMBED_INIT_STD = 1.0
DECAY_PROJ_INIT_SCALE = 0.5
_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    # the source's keys
    layer_types: Tuple[str, ...] = ()
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: Any = dataclasses.field(
        default_factory=lambda: _FrozenDict(rope_theta=None))
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-6
    # the stack is mixed: nothing to scan over
    scan_layers: bool = False
    kernel_init: Callable = in_float32(nn.initializers.lecun_normal())

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        put("layer_types", tuple(self.layer_types))
        put("rope_parameters", _FrozenDict(self.rope_parameters or {}))
        assert len(self.layer_types) == self.num_hidden_layers, (
            f"layer_types names {len(self.layer_types)} layers, "
            f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.layer_types) - {LINEAR, FULL}
        assert not unknown, f"unknown layer_types {sorted(unknown)}"
        assert not self.scan_layers, "layers of two kinds do not scan"
        for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only the published "
                    f"Olmo-Hybrid form ({want!r}) is implemented")
        if self.rope_parameters.get("rope_theta") is not None:
            raise NotImplementedError(
                "rope_parameters.rope_theta is set: the published "
                "Olmo-Hybrid has no rotary embedding (null), and only that "
                "form is implemented")
        assert self.linear_num_value_heads == self.linear_num_key_heads, (
            "a linear_attention layer's state is a matrix a head: "
            f"linear_num_value_heads {self.linear_num_value_heads} != "
            f"{self.linear_num_key_heads}")
        assert self.num_key_value_heads == self.num_attention_heads, (
            "a full_attention layer is MHA as published: "
            f"{self.num_key_value_heads} KV heads != "
            f"{self.num_attention_heads}")
        put("sliding_window", None)
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps keys and values only; a "
                "linear_attention layer's convolution rows and matrix "
                "state live per slot beside the paged pool — serve this "
                "model through RaggedInferenceEngineV2")


_PERIOD = (LINEAR, LINEAR, LINEAR, FULL)

PRESETS = {
    "olmo-hybrid-7b": dict(
        vocab_size=100352, hidden_size=3840, intermediate_size=11008,
        num_hidden_layers=32, layer_types=_PERIOD * 8,
        num_attention_heads=30, num_key_value_heads=30,
        max_position_embeddings=65536),
    # keys and values whose widths differ and are no whole lane tile, fifteen
    # attention heads (stored as sixteen), one period
    "tinyolmohybrid": dict(
        vocab_size=96, hidden_size=120, intermediate_size=128,
        num_hidden_layers=4, layer_types=_PERIOD,
        num_attention_heads=15, num_key_value_heads=15,
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=16,
        max_position_embeddings=512),
}


def get_config(preset: str, **overrides) -> OlmoHybridConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return OlmoHybridConfig(**kw)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)`` a head (flash-linear-attention's; drawn from 1e-4,
    log 0 being no first value)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of ``exp U(log 0.001, log 0.1)`` a head."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _decay_proj_init(key, shape, dtype=jnp.float32):
    return (DECAY_PROJ_INIT_SCALE * nn.initializers.lecun_normal()(
        key, shape, jnp.float32)).astype(dtype)


class ShortConv(nn.Module):
    """A causal depthwise convolution over q~, k~ and v~ side by side, then
    SiLU, then q and k at unit length a head (q scaled by ``dk ** -0.5``);
    served, its last inputs are per-slot state of this module.

    ``parts``: ``(q~, k~, v~)`` as their projections leave them, ``[B, S, H *
    dk]`` twice and ``[B, S, H * dv]`` -> ``q, k [B, S, H, dk]``, ``v [B, S,
    H, dv]``.  A served fused tick on the chip is ONE pass a part
    (``ops/short_conv.py``: the kernel takes the parts where they lie, the
    norms inside it); every other call runs the lines below on the parts
    side by side."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, parts, positions, ragged_meta=None, *, heads: int):
        cfg, H = self.config, heads
        u = jnp.concatenate(parts, axis=-1)
        B, S, C = u.shape
        dk, dv = parts[0].shape[-1] // H, parts[2].shape[-1] // H
        K = cfg.linear_conv_kernel_dim
        taps = self.param("weight", _taps_init, (C, K), cfg.param_dtype)
        scales = (float(1.0 / np.sqrt(dk)), 1.0, None)

        def unit(a, scale):
            a = a.reshape(B, S, H, dk).astype(jnp.float32)
            a = a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + _L2_EPS)
            return (a * scale).astype(cfg.dtype)

        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import ragged_short_conv
            from deepspeed_tpu.ops import routes, short_conv

            if short_conv.route(S, ragged_meta["cu_q_lens"].shape[0] - 1, K,
                                u.dtype, dk) != routes.XLA:
                q, k, v = ragged_short_conv(
                    self, tuple(p[0] for p in parts), taps,
                    positions.reshape(-1), ragged_meta, silu=True,
                    unit=(dk, _L2_EPS, scales))
                return (q.reshape(B, S, H, dk), k.reshape(B, S, H, dk),
                        v.reshape(B, S, H, dv))
            c = ragged_short_conv(self, u[0], taps, positions.reshape(-1),
                                  ragged_meta)[None]
        else:
            # whole sequences from their first token: nothing to carry
            t = taps.astype(jnp.float32)
            c = sum(jnp.pad(u, ((0, 0), (d, 0), (0, 0)))[:, :S].astype(
                jnp.float32) * t[:, K - 1 - d] for d in range(K)
            ).astype(u.dtype)
        y = jax.nn.silu(c.astype(jnp.float32)).astype(u.dtype)
        q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
        return unit(q, scales[0]), unit(k, scales[1]), v.reshape(B, S, H, dv)


class GatedDeltaNet(nn.Module):
    """The gated delta rule over a matrix state (the module docstring's
    equations)."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, u, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = u.shape
        H, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        proj = lambda name, n: nn.Dense(n, name=name, **dense)(u)
        if cfg.paged_decode:
            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
        q, k, v = ShortConv(cfg, name="conv")(
            (proj("q_proj", H * dk), proj("k_proj", H * dk),
             proj("v_proj", H * dv)), positions, ragged_meta, heads=H)
        with jax.named_scope("gates"):
            f32 = dict(dense, dtype=jnp.float32)
            beta = jax.nn.sigmoid(nn.Dense(H, name="b_proj", **f32)(u))
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            a_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (H,), jnp.float32)
            g = -jnp.exp(a_log) * jax.nn.softplus(nn.Dense(
                H, name="a_proj", **dict(f32, kernel_init=_decay_proj_init)
            )(u) + dt_bias)
            gate = jax.nn.silu(proj("g_proj", H * dv).astype(jnp.float32))
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import ragged_gated_delta

            o = ragged_gated_delta(self, q[0], k[0], v[0], g[0], beta[0],
                                   positions.reshape(-1), ragged_meta)[None]
        else:
            zero = jnp.zeros((H, dk, dv), jnp.float32)
            o = jax.vmap(lambda *a: gated_delta_rule.recurrence(*a, zero)[0])(
                q, k, v, g, beta).astype(cfg.dtype)
        o = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="o_norm")(o)
        y = (o * gate.reshape(B, S, H, dv)).astype(cfg.dtype)
        return nn.Dense(E, name="o_proj", **dense)(y.reshape(B, S, H * dv))


class FullAttention(nn.Module):
    """Multi-head attention without rotary embedding, q and k normed over
    the whole projection (the module docstring's equations)."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = x.shape
        H, D = cfg.num_attention_heads, cfg.head_dim
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        proj = lambda name: nn.Dense(H * D, name=name, **dense)(x)
        heads = lambda a: a.reshape(B, S, H, D).transpose(0, 2, 1, 3)
        q = heads(norm("q_norm")(proj("q_proj")))
        k = heads(norm("k_norm")(proj("k_proj")))
        v = heads(proj("v_proj"))
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import (
                kv_heads_stored, paged_update_and_attend)

            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
            # thirty heads are stored as thirty-two: zero heads whose
            # outputs are dropped (inference/paged/kv_pages.py)
            pad = kv_heads_stored(H) - H
            wide = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
            y = paged_update_and_attend(self, wide(q), wide(k), wide(v),
                                        ragged_meta, cfg)[:, :H]
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32) / np.sqrt(D)
            causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            y = jnp.einsum("bhqk,bhkd->bhqd", p.astype(cfg.dtype), v)
        y = y.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        return nn.Dense(E, name="o_proj", **dense)(y)


class OlmoHybridBlock(nn.Module):
    config: OlmoHybridConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        mlp = LlamaMLP(cfg, name="mlp")
        if cfg.layer_types[self.layer_idx] == LINEAR:
            h = x + GatedDeltaNet(cfg, name="linear_attn")(
                norm("attention_layer_norm")(x), positions, ragged_meta)
            return h + mlp(norm("feedforward_layer_norm")(h))
        h = x + norm("post_attention_norm")(
            FullAttention(cfg, name="self_attn")(x, positions, ragged_meta))
        return h + norm("post_feedforward_norm")(mlp(h))


class OlmoHybridModel(nn.Module):
    """Hidden states after the final norm (of the rows a fused tick
    samples, where its metadata names them)."""

    config: OlmoHybridConfig

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
            x = OlmoHybridBlock(cfg, i, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class OlmoHybridForCausalLM(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = OlmoHybridModel(cfg, name="model")(
            input_ids, positions, deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_init_kwargs(cfg))(x)
