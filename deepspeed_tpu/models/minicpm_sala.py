"""MiniCPM-SALA model family (openbmb MiniCPM-SALA, ``model_type:
minicpm_sala``): sparse and linear attention, layer by layer.

``mixer_types`` says, layer by layer, whether the token mixer is
``"minicpm4"`` (grouped-query attention that, from ``dense_len`` on,
attends only the blocks its compressed scores choose) or
``"lightning-attn"`` (linear attention over a decaying matrix state a
head).  MiniCPM's scalars hold throughout.  With ``N`` RMSNorm, ``h`` the
residual stream and ``a = scale_depth / sqrt(depth_scale_layers)``::

    h_0 = embed(id) * scale_emb
    h <- h + a * Mixer_l(N(h))
    h <- h + a * W_down(silu(W_gate u) * W_up u),  u = N(h)
    logits = W_head(N(h) / (hidden_size / dim_model_base))

- **lightning-attn**, ``lightning_nh`` heads of ``lightning_head_dim``:
  ``q, k, v = W_q u, W_k u, W_v u``; RMSNorm over each head of ``q`` and
  ``k``; RoPE (half-split, ``rope_theta``) on both; ``q / sqrt(d)``;
  ``S_t = l_h S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t`` in float32 with
  ``l_h = exp(-2^(-8 (h + 1) / heads))``; ``y = W_o(N_E(o) * sigmoid(W_g
  u))`` (output norm over the concatenated heads, output gate).  What a
  served sequence carries is ``S``: per-slot state beside the pages
  (``inference/paged/linear_state.py:ragged_linear_attention``).
- **minicpm4**, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads, no RoPE, RMSNorm over each head of
  ``q`` and ``k``, scale ``1 / sqrt(d)``, ``y = W_o(att * sigmoid(W_g
  u))``.  The selection (``sparse_config``; ``ops/block_select_attention
  .py``): compressed keys ``c_j = mean(k_i, stride j <= i < stride j +
  kernel)`` over the windows that end at or before the query; ``softmax_j(q
  . c_j / sqrt(d))`` a head, summed over a group's heads; a block's score
  the maximum over the windows that overlap it; block 0, the blocks of the
  last ``window_size`` positions and the best-scored others up to ``topk``.
  **By position**: a query before ``dense_len`` attends every earlier key
  (HF decides by the length of the call, which a chunked prefill cannot
  reproduce; by position the logits are the same however a prompt is
  chunked).  Served, its keys and values are a pool of two KV heads and
  its compressed keys a second pool leaf
  (``inference/paged/block_select.py:sparse_update_and_attend``).

Only the serving form and whole sequences from their first token are here;
the dense decode cache is refused (it has nowhere to keep either state).

**First values** (what ``init`` draws; a checkpoint brings its own): every
matrix lecun-normal, drawn in float32 and rounded to ``param_dtype``; the
embedding at std ``1 / sqrt(hidden)``, so ``scale_emb`` times it has RMS
0.19 and the first blocks' updates (``a`` times a unit-scale mixer) are
most of the stream from the second layer on; the head at ``hidden_size /
dim_model_base`` times lecun-normal's size, so that the ``/ 16`` before it
leaves logits of standard deviation 1, as the benchmark's other
configurations have; every norm scale 1.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import (LlamaConfig, LlamaMLP, RMSNorm,
                                        _init_kwargs, count_params,
                                        rotary_embedding)
from deepspeed_tpu.models.lfm2_moe import _FrozenDict
from deepspeed_tpu.models.ouro import in_float32
from deepspeed_tpu.ops import linear_attention
from deepspeed_tpu.ops.block_select_attention import Selection, block_mask

__all__ = ["MiniCPMSALAConfig", "MiniCPMSALAModel", "MiniCPMSALAForCausalLM",
           "get_config", "count_params"]

SPARSE, LINEAR = "minicpm4", "lightning-attn"


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig(LlamaConfig):
    # the source's keys
    mixer_types: Tuple[str, ...] = ()
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_scale: str = "1/sqrt(d)"
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    mup_denominator: int = 32          # no part in the forward pass
    rand_init: bool = False            # no part in the forward pass
    rms_norm_eps: float = 1e-6
    # not in config.json: the family's (MiniCPM4's) ``sparse_config``, and
    # the depth the residual scale is taken at (the published 32, which a
    # cut in depth does not change)
    sparse_config: Any = dataclasses.field(
        default_factory=lambda: _FrozenDict(Selection()._asdict()))
    depth_scale_layers: int = 32
    # the stack is mixed: nothing to scan over
    scan_layers: bool = False
    kernel_init: Callable = in_float32(nn.initializers.lecun_normal())

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        put("mixer_types", tuple(self.mixer_types))
        put("sparse_config", _FrozenDict(self.sparse_config))
        assert len(self.mixer_types) == self.num_hidden_layers, (
            f"mixer_types names {len(self.mixer_types)} layers, "
            f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.mixer_types) - {SPARSE, LINEAR}
        assert not unknown, f"unknown mixer_types {sorted(unknown)}"
        assert not self.scan_layers, "layers of two kinds do not scan"
        for key, want in (("lightning_scale", "1/sqrt(d)"),
                          ("lightning_use_rope", True),
                          ("attn_use_rope", False), ("qk_norm", True),
                          ("use_output_gate", True),
                          ("use_output_norm", True),
                          ("attn_use_output_gate", True)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only the published "
                    f"MiniCPM-SALA form ({want!r}) is implemented")
        assert self.lightning_nkv == self.lightning_nh, (
            "a lightning-attn layer's state is a matrix a head: "
            f"lightning_nkv {self.lightning_nkv} != {self.lightning_nh}")
        put("sliding_window", None)
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps keys and values only; a "
                "lightning-attn layer's matrix state lives per slot beside "
                "the paged pool and a minicpm4 layer chooses among pages — "
                "serve this model through RaggedInferenceEngineV2")

    @property
    def selection(self) -> Selection:
        return Selection(**self.sparse_config)

    @property
    def residual_scale(self) -> float:
        return float(self.scale_depth / np.sqrt(self.depth_scale_layers))


_SALA_TYPES = tuple(SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LINEAR
                    for i in range(32))

PRESETS = {
    "minicpm-sala": dict(
        vocab_size=73448, hidden_size=4096, intermediate_size=16384,
        num_hidden_layers=32, mixer_types=_SALA_TYPES,
        num_attention_heads=32, num_key_value_heads=2,
        max_position_embeddings=524288),
    # heads of 128 (the kernels' width under the interpreter), a selection
    # small enough for a CPU test to cross dense_len
    "tinysala": dict(
        vocab_size=96, hidden_size=256, intermediate_size=128,
        num_hidden_layers=4, mixer_types=(SPARSE, LINEAR, LINEAR, SPARSE),
        num_attention_heads=2, num_key_value_heads=1, lightning_nh=2,
        lightning_nkv=2, dim_model_base=64, max_position_embeddings=512,
        sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16,
                           init_blocks=1, window_size=32, topk=5,
                           dense_len=64)),
}


def get_config(preset: str, **overrides) -> MiniCPMSALAConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return MiniCPMSALAConfig(**kw)


def _mixer_parts(mdl, cfg, x, heads: int, kv_heads: int, d: int):
    """What both mixers share: q, k, v ``[B, S, heads, d]`` with the
    per-head QK-norm, and the output gate ``sigmoid(W_g u)``."""
    B, S, _ = x.shape
    dense = dict(use_bias=False, dtype=cfg.dtype,
                 param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
    proj = lambda name, n: nn.Dense(n * d, name=name, **dense)(x).reshape(
        B, S, n, d)
    q = norm("q_norm")(proj("q_proj", heads))
    k = norm("k_norm")(proj("k_proj", kv_heads))
    v = proj("v_proj", kv_heads)
    with jax.named_scope("gate"):
        gate = jax.nn.sigmoid(nn.Dense(heads * d, name="g_proj", **dense)(x))
    out = lambda y: nn.Dense(x.shape[-1], name="o_proj", **dense)(y)
    return q, k, v, gate, out


class LightningAttention(nn.Module):
    """Linear attention over a decaying matrix state (the module
    docstring's equations)."""

    config: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, x, positions, ragged_meta=None):
        cfg = self.config
        B, S, _ = x.shape
        H, d = cfg.lightning_nh, cfg.lightning_head_dim
        q, k, v, gate, out = _mixer_parts(self, cfg, x, H, H, d)
        rope = lambda a: rotary_embedding(
            a.transpose(0, 2, 1, 3), positions, cfg.rope_theta
        ).transpose(0, 2, 1, 3)
        q, k = rope(q), rope(k)
        q = (q.astype(jnp.float32) / np.sqrt(d)).astype(q.dtype)
        log_decay = linear_attention.head_log_decay(H)
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import ragged_linear_attention

            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
            o = ragged_linear_attention(
                self, q[0], k[0], v[0], log_decay, positions.reshape(-1),
                ragged_meta)[None]
        else:
            # whole sequences from their first token: nothing to carry
            zero = jnp.zeros((H, d, d), jnp.float32)
            o = jax.vmap(lambda q, k, v: linear_attention.recurrence(
                q, k, v, log_decay, zero)[0])(q, k, v).astype(cfg.dtype)
        o = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="o_norm")(
            o.reshape(B, S, H * d))
        return out(o * gate)


class SelectAttention(nn.Module):
    """Grouped-query attention over chosen blocks (the module docstring's
    equations)."""

    config: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, x, positions, ragged_meta=None):
        cfg = self.config
        B, S, _ = x.shape
        H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        d = cfg.head_dim
        q, k, v, gate, out = _mixer_parts(self, cfg, x, H, Hkv, d)
        sm_scale = float(1.0 / np.sqrt(d))
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import \
                sparse_update_and_attend

            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
            y = sparse_update_and_attend(
                self, q[0], k[0], v[0], positions.reshape(-1), ragged_meta,
                cfg, sm_scale=sm_scale)[None]
        else:
            y = _whole_sequence_attention(q, k, v, cfg.selection, sm_scale)
        return out(y.reshape(B, S, H * d) * gate)


def _whole_sequence_attention(q, k, v, sel: Selection, sm_scale: float):
    """``q [B, S, H, d]``, ``k, v [B, S, Hkv, d]``, every sequence from its
    first token: the chosen-block mask a query, then masked attention."""
    B, S, H, d = q.shape
    Hkv = k.shape[2]
    NB = -(-S // sel.block_size)
    NW = NB * sel.windows_per_block
    pad = NW * sel.kernel_stride + sel.kernel_size - S
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    starts = jnp.arange(NW) * sel.kernel_stride
    ckeys = jnp.mean(
        kp[:, starts[:, None] + jnp.arange(sel.kernel_size)[None]], axis=2)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    with jax.named_scope("select"):
        chosen = block_mask(q, ckeys.transpose(0, 2, 1, 3), pos, sel,
                            sm_scale)                     # [B, S, Hkv, NB]
    ok = jnp.logical_and(
        jnp.repeat(chosen, sel.block_size, axis=-1)[..., :S],
        (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None, :, None])
    qg = q.reshape(B, S, Hkv, H // Hkv, d)
    s = jnp.einsum("bqgid,bkgd->bqgik", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(jnp.where(ok[:, :, :, None], s, -jnp.inf), axis=-1)
    y = jnp.einsum("bqgik,bkgd->bqgid", p.astype(q.dtype), v)
    return y.reshape(B, S, H, d)


class MiniCPMSALABlock(nn.Module):
    config: MiniCPMSALAConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        a = jnp.asarray(cfg.residual_scale, cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        h = norm("input_layernorm")(x)
        if cfg.mixer_types[self.layer_idx] == LINEAR:
            y = LightningAttention(cfg, name="lightning_attn")(
                h, positions, ragged_meta)
        else:
            y = SelectAttention(cfg, name="self_attn")(
                h, positions, ragged_meta)
        x = x + a * y
        return x + a * LlamaMLP(cfg, name="mlp")(
            norm("post_attention_layernorm")(x))


class MiniCPMSALAModel(nn.Module):
    """Hidden states after the final norm (of the rows a fused tick
    samples, where its metadata names them)."""

    config: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.inference.common import sampled_rows

        cfg = self.config
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens",
                     embedding_init=in_float32(
                         nn.initializers.variance_scaling(
                             1.0, "fan_in", "normal", out_axis=0)))(input_ids)
        x = x * jnp.asarray(cfg.scale_emb, cfg.dtype)
        for i in range(cfg.num_hidden_layers):
            x = MiniCPMSALABlock(cfg, i, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class MiniCPMSALAForCausalLM(nn.Module):
    config: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        width = cfg.hidden_size / cfg.dim_model_base
        x = MiniCPMSALAModel(cfg, name="model")(
            input_ids, positions, deterministic, ragged_meta)
        x = (x.astype(jnp.float32) / width).astype(cfg.dtype)
        head_init = in_float32(nn.initializers.variance_scaling(
            width * width, "fan_in", "normal"))
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        kernel_init=head_init)(x)
