"""Keye-VL-2.0 language model (Kwai-Keye ``Keye-VL-2.0-30B-A3B``,
``model_type: KeyeVL2``): a Qwen3-MoE-shaped block whose attention reads
only the keys a lightning indexer chooses, token by token.

With ``N`` RMSNorm (eps ``rms_norm_eps``, learned scale), ``h`` the residual
stream, no biases::

    h <- h + W_o att(N(h));    h <- h + MoE(N(h));    logits = W_head N(h)

- **Attention** over ``num_attention_heads`` query heads and
  ``num_key_value_heads`` KV heads of ``head_dim`` (a key of the config:
  ``hidden_size / heads`` it is not): ``q, k, v = W_q u, W_k u, W_v u``;
  RMSNorm with a learned ``head_dim``-vector over EACH HEAD of ``q`` and of
  ``k`` (``LlamaConfig.qk_norm_per_head``'s form); RoPE half-split,
  ``rope_theta``.  ``rope_scaling.mrope_section`` splits the frequency pairs
  among three position rows (t, h, w); a text token's three positions are
  equal, and then it IS plain RoPE, which is what is applied here (the
  benchmark's plain reference applies the sections to three equal rows, and
  a test holds the two equal).
- **Indexer** (``sa_config``; DeepSeek-V3.2's lightning indexer on a GQA
  model, so from ``u``): ``qI = W_qI u`` (``indexer_num_heads`` x
  ``indexer_head_dim``); ``kI = LayerNorm(W_kI u)`` (ONE key a token, scale
  and bias); RoPE half-split over the whole of both, same theta; ``w = W_w
  u * HI^(-1/2) * dI^(-1/2)``.  ``I(t, s) = sum_j w(t, j) relu(qI(t, j) .
  kI(s))`` in float32.  A query attends the ``topk`` keys ``s <= t`` of
  largest ``I`` (ties to the lower ``s``; every ``s <= t`` where ``t + 1 <=
  topk``): a rule BY QUERY, so a prompt gives the same logits however it is
  chunked, and exact (``ops/token_select_attention.py``).  V3.2's Hadamard
  rotation and fp8 of ``qI``, ``kI`` are an orthogonal change of basis plus
  a quantisation: served in bf16 here, left out.  ``q_chunk_size`` /
  ``kv_chunk_size`` are the source kernel's tiling and take no part.
- **MoE**: ``p = softmax(W_r u)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest, divided by their sum (``norm_topk_prob``);
  SwiGLU experts of width ``moe_intermediate_size``; no shared expert.
  ``intermediate_size`` (a dense layer's width) is unused: every layer is
  sparse (``decoder_sparse_step`` 1, ``mlp_only_layers`` empty).  The FFN
  half is :class:`~deepspeed_tpu.models.qwen2_moe.Qwen2MoeBlock`'s as OLMoE
  takes it (``moe_layer``, dropless on the grouped path when served).

**The vision tower is not here**: image tokens would enter as embedding rows
with three-row positions.  Only the serving form and whole sequences from
their first token are; the dense decode cache is refused (it has nowhere to
keep the index keys).  Served, a layer's keys and values and its index keys
are two pool leaves (``inference/paged/index_select.py``).

**First values** (what ``init`` draws; a checkpoint brings its own): flax's
own lecun normal for every matrix, every norm scale 1, the LayerNorm's bias
0, and two that are not flax's.  **The embedding at**
:data:`EMBED_INIT_STD` **(1.0) a value**, where flax draws ``1/sqrt(hidden)``:
at flax's size a token's own row is a fiftieth of what the first block adds
to it, attention over thousands of keys drawn from a seed returns nearly the
same mean value row to every query, and after six layers the streams of
different tokens have collapsed onto one another (on the chip the reference
picked 3 distinct tokens at 16 positions after a 12,300-token prompt, and a
rounding of every matmul operand to e4m3 moved none of them on some seeds:
PERF.md section 6, PR 47).  At 1.0 the token's row is the larger part of its
stream, 48 positions pick 48 tokens, and the sound program, the e4m3
reference and the reference with the selection left out read apart.  **The
experts' down-projections at** :data:`EXPERT_DOWN_INIT_SCALE` **(0.3) of
lecun-normal's size**, as ``models/lfm2_moe.py`` draws them: the larger the
experts' share of the stream, the more a token that the program routes
otherwise than the float32 reference does (its eighth and ninth router
values within bfloat16's noise of each other) moves the logits.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.lfm2_moe import _FrozenDict
from deepspeed_tpu.models.llama import (RMSNorm, _init_kwargs,
                                        rotary_embedding)
from deepspeed_tpu.models.mixtral import live_rows, moe_layer
from deepspeed_tpu.models.qwen2_moe import Qwen2MoeConfig, count_params
from deepspeed_tpu.ops.token_select_attention import Indexer, token_mask

__all__ = ["KeyeVL2Config", "KeyeVL2Model", "KeyeVL2ForCausalLM",
           "get_config", "count_params"]


# first values (see the module docstring)
EMBED_INIT_STD = 1.0
EXPERT_DOWN_INIT_SCALE = 0.3


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config(Qwen2MoeConfig):
    # the source's keys
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    attention_bias: bool = False
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    rope_scaling: Any = dataclasses.field(
        default_factory=lambda: _FrozenDict(
            mrope_section=(16, 24, 24), rope_type="default", type="default"))
    sa_config: Any = dataclasses.field(
        default_factory=lambda: _FrozenDict(Indexer()._asdict()))
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    # not in config.json: the Qwen3 family's QK-norm, a head at a time
    qk_norm: bool = True
    qk_norm_per_head: bool = True
    # the fused step threads metadata a scan carry cannot
    scan_layers: bool = False

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        put("mlp_only_layers", tuple(self.mlp_only_layers))
        put("rope_scaling", _FrozenDict(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in dict(self.rope_scaling).items()))
        put("sa_config", _FrozenDict(self.sa_config))
        # the shared expert layer reads the Mixtral lineage's name
        put("num_local_experts", self.num_experts)
        for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", ()),
                          ("use_sliding_window", False),
                          ("tie_word_embeddings", False),
                          ("attention_bias", False), ("qk_norm", True),
                          ("qk_norm_per_head", True),
                          ("shared_expert_intermediate_size", 0)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only the published "
                    f"Keye-VL-2.0 form ({want!r}) is implemented")
        sections = self.rope_scaling.get("mrope_section")
        assert sections is None or 2 * sum(sections) == self.head_dim, (
            f"mrope_section {sections} does not split head_dim "
            f"{self.head_dim}'s frequency pairs")
        assert self.indexer.indexer_num_kv_heads == 1, (
            "one index key a token: indexer_num_kv_heads "
            f"{self.indexer.indexer_num_kv_heads}")
        put("sliding_window", None)
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps keys and values only; this "
                "model's index keys are a leaf of the paged pool and its "
                "choice is among the pool's tokens — serve it through "
                "RaggedInferenceEngineV2")

    @property
    def indexer(self) -> Indexer:
        return Indexer(**self.sa_config)


PRESETS = {
    "keye-vl2-30b-a3b": dict(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144,
        num_hidden_layers=48, num_attention_heads=32, num_key_value_heads=4,
        max_position_embeddings=262144),
    # a selection small enough for a CPU test to cross topk
    "tinykeye": dict(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, num_experts=8,
        num_experts_per_tok=2, max_position_embeddings=512,
        rope_scaling=dict(mrope_section=(4, 6, 6), rope_type="default",
                          type="default"),
        sa_config=dict(indexer_num_heads=2, indexer_head_dim=16,
                       indexer_num_kv_heads=1, topk=24, q_chunk_size=512,
                       kv_chunk_size=512)),
}


def get_config(preset: str, **overrides) -> KeyeVL2Config:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return KeyeVL2Config(**kw)


def _rope(x, positions, theta):
    """``x [B, S, heads, d]`` rotated over the whole of ``d``."""
    return rotary_embedding(x.transpose(0, 2, 1, 3), positions,
                            theta).transpose(0, 2, 1, 3)


class LightningIndexer(nn.Module):
    """``(qI [B, S, HI, dI], kI [B, S, dI], w [B, S, HI] float32)`` of the
    module docstring's indexer."""

    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        sa = cfg.indexer
        B, S, _ = x.shape
        HI, dI = sa.indexer_num_heads, sa.indexer_head_dim
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        qI = nn.Dense(HI * dI, name="q_proj", **dense)(x).reshape(
            B, S, HI, dI)
        kI = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="k_norm")(
            nn.Dense(dI, name="k_proj", **dense)(x))
        # the head weights in float32 (sixteen numbers a token: a bf16
        # rounding of each would move every score by a part in 300)
        w = nn.Dense(HI, name="weights_proj", **dict(
            dense, dtype=jnp.float32))(x)
        qI = _rope(qI, positions, cfg.rope_theta)
        kI = _rope(kI[:, :, None], positions, cfg.rope_theta)[:, :, 0]
        return qI, kI, w * sa.head_scale


class IndexedAttention(nn.Module):
    """Grouped-query attention over the tokens the indexer chooses."""

    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x, positions, ragged_meta=None):
        cfg = self.config
        B, S, E = x.shape
        H, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        proj = lambda name, n: nn.Dense(n * d, name=name, **dense)(
            x).reshape(B, S, n, d)
        q = _rope(norm("q_norm")(proj("q_proj", H)), positions,
                  cfg.rope_theta)
        k = _rope(norm("k_norm")(proj("k_proj", Hkv)), positions,
                  cfg.rope_theta)
        v = proj("v_proj", Hkv)
        qI, kI, w = LightningIndexer(cfg, name="indexer")(x, positions)
        sm_scale = float(1.0 / np.sqrt(d))
        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import \
                tokensel_update_and_attend

            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
            y = tokensel_update_and_attend(
                self, q[0], k[0], v[0], qI[0], kI[0], w[0],
                positions.reshape(-1), ragged_meta, cfg,
                sm_scale=sm_scale)[None]
        else:
            y = _whole_sequence_attention(q, k, v, qI, kI, w,
                                          cfg.indexer.topk, sm_scale)
        return nn.Dense(E, name="o_proj", **dense)(y.reshape(B, S, H * d))


def _whole_sequence_attention(q, k, v, qI, kI, w, topk: int,
                              sm_scale: float):
    """``q [B, S, H, d]``, ``k, v [B, S, Hkv, d]``, ``qI [B, S, HI, dI]``,
    ``kI [B, S, dI]``, ``w [B, S, HI]``, every sequence from its first
    token: the chosen-token mask a query, then masked attention."""
    B, S, H, d = q.shape
    Hkv = k.shape[2]
    seen = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None]
    with jax.named_scope("index"):
        s = jnp.einsum("bqhd,bkd->bqhk", qI, kI,
                       preferred_element_type=jnp.float32)
        score = jnp.einsum("bqh,bqhk->bqk", w, jnp.maximum(s, 0.0))
    with jax.named_scope("select"):
        ok = token_mask(score, jnp.broadcast_to(seen, score.shape), topk)
    qg = q.reshape(B, S, Hkv, H // Hkv, d)
    s = jnp.einsum("bqgid,bkgd->bqgik", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(jnp.where(ok[:, :, None, None], s, -jnp.inf), axis=-1)
    y = jnp.einsum("bqgik,bkgd->bqgid", p.astype(q.dtype), v)
    return y.reshape(B, S, H, d)


class KeyeVL2Block(nn.Module):
    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x)
        x = x + IndexedAttention(cfg, name="self_attn")(h, positions,
                                                        ragged_meta)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm")(x)
        y, l_aux = moe_layer(
            cfg, "mlp", intermediate_size=cfg.expert_intermediate,
            normalize_weights=cfg.norm_topk_prob,
            down_init_scale=EXPERT_DOWN_INIT_SCALE)(
                h, live=live_rows(ragged_meta))
        return x + y, l_aux


class KeyeVL2Model(nn.Module):
    """``(hidden states after the final norm, mean-per-layer aux loss)``;
    of the rows a fused tick samples, where its metadata names them."""

    config: KeyeVL2Config

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.inference.common import sampled_rows

        cfg = self.config
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=nn.initializers.normal(EMBED_INIT_STD),
                     name="embed_tokens")(input_ids)
        aux = jnp.asarray(0.0, jnp.float32)
        for i in range(cfg.num_hidden_layers):
            x, l_aux = KeyeVL2Block(cfg, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
            aux = aux + l_aux
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return x, aux / cfg.num_hidden_layers


class KeyeVL2ForCausalLM(nn.Module):
    config: KeyeVL2Config

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x, aux = KeyeVL2Model(cfg, name="model")(input_ids, positions,
                                                 deterministic, ragged_meta)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="lm_head")(x)
        return logits, aux
