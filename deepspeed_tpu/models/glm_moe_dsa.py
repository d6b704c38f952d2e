"""GLM-MoE-DSA model family (zai-org GLM-5, ``model_type: glm_moe_dsa``):
``models/glm4_moe_lite.py``'s block (multi-head LATENT attention with a
low-rank query, leading dense layers, then expert layers of a sigmoid router
with a selection bias beside one ungated shared expert) whose attention
reads only the cached latent rows a lightning indexer chooses, token by
token (DeepSeek-V3.2's sparse attention INSIDE the latent pool).

A layer is that file's, with one step more in its attention:

- **Attention**: ``c_q``, every head's ``[q_nope | q_rope]``, ``c``, ``k_r``
  and ``W_kvb`` as there (:func:`~deepspeed_tpu.models.glm4_moe_lite.
  latent_parts`); scale ``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``
  (the source's ``qk_head_dim``).  The softmax of query ``t`` runs over the
  set ``S(t)`` the indexer chooses and no other key.
- **Indexer** (``index_n_heads`` x ``index_head_dim``, ``index_topk``): ``qI
  = W_Iq c_q``, from the QUERY LATENT, not from the layer's input; ``kI =
  LayerNorm(W_Ik x)`` (scale and bias, eps :data:`INDEX_NORM_EPS`), ONE key a
  token; RoPE (half-split, the attention's theta) on the FIRST
  ``qk_rope_head_dim`` dims of both, the rest as they are; ``w = W_w x *
  HI^(-1/2) * dI^(-1/2)`` in float32.  ``I(t, s) = sum_j w(t, j) relu(qI(t,
  j) . kI(s))`` in float32; ``S(t)`` = the ``index_topk`` keys ``s <= t`` of
  largest ``I`` (ties to the lower ``s``; every ``s <= t`` where ``t + 1 <=
  index_topk``): a rule BY QUERY, so a prompt gives the same logits however
  it is chunked, and exact (``ops/token_select_attention.py``).  V3.2's
  Hadamard rotation and fp8 of ``qI``, ``kI`` are an orthogonal change of
  basis plus a quantisation: served in bf16 here, left out.
- **Served**, a layer keeps two pool leaves, the latent row ``[c | k_r]``
  and the index key (``inference/paged/latent_select.py``): a prompt
  chunk's rows take ``index_select`` and a masked pass of the absorbed form
  over the latent pages, a decode row scores its pages, chooses, and gathers
  its ``index_topk`` rows by token (``ops/latent_select_attention.py``).
- **Experts**: the lineage's ``noaux_tc`` router.  ``n_routed_experts`` is
  the number of experts HELD here: share ``expert_shard`` of the
  ``expert_shards`` chips that share each layer (the published model is
  ``n_routed_experts=256, expert_shards=1``); the router is ``n_routed_experts
  * expert_shards`` wide, a copy routed to an absent expert adds nothing,
  and nothing stands in for the exchange (``moe/sharded_moe.py:held_moe``).

``rope_interleave`` / ``indexer_rope_interleave`` (the source pairs a rope
dim with its neighbour): the half-split form is applied here, which with
weights drawn from a seed differs by a fixed permutation of ``q_b_proj``'s,
``kv_a_proj_with_mqa``'s and the indexer's rope columns.  The
multi-token-prediction block (``num_nextn_predict_layers``) is not built,
as in the lineage.

**First values** (``init``; a checkpoint brings its own): the lineage's
(every matrix lecun-normal drawn in float32, the selection bias at standard
deviation 0.03), but for two.  **The embedding at** :data:`EMBED_INIT_STD`
**(1.0) a value**, as ``models/keye_vl2.py`` draws it and for its reason:
attention over thousands of seeded keys returns nearly one mean value row to
every query, and at ``1 / sqrt(hidden)`` a token's own row is lost under
what the first block adds, so the streams of different tokens collapse and
no check can tell a choice of keys from its absence.  **The routed experts'
down-projections at** :data:`EXPERT_DOWN_INIT_SCALE` of lecun-normal's
size: with ``routed_scaling_factor`` 2.5 a chosen expert's share of a
layer's update is the 0.3 it has in the lineage.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                Glm4MoeLiteForCausalLM,
                                                Glm4MoeLiteModel,
                                                count_params,
                                                expanded_attention,
                                                latent_parts, softmax_scale)
from deepspeed_tpu.models.lfm2_moe import _FrozenDict
from deepspeed_tpu.models.llama import _init_kwargs, rotary_embedding
from deepspeed_tpu.models.ouro import in_float32
from deepspeed_tpu.ops.token_select_attention import Indexer, token_mask

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaModel", "GlmMoeDsaForCausalLM",
           "get_config", "count_params"]

# first values (see the module docstring)
EMBED_INIT_STD = 1.0
EXPERT_DOWN_INIT_SCALE = 0.12
# the indexer's LayerNorm (DeepSeek-V3.2's own: no key of the source)
INDEX_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig(Glm4MoeLiteConfig):
    # the source's keys beside the lineage's
    index_head_dim: int = 128
    index_n_heads: int = 32
    index_topk: int = 2048
    indexer_rope_interleave: bool = True
    rope_interleave: bool = True
    qk_head_dim: int = 256
    moe_layer_freq: int = 1
    scoring_func: str = "sigmoid"
    ep_size: int = 1
    rope_parameters: Any = dataclasses.field(
        default_factory=lambda: _FrozenDict(rope_theta=1000000,
                                            rope_type="default"))
    routed_scaling_factor: float = 2.5
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    expert_down_init_scale: float = EXPERT_DOWN_INIT_SCALE

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        put("rope_parameters", _FrozenDict(self.rope_parameters))
        for key, want in (("moe_layer_freq", 1), ("scoring_func", "sigmoid"),
                          ("qk_head_dim", self.head_dim)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only {want!r} is "
                    "implemented (the published GLM-5's value)")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"rope_parameters={dict(self.rope_parameters)!r}: only the "
                "default rotary embedding is implemented")
        put("rope_theta", float(self.rope_parameters["rope_theta"]))
        assert self.index_head_dim >= self.qk_rope_head_dim, (
            self.index_head_dim, self.qk_rope_head_dim)

    @property
    def indexer(self) -> Indexer:
        """The indexer as ``ops/token_select_attention.py`` and the cache
        kind read it."""
        return Indexer(indexer_num_heads=self.index_n_heads,
                       indexer_head_dim=self.index_head_dim,
                       indexer_num_kv_heads=1, topk=self.index_topk)


PRESETS = {
    "glm-5": dict(
        vocab_size=154880, hidden_size=6144, intermediate_size=12288,
        moe_intermediate_size=2048, num_hidden_layers=78,
        num_attention_heads=64, num_key_value_heads=64, n_routed_experts=256,
        n_shared_experts=1, num_experts_per_tok=8, routed_scaling_factor=2.5,
        first_k_dense_replace=3, q_lora_rank=2048, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        max_position_embeddings=202752),
    # a selection small enough for a CPU test to cross topk; v and q/k
    # widths differ, heads no power of two, a dense layer first, and 4 of
    # the router's 8 experts held
    "tinyglm5": dict(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=3, num_key_value_heads=3, n_routed_experts=4,
        expert_shards=2, n_shared_experts=1, num_experts_per_tok=2,
        first_k_dense_replace=1, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12, qk_head_dim=24,
        index_head_dim=16, index_n_heads=2, index_topk=24,
        max_position_embeddings=512),
}


def get_config(preset: str, **overrides) -> GlmMoeDsaConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return GlmMoeDsaConfig(**kw)


def _rope_first(x, positions, theta, dims: int):
    """``x [B, S, heads, d]`` with its first ``dims`` dims rotated."""
    turned = rotary_embedding(x[..., :dims].transpose(0, 2, 1, 3), positions,
                              theta).transpose(0, 2, 1, 3)
    return jnp.concatenate([turned, x[..., dims:]], axis=-1)


class LatentIndexer(nn.Module):
    """``(qI [B, S, HI, dI], kI [B, S, dI], w [B, S, HI] float32)`` of the
    module docstring's indexer, from the layer's input ``x`` and the query
    latent ``c_q``."""

    config: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x, c_q, positions):
        cfg = self.config
        sa = cfg.indexer
        B, S, _ = x.shape
        HI, dI = sa.indexer_num_heads, sa.indexer_head_dim
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
        qI = nn.Dense(HI * dI, name="wq_b", **dense)(c_q).reshape(
            B, S, HI, dI)
        kI = nn.LayerNorm(epsilon=INDEX_NORM_EPS, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="k_norm")(
            nn.Dense(dI, name="wk", **dense)(x))
        # the head weights in float32 (thirty-two numbers a token: a bf16
        # rounding of each would move every score by a part in 300)
        w = nn.Dense(HI, name="weights_proj", **dict(
            dense, dtype=jnp.float32))(x)
        dr = cfg.qk_rope_head_dim
        qI = _rope_first(qI, positions, cfg.rope_theta, dr)
        kI = _rope_first(kI[:, :, None], positions, cfg.rope_theta, dr)[:, :,
                                                                        0]
        return qI, kI, w * sa.head_scale


class GlmMoeDsaAttention(nn.Module):
    """Latent attention over the rows the indexer chooses (the module
    docstring's equations): expanded under the chosen-key mask where whole
    sequences are run, absorbed over the paged latent rows where the engine
    serves."""

    config: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        B, S, E = x.shape
        H = cfg.num_attention_heads
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank = cfg.kv_lora_rank
        c_q, queries, q, c, k_r, w_kvb, out = latent_parts(
            self, x, positions, all_queries=not cfg.paged_decode)
        qI, kI, w = LatentIndexer(cfg, name="indexer")(x, c_q, positions)

        if not cfg.paged_decode:
            # whole sequences from their first token: expanded, nothing
            # cached, under the mask of the keys each query chooses
            seen = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None]
            with jax.named_scope("index"):
                s = jnp.einsum("bqhd,bkd->bqhk", qI, kI,
                               preferred_element_type=jnp.float32)
                score = jnp.einsum("bqh,bqhk->bqk", w, jnp.maximum(s, 0.0))
            with jax.named_scope("select"):
                ok = token_mask(score, jnp.broadcast_to(seen, score.shape),
                                cfg.index_topk)
            return out(expanded_attention(cfg, *q, c, k_r, w_kvb,
                                          ok[:, None]))

        from deepspeed_tpu.inference.paged import (
            chosen_latent_update_and_attend, latent_width)
        from deepspeed_tpu.ops.linear_attention import from_tiles

        assert ragged_meta is not None and B == 1, (
            "paged token batches are [1, T] with the engine's ragged_meta")
        with jax.named_scope("absorb"):
            w_k, w_v = w_kvb[..., :dn], w_kvb[..., dn:]
        pad = latent_width(cfg) - rank - dr

        def absorbed(take, heads_first: bool):
            """Every head's absorbed query of the tick's rows ``take [n]``
            (``S``: no row), as wide as the pool's row: from the rows of
            ``c_q``, so what is gathered is ``q_lora_rank`` wide."""
            q_nope, q_rope = queries(
                jnp.take(c_q, take, axis=1, mode="fill", fill_value=0),
                jnp.take(positions.reshape(-1), take, mode="fill",
                         fill_value=0))
            form = "hsc" if heads_first else "shc"
            with jax.named_scope("absorb"):
                q_c = jnp.einsum(f"hsn,chn->{form}", q_nope[0], w_k)
            q_r = q_rope[0] if heads_first else q_rope[0].transpose(1, 0, 2)
            return jnp.concatenate(
                [q_c, q_r, jnp.zeros(q_c.shape[:2] + (pad,), q_c.dtype)],
                axis=-1)

        got = chosen_latent_update_and_attend(
            self, absorbed, jnp.concatenate([c[0], k_r[0]], axis=-1), qI[0],
            kI[0], w[0], positions.reshape(-1), ragged_meta, cfg,
            value_width=rank, sm_scale=softmax_scale(cfg))
        if ragged_meta.get("one_query_a_slot") is True:
            with jax.named_scope("absorb"):
                y = jnp.einsum("shc,chv->shv", got, w_v)
            return out(y[None])
        o_tiles, tile_rows, o_one, at = got
        with jax.named_scope("absorb"):
            y_t = jnp.einsum("htc,chv->thv", o_tiles, w_v)
            y_one = jnp.einsum("shc,chv->shv", o_one, w_v)
        # the tiles' rows back in the tick's order while they are ``H * dv``
        # wide; the sequences of one query row join behind the output
        # projection, where the rows are narrowest
        y = from_tiles(y_t.reshape(y_t.shape[0], -1), tile_rows, S)
        return out(y.reshape(S, H, -1)).at[at].set(
            out(y_one), mode="drop")[None]


class GlmMoeDsaModel(Glm4MoeLiteModel):
    """Hidden states after the final norm: the lineage's body with this
    family's attention and embedding."""

    config: GlmMoeDsaConfig
    attention: Callable = GlmMoeDsaAttention
    embedding_init: Callable = in_float32(
        nn.initializers.normal(EMBED_INIT_STD))


class GlmMoeDsaForCausalLM(Glm4MoeLiteForCausalLM):
    config: GlmMoeDsaConfig
    body: Callable = GlmMoeDsaModel
