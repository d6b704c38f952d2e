"""GLM-4-MoE-Lite model family (zai-org GLM-4.7-Flash, ``model_type:
glm4_moe_lite``): DeepSeek-V2/V3's multi-head LATENT attention with a
low-rank query, one leading dense layer, then expert layers of a sigmoid
router with a selection bias beside one ungated shared expert.

With ``N`` RMSNorm and ``h`` the residual stream, layer ``l`` is::

    h <- h + Attn_l(N_in(h));   h <- h + FF_l(N_post(h))

- **Attention** (every layer; no bias anywhere).  ``c_q = N_q(W_qa x)``
  (``q_lora_rank``); per head ``[q_nope | q_rope] = W_qb c_q``
  (``qk_nope_head_dim | qk_rope_head_dim``).  ``[c_raw | k_r_raw] = W_kva
  x`` (``kv_lora_rank | qk_rope_head_dim``); ``c = N_kv(c_raw)``; ``k_r =
  RoPE(k_r_raw)``, ONE rotated key shared by the heads; ``q_rope <-
  RoPE(q_rope)``.  Per head ``[k_nope | v] = W_kvb c`` (``qk_nope_head_dim |
  v_head_dim``).  Scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(
  qk_nope_head_dim + qk_rope_head_dim)``, causal softmax in float32, ``o =
  W_o concat_h(sum_u p v)``.
- **The same numbers absorbed**, which is what a served token runs: with
  ``W_kvb = [W_uk | W_uv]`` a head, ``q_nope . k_nope(u) = (W_uk^T q_nope) .
  c(u)`` and ``sum_u p v(u) = W_uv (sum_u p c(u))``: the heads' queries,
  ``kv_lora_rank + qk_rope_head_dim`` wide, meet the cached row ``[c |
  k_r]``, whose first ``kv_lora_rank`` columns are also the value.  **The
  cache holds ``c`` AFTER its norm and ``k_r`` AFTER its rotation**: one row
  a token a layer, nothing per head (``inference/paged/latent.py:
  latent_update_and_attend``; the pool is ``[num_pages, page_size, W]``).
- **FF, the first** ``first_k_dense_replace`` **layers**: ``models/llama.py``'s
  SwiGLU MLP, ``intermediate_size`` wide.
- **FF, every later layer**: ``moe/layer.py``'s dropless form with the
  sigmoid router (``topk_method: noaux_tc``): ``s = sigmoid(W_g x)`` in
  float32, the chosen set the top k of ``s + b`` (``b`` the
  ``e_score_correction_bias``; ``n_group = topk_group = 1``, so no group
  step), the weights ``routed_scaling_factor * s_i / (sum_chosen s +
  1e-20)`` without the bias (``norm_topk_prob``); beside it ``n_shared_experts``
  shared experts as ONE SwiGLU of ``n_shared_experts *
  moe_intermediate_size``, ungated and unscaled, on every live row.
- ``logits = W_head N_final(h)``, untied.

RoPE is the half-split ("rotate_half") form over all ``qk_rope_head_dim``
dims (``models/llama.py:rotary_embedding``); a checkpoint whose rope columns
are interleaved pairs differs by a fixed permutation of ``q_b_proj``'s and
``kv_a_proj_with_mqa``'s rope columns.

Only the serving form and the plain forward pass are here: the expert layer
is dropless whatever the call, and the dense decode cache is refused (it
keeps per-head keys and values; the paged engine keeps latent rows).  The
multi-token-prediction block (``num_nextn_predict_layers``) is not built: it
drafts the token after next and changes no logit of the model.

**First values** (``init``; a checkpoint brings its own): every matrix
lecun-normal drawn in float32 and rounded to ``param_dtype``, the untied
head among them, so logits have standard deviation about 1; every norm
scale 1.  Two follow ``models/lfm2_moe.py``, for its reasons (a model
served AS DRAWN has to be one whose check can tell a sound program from a
wrong one): the routed experts' down-projections start at
:data:`EXPERT_DOWN_INIT_SCALE` of lecun-normal's size, which with
``routed_scaling_factor`` 1.8 gives a chosen expert the share of a layer's
update it has there (1.8 x 1/6 = 0.3), and the selection bias is drawn at
standard deviation :data:`EXPERT_BIAS_INIT_STD`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import (LlamaMLP, RMSNorm, _init_kwargs,
                                        count_params, rotary_embedding)
from deepspeed_tpu.models.mixtral import (MixtralConfig, live_rows,
                                          moe_layer)
from deepspeed_tpu.models.ouro import in_float32

__all__ = ["Glm4MoeLiteConfig", "Glm4MoeLiteModel", "Glm4MoeLiteForCausalLM",
           "get_config", "count_params"]

# first values (see the module docstring)
EXPERT_BIAS_INIT_STD = 0.03
EXPERT_DOWN_INIT_SCALE = 1.0 / 6.0
# the DeepSeek-V3 family's code adds this to the sum of the chosen scores
ROUTED_WEIGHT_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig(MixtralConfig):
    # the source's keys (``intermediate_size`` is the DENSE layers' width)
    hidden_act: str = "silu"
    moe_intermediate_size: int = 1536
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 0
    rope_scaling: Any = None
    tie_word_embeddings: bool = False
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    # the chips that share each expert layer, and which share this is: the
    # router is ``n_routed_experts * expert_shards`` wide and
    # ``n_routed_experts`` the experts HELD here (no keys of the source; the
    # published models are ``expert_shards=1``)
    expert_shards: int = 1
    expert_shard: int = 0
    # a first value (the module docstring)
    expert_down_init_scale: float = EXPERT_DOWN_INIT_SCALE
    # layers of two feed-forwards do not scan; one chip holds the stage
    scan_layers: bool = False
    expert_parallel: bool = False
    kernel_init: Callable = in_float32(nn.initializers.lecun_normal())

    def __post_init__(self):
        super().__post_init__()
        put = lambda k, v: object.__setattr__(self, k, v)
        for key, want in (("hidden_act", "silu"), ("topk_method", "noaux_tc"),
                          ("n_group", 1), ("topk_group", 1),
                          ("rope_scaling", None),
                          ("tie_word_embeddings", False),
                          ("attention_bias", False),
                          ("partial_rotary_factor", 1)):
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only {want!r} is "
                    "implemented (the published GLM-4.7-Flash's value)")
        if self.num_nextn_predict_layers:
            raise NotImplementedError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}: "
                "the multi-token-prediction block is not built (it drafts "
                "the token after next and changes no logit, and the "
                "engine has no loop that would verify a draft)")
        assert not self.scan_layers, "layers of two feed-forwards do not scan"
        assert self.qk_rope_head_dim % 2 == 0, self.qk_rope_head_dim
        # what the shared blocks read, under the names they read it by
        put("num_local_experts", int(self.n_routed_experts))
        put("sliding_window", None)
        if self.decode and not self.paged_decode:
            raise NotImplementedError(
                "the dense decode cache keeps per-head keys and values; "
                "latent attention caches one [c | k_r] row a token — serve "
                "this model through RaggedInferenceEngineV2")

    @property
    def head_dim(self) -> int:
        """A query's (and an expanded key's) width; no key of the source."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim


PRESETS = {
    "glm-4.7-flash": dict(
        vocab_size=154880, hidden_size=2048, intermediate_size=10240,
        moe_intermediate_size=1536, num_hidden_layers=47,
        num_attention_heads=20, num_key_value_heads=20, n_routed_experts=64,
        n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=1.8,
        first_k_dense_replace=1, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        rope_theta=1000000.0, max_position_embeddings=202752),
    # v and q/k widths differ, heads no power of two, a dense layer first
    "tinyglm": dict(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=3, num_key_value_heads=3, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, max_position_embeddings=256),
}


def get_config(preset: str, **overrides) -> Glm4MoeLiteConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return Glm4MoeLiteConfig(**kw)


class LatentParts(NamedTuple):
    """What a latent-attention module of this lineage projects from its
    input (:func:`latent_parts`): the query latent ``c_q [B, S,
    q_lora_rank]``; ``queries(c_q, positions)``, every head's ``(q_nope [B,
    H, n, dn], q_rope [B, H, n, dr])`` of ``n`` of its rows, heads first and
    rotated; ``q``, that pair of ALL its rows (None where the caller asks
    for none); the normalised latent ``c [B, S, rank]`` and the one rotated key
    ``k_r [B, S, dr]`` the heads share; the up-projection ``w_kvb [rank, H,
    dn + dv]``; and ``out``, the output projection of ``[..., H, dv]``."""
    c_q: jax.Array
    queries: Callable
    q: Any
    c: jax.Array
    k_r: jax.Array
    w_kvb: jax.Array
    out: Callable


def latent_parts(mdl: nn.Module, x, positions,
                 all_queries: bool = True) -> LatentParts:
    """The projections of the module docstring's attention, made inside
    ``mdl`` (a compact module whose ``config`` is of this lineage) under the
    names a checkpoint gives them; ``all_queries``: every row's queries
    among them, made where this lineage's first family traced them (a
    caller that gathers its rows first asks for none)."""
    cfg = mdl.config
    B, S, E = x.shape
    H = cfg.num_attention_heads
    dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.v_head_dim)
    rank = cfg.kv_lora_rank
    dense = dict(use_bias=False, dtype=cfg.dtype,
                 param_dtype=cfg.param_dtype, **_init_kwargs(cfg))
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
    c_q = norm("q_a_layernorm")(
        nn.Dense(cfg.q_lora_rank, name="q_a_proj", **dense)(x))
    q_b_proj = nn.Dense(H * (dn + dr), name="q_b_proj", **dense)

    def queries(c_q, positions):
        q = q_b_proj(c_q).reshape(B, -1, H, dn + dr).transpose(0, 2, 1, 3)
        return q[..., :dn], rotary_embedding(q[..., dn:], positions,
                                             cfg.rope_theta)

    q = queries(c_q, positions) if all_queries else None
    kv_a = nn.Dense(rank + dr, name="kv_a_proj_with_mqa", **dense)(x)
    c = norm("kv_a_layernorm")(kv_a[..., :rank])        # [B, S, rank]
    k_r = rotary_embedding(kv_a[..., rank:][:, None], positions,
                           cfg.rope_theta)[:, 0]        # [B, S, dr]
    w_kvb = mdl.param("kv_b_proj", cfg.kernel_init,
                      (rank, H * (dn + dv)), cfg.param_dtype)
    w_kvb = w_kvb.astype(cfg.dtype).reshape(rank, H, dn + dv)
    o_proj = nn.Dense(E, name="o_proj", **dense)
    out = lambda y: o_proj(y.reshape(y.shape[:-2] + (H * dv,)))
    return LatentParts(c_q, queries, q, c, k_r, w_kvb, out)


def expanded_attention(cfg, q_nope, q_rope, c, k_r, w_kvb, ok):
    """Whole sequences from their first token, expanded and nothing cached:
    ``q_nope [B, H, S, dn]``, ``q_rope [B, H, S, dr]`` over the keys and
    values ``W_kvb c`` gives every head, under the mask ``ok [..., S, S]``
    (a query down, a key across) -> ``[B, S, H, dv]``."""
    dn = cfg.qk_nope_head_dim
    with jax.named_scope("kv_b"):
        kv = jnp.einsum("bsc,chd->bhsd", c, w_kvb)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("bhqn,bhkn->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhqr,bkr->bhqk", q_rope, k_r,
                           preferred_element_type=jnp.float32))
    p = jax.nn.softmax(
        jnp.where(ok, scores * softmax_scale(cfg), -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkv->bqhv", p.astype(cfg.dtype), v)


def softmax_scale(cfg) -> float:
    return float(1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))


class Glm4MoeLiteAttention(nn.Module):
    """Multi-head latent attention (the module docstring's equations):
    expanded where whole sequences are run, absorbed over the paged latent
    rows where the engine serves."""

    config: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        B, S, _ = x.shape
        H = cfg.num_attention_heads
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank = cfg.kv_lora_rank
        c_q, queries, (q_nope, q_rope), c, k_r, w_kvb, out = latent_parts(
            self, x, positions)
        sm_scale = softmax_scale(cfg)

        if cfg.paged_decode:
            from deepspeed_tpu.inference.paged import (
                latent_update_and_attend, latent_width)
            from deepspeed_tpu.ops.latent_attention import tick_rows

            assert ragged_meta is not None and B == 1, (
                "paged token batches are [1, T] with the engine's "
                "ragged_meta")
            with jax.named_scope("absorb"):
                w_k, w_v = w_kvb[..., :dn], w_kvb[..., dn:]
            rows = jnp.concatenate([c[0], k_r[0]], axis=-1)    # [S, W]
            attend = functools.partial(
                latent_update_and_attend, self, rows=rows,
                ragged_meta=ragged_meta, cfg=cfg, value_width=rank,
                sm_scale=sm_scale)

            def tokens_first(q_nope, q_rope):
                """Every head's absorbed query, ``[n, H, rank + dr]``."""
                with jax.named_scope("absorb"):
                    q_c = jnp.einsum("hsn,chn->shc", q_nope[0], w_k)
                return jnp.concatenate(
                    [q_c, q_rope[0].transpose(1, 0, 2)], axis=-1)

            if ragged_meta.get("fused_tick") is True:
                # a fused tick's kernel takes q HEADS FIRST as it is held
                # here and answers as the up-projection reads: the tick's
                # rows grow to whole sublane tiles while they are ``dn`` and
                # ``dr`` wide, the absorbed query is born beside its
                # rotated part and the row's padding, and nothing as large
                # as ``T x H x W`` is turned or padded on either side
                Tp = tick_rows(S)
                grow = lambda a: jnp.pad(                   # noqa: E731
                    a, ((0, 0), (0, Tp - S), (0, 0)))
                with jax.named_scope("absorb"):
                    q_c = jnp.einsum("hsn,chn->hsc", grow(q_nope[0]), w_k)
                q_abs = jnp.concatenate([q_c, grow(q_rope[0]), jnp.zeros(
                    (H, Tp, latent_width(cfg) - rank - dr), q_c.dtype)],
                    axis=-1)                                   # [H, Tp, W]
                # the first row of every packed sequence again, tokens
                # first as the decode kernel takes the sequences of ONE
                # query row: from the rows of ``c_q`` (a gather along the
                # tokens of anything held heads first has XLA turn it)
                first = jnp.minimum(ragged_meta["cu_q_lens"][:-1], S - 1)
                q_one = tokens_first(*queries(
                    jnp.take(c_q, first, axis=1),
                    jnp.take(positions, first, axis=-1)))
                o_c, o_one, at = attend(q=(q_abs, q_one))
                with jax.named_scope("absorb"):
                    y = jnp.einsum("htc,chv->thv", o_c, w_v)
                    y_one = jnp.einsum("shc,chv->shv", o_one, w_v)
                # the sequences of one query row join where the rows are
                # narrowest, behind the output projection
                return out(y)[:S].at[at].set(out(y_one), mode="drop")[None]
            o_c = attend(q=tokens_first(q_nope, q_rope))       # [S, H, rank]
            with jax.named_scope("absorb"):
                y = jnp.einsum("shc,chv->shv", o_c, w_v)
            return out(y[None])

        # whole sequences from their first token: expanded, nothing cached
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        return out(expanded_attention(cfg, q_nope, q_rope, c, k_r, w_kvb,
                                      causal))


def routed_experts(cfg: Glm4MoeLiteConfig, name: str = "mlp"):
    """The routed half of an expert layer as the module docstring has it
    (``moe/layer.py``'s dropless form under the ``noaux_tc`` router), of the
    share of the router's experts ``cfg`` holds."""
    return moe_layer(
        cfg, name, intermediate_size=cfg.moe_intermediate_size,
        normalize_weights=cfg.norm_topk_prob, scoring="sigmoid",
        expert_bias=True, expert_bias_init_std=EXPERT_BIAS_INIT_STD,
        down_init_scale=cfg.expert_down_init_scale,
        routed_scaling_factor=cfg.routed_scaling_factor,
        routed_weight_eps=ROUTED_WEIGHT_EPS,
        expert_shards=cfg.expert_shards, expert_shard=cfg.expert_shard,
        dispatch_impl="grouped")


class Glm4MoeLiteBlock(nn.Module):
    config: Glm4MoeLiteConfig
    layer_idx: int
    # the lineage's other family brings its own (``models/glm_moe_dsa.py``)
    attention: Callable = Glm4MoeLiteAttention

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + self.attention(cfg, name="self_attn")(
            norm("input_layernorm")(x), positions, deterministic,
            ragged_meta)
        h = norm("post_attention_layernorm")(x)
        if self.layer_idx < cfg.first_k_dense_replace:
            return x + LlamaMLP(cfg, name="mlp")(h)
        live = live_rows(ragged_meta)
        y, _ = routed_experts(cfg)(h, live=live)
        if cfg.n_shared_experts:
            # ungated, unscaled, on every live row; padding rows and
            # inactive slots stay zeros as the routed path leaves them
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


class Glm4MoeLiteModel(nn.Module):
    """Hidden states after the final norm."""

    config: Glm4MoeLiteConfig
    attention: Callable = Glm4MoeLiteAttention
    embedding_init: Callable = in_float32(nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", out_axis=0))

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.inference.common import sampled_rows

        cfg = self.config
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens",
                     embedding_init=self.embedding_init)(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = Glm4MoeLiteBlock(cfg, i, self.attention, name=f"layers_{i}")(
                x, positions, deterministic, ragged_meta)
        # a fused tick's head rows, before the row-wise final norm
        x = sampled_rows(x, ragged_meta)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class Glm4MoeLiteForCausalLM(nn.Module):
    config: Glm4MoeLiteConfig
    # the body, which the lineage's other family gives its own attention
    # and first values
    body: Callable = Glm4MoeLiteModel

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        x = self.body(cfg, name="model")(
            input_ids, positions, deterministic, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        **_init_kwargs(cfg))(x)
