"""Ouro model family (ByteDance Ouro-1.4B / 2.6B "LoopLM"; arXiv:2510.25741).

A looped language model: the WHOLE stack of decoder blocks runs
``total_ut_steps`` times a token with the same weights, the final RMSNorm
applied at the end of every pass and its output fed to the next one.  What
the loop changes, from the published ``config.json`` and HF
``modeling_ouro.py``:

- **four RMSNorms a block** ("sandwich" normalisation): ``x + N2(Attn(N1
  x))`` then ``x + N4(SwiGLU(N3 x))`` (HF's ``input_layernorm``,
  ``input_layernorm_2``, ``post_attention_layernorm``,
  ``post_attention_layernorm_2``);
- **keys and values per (pass, layer)**: pass ``u`` of layer ``l`` attends
  to what the same pass of the same layer wrote for the earlier positions,
  so a cached token owns ``total_ut_steps x num_hidden_layers`` K/V rows
  (HF ``UniversalTransformerCache``).  The paged pool gives every layer a
  page of ``[total_ut_steps, page, 2 * Hkv, D]`` (``inference/paged/kv_pages.py``:
  the ``kv_slot`` entry of ``ragged_meta``);
- **an exit gate**: one ``Linear(hidden, 1)`` on every pass's output; its
  sigmoids give a distribution over the passes a token could stop at.  At
  the published ``early_exit_threshold`` 1 every token runs every pass and
  the logits are the last pass's: the gate is computed and sown
  (``loop_stats``), never acted on.  A threshold below 1 is refused.

Attention, MLP, RMSNorm and RoPE are ``models/llama.py``'s.  The pass loop
is ONE loop in the compiled program (``nn.scan`` with the weights broadcast
and the cache carried), so a program holds the blocks once however many
passes run.  Only the serving form is here (unrolled layers, the paged
pool); the training form, a scan over the layers inside the scan over the
passes, waits for its own issue (ROADMAP R9).

**First values** (what ``init`` draws; a checkpoint brings its own).  HF's
(every norm scale 1, embedding std 0.02) start a TRAINING run; a model
served as drawn is not a function of its input under them: each of the 192
block applications adds two updates the size of the whole stream, the
embedding (a fiftieth of one update) is gone after the first, and attention,
which averages the context and is scaled back to full size by its
post-norm, drives every position of every prompt to one state.  So the
embedding is drawn at the stream's own size (``EMBED_INIT_STD`` 1) and the
two post-norms start small: the MLP's at 0.2, so that the 48 updates of a
pass together (1.4) are of the order of what the pass started from, and
attention's at 0.05, a quarter of that, because its updates are the ones
that align across positions.  Found by sweeps at the published size, on
the CPU in float32 and on the chip through the engine (PERF.md section 6,
PR 32): every pass then moves the logits, bf16 holds them, and e4m3, a
pass fewer or another pass's K/V do not.

Every matrix and the embedding are DRAWN IN FLOAT32 and rounded to
``param_dtype`` (``in_float32``).  Drawn in bfloat16 directly, as flax's
defaults do under ``param_dtype=bfloat16``, jax's normal lies 1.2 % of a
standard deviation below zero (its uniform has seven bits), so every
matrix carries the same rank-one all-ones component, as large as the
random part's typical gain at these widths; 192 applications make that
one direction the whole stream, whatever the token (PERF.md section 6,
PR 32).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import (LlamaAttention, LlamaConfig,
                                        LlamaMLP, RMSNorm, count_params)

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "get_config",
           "count_params", "exit_distribution"]

# first values (see the module docstring)
EMBED_INIT_STD = 1.0
ATTN_POST_NORM_INIT = 0.05
MLP_POST_NORM_INIT = 0.2


def in_float32(init: Callable) -> Callable:
    """``init`` drawn in float32 and rounded to the dtype asked for."""
    def draw(key, shape, dtype=jnp.float32):
        return init(key, shape, jnp.float32).astype(dtype)
    return draw


_KERNEL_INIT = in_float32(nn.initializers.lecun_normal())


@dataclasses.dataclass(frozen=True)
class OuroConfig(LlamaConfig):
    # the source's keys
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    # the two norms AFTER attention and MLP.  Only the test that holds the
    # block to Llama's sets it False; no configuration does
    sandwich_norm: bool = True
    # the layers are unrolled inside the pass loop (the serving form)
    scan_layers: bool = False
    kernel_init: Callable = _KERNEL_INIT

    def __post_init__(self):
        super().__post_init__()
        assert self.total_ut_steps >= 1, self.total_ut_steps
        assert not self.scan_layers, (
            "the looped stack has its serving form only, with the layers "
            "unrolled: a scan over the layers would need the paged pool as "
            "one stacked leaf ([layers, pages, ...]) where the page "
            "operations of inference/v2/kv_state.py copy leaf[page]")
        if self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold}: exiting "
                "a token before its last pass is not implemented — later "
                "tokens would find no keys in the passes it skipped, which "
                "needs a cache rule and a reference of its own; the "
                "published value 1.0 runs every pass for every token")
        assert self.pipeline_stages <= 1 and not self.tensor_parallel, (
            "the looped stack does not compose with pipeline or tensor "
            "parallelism")
        assert not (self.decode and not self.paged_decode), (
            "the dense decode cache holds one K/V slot a layer; a looped "
            "model needs one a pass — serve it through the paged engine "
            "(RaggedInferenceEngineV2)")


PRESETS = {
    "ouro-2.6b": dict(vocab_size=49152, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=48,
                      num_attention_heads=16, num_key_value_heads=16,
                      rms_norm_eps=1e-6, rope_theta=1000000.0,
                      max_position_embeddings=65536, total_ut_steps=4,
                      early_exit_threshold=1.0),
    "tinyouro": dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, rms_norm_eps=1e-6,
                     rope_theta=1000000.0, max_position_embeddings=64,
                     total_ut_steps=3),
}


def get_config(preset: str, **overrides) -> OuroConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return OuroConfig(**kw)


def exit_distribution(lam: jax.Array) -> jax.Array:
    """Gate values ``lam [U, ...]`` (``sigmoid`` of the gate after each
    pass) -> the probability of stopping at each pass, ``[U, ...]``:
    ``p_u = lam_u * prod_{j<u}(1 - lam_j)``, and the last pass takes what
    is left."""
    if lam.shape[0] == 1:
        return jnp.ones_like(lam)
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)       # past passes 1..U-1
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]])
    return jnp.concatenate([lam[:-1] * before, stay[-1:]])


class OuroBlock(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, x, positions, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.runtime.zero import pin_activation

        cfg = self.config
        norm = lambda name, init=1.0: RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                              init, name=name)
        x = pin_activation(x)
        a = LlamaAttention(cfg, name="self_attn")(
            norm("input_layernorm")(x), positions, deterministic,
            ragged_meta)
        if cfg.sandwich_norm:
            a = norm("input_layernorm_2", ATTN_POST_NORM_INIT)(a)
        x = x + a
        m = LlamaMLP(cfg, name="mlp")(norm("post_attention_layernorm")(x))
        if cfg.sandwich_norm:
            m = norm("post_attention_layernorm_2", MLP_POST_NORM_INIT)(m)
        return x + m


def _one_pass(mdl: "OuroModel", x, u, positions, deterministic,
              ragged_meta):
    """Pass ``u``: every block once, the final norm, the gate.  Modules
    are named as in an unlooped stack (``layers_<i>``, ``norm``), so the
    passes share them."""
    from deepspeed_tpu.models.gpt2 import _maybe_remat

    cfg = mdl.config
    if ragged_meta is not None and cfg.total_ut_steps > 1:
        # which of a page's K/V slots this pass reads and writes
        # (``inference/paged/kv_pages.py``): its own
        ragged_meta = {**ragged_meta, "kv_slot": u}
    block_cls = _maybe_remat(OuroBlock, cfg)
    for i in range(cfg.num_hidden_layers):
        x = block_cls(cfg, name=f"layers_{i}")(x, positions, deterministic,
                                               ragged_meta)
    x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
    gate = nn.Dense(1, use_bias=True, dtype=jnp.float32,
                    param_dtype=jnp.float32, name="early_exit_gate")(
                        x.astype(jnp.float32))
    return x, jax.nn.sigmoid(gate[..., 0])


class OuroModel(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        cfg = self.config
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.arange(S)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens",
                     embedding_init=in_float32(nn.initializers.normal(
                         EMBED_INIT_STD)))(input_ids)
        U = cfg.total_ut_steps

        def body(mdl, x, u):
            return _one_pass(mdl, x, u, positions, deterministic,
                             ragged_meta)

        if U == 1 or self.is_initializing():
            # one pass makes every weight and every cache variable (a
            # carried collection cannot be born inside a scan)
            x, lam = body(self, x, jnp.int32(0))
            lam = jnp.broadcast_to(lam, (U,) + lam.shape)
        else:
            x, lam = nn.scan(
                body, variable_broadcast="params",
                variable_carry="cache",
                split_rngs={"params": False, "dropout": True},
                length=U)(self, x, jnp.arange(U, dtype=jnp.int32))
        if U > 1:
            # how likely a token is to have stopped by the end of passes
            # 1..U-1, summed over the rows that are tokens of a sequence
            cdf = jnp.cumsum(exit_distribution(lam), axis=0)[:-1]
            if ragged_meta is not None and "live" in ragged_meta:
                cdf = jnp.where(ragged_meta["live"][None, None], cdf, 0.0)
            self.sow("loop_stats", "exit_cdf", jnp.sum(cdf, axis=(1, 2)))
        return x


class OuroForCausalLM(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 ragged_meta=None):
        from deepspeed_tpu.inference.common import sampled_rows
        from deepspeed_tpu.models.llama import _wq_kwargs

        cfg = self.config
        x = OuroModel(cfg, name="model")(input_ids, positions, deterministic,
                                         ragged_meta)
        # after the pass loop: the final norm and the exit gate run inside
        # it over every row, and ``exit_cdf`` sums over the live ones
        x = sampled_rows(x, ragged_meta)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head",
                        kernel_init=cfg.kernel_init, **_wq_kwargs(cfg))(x)
