"""Per-head K/V pages: the cache kind of an attention layer that keeps a key
and a value a head a token, ``[num_pages, page_size, 2 * Hkv, Dh]``.

Attention over them is JAX's built-in vLLM-TPU Pallas kernel
(``jax.experimental.pallas.ops.tpu.ragged_paged_attention``) on TPU, and
``reference.py:ref_paged_attention`` — an XLA-compilable, mask-based
equivalent of the kernel's reference math — everywhere else (CPU tests).
A decode program, which has one query row a sequence slot and says so in
its ``ragged_meta``, takes ``ops/paged_decode.py`` instead wherever that
kernel's layout holds; a fused tick, which says so too, takes
``ops/paged_prefill.py`` for its prompt chunks and the decode kernel for
its one-query rows.

A model whose layers run ``U`` times a token (``models/ouro.py``) needs
``U`` K/V slots a layer.  Its layer's pool is ``[num_pages, U, page_size,
2 * Hkv, Dh]``: a page holds all ``U`` slots of its tokens, so whatever
copies, spills or shares ``leaf[page]`` moves them together and the
allocator counts pages as before.  Pass ``u`` (``ragged_meta["kv_slot"]``)
reads and writes the same buffer as ``[num_pages * U, ...]`` at page
``p * U + u``; the kernels see an ordinary pool; what reads a layer's pool
as ONE slot is refused by name.

Heads of 64 (``models/lfm2_moe.py``) are stored **zero-padded to 128**
(:func:`kv_lane_dim`): both Pallas kernels are built for 128 lanes, a
padded key adds ``0 * 0`` to a score and a padded value column is sliced
off the output, so the result is exact and the softmax scale stays the
true head's.  A token then costs the pool ``2 * Hkv * 128`` values a K/V
layer, twice its unpadded size.

Thirty KV heads (``models/olmo_hybrid.py``) are stored as **thirty-two**
(:func:`kv_heads_stored`): a token's ``2 * Hkv`` combined heads are the
sublanes of a page's tiles, both Pallas kernels need whole tiles of them
(``2 * Hkv`` a multiple of 16), and 60 is none.  The model pads q, k and v
with zero heads before the call and drops the padded heads' (zero-query)
outputs after it, so the result is exact; a token costs the pool 32 heads'
bytes, a fifteenth more.  Only where the padding is that cheap: two or four
KV heads are stored as they are.  **Four** (``models/smallthinker.py``: plain
GQA, 28 query heads over four) take both kernels all the same: a token's eight
combined heads are half a sublane tile, so the kernels fetch and buffer the
pool a PAIR of tokens a tile (``ops/paged_decode.py:tokens_a_tile``: ``[P,
page / 2, 16, Dh]``, the same bytes where they lie) and a token costs the pool
and a decode tick its own four heads' bytes, not eight's.  **Two** would be
four tokens a tile, which no configuration reads through these kernels (the
one model with two KV heads attends chosen blocks of a pool of its own
layout): that tile has never been handed to the compiler, and such a pool
keeps the XLA twin.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paged.reference import (
    ref_paged_attention, ref_paged_attention_quant)
from deepspeed_tpu.inference.paged.tree import (_LANES, WINDOW_POOL, Part,
                                                window_pool_layers)
from deepspeed_tpu.ops import paged_decode, paged_prefill, routes
from deepspeed_tpu.ops.paged_decode import paged_decode_attention
from deepspeed_tpu.ops.paged_prefill import fused_tick_attention
from deepspeed_tpu.utils.compat import shard_map as _shard_map_compat

# block sizes handed to the upstream ragged paged kernel (see ``attend``):
# a KV block of at most 512 tokens, and of at most 4096 rows of K or V
# (tokens x KV heads) — what 8 KV heads x 512 tokens hold
_RPA_KV_BLOCK_TOKENS = 512
_RPA_KV_BLOCK_HEAD_TOKENS = 4096
_RPA_QUERY_BLOCK = 32


def rpa_kv_pages_per_block(kv_heads: int, page_size: int,
                           pages_per_seq: int) -> int:
    """KV pages one block of the upstream kernel holds.  The block lives
    in scoped VMEM, double-buffered, and its bytes grow with the KV heads
    (pages are ``[P, page, 2 * Hkv, D]``): 512 tokens at up to 8 KV heads,
    256 at 16, never under one page."""
    tokens = min(_RPA_KV_BLOCK_TOKENS,
                 _RPA_KV_BLOCK_HEAD_TOKENS // max(kv_heads, 1))
    return max(1, min(pages_per_seq, tokens // page_size))


def kv_slots_per_layer(cfg) -> int:
    """K/V slots a layer holds for one token: the passes of a looped model
    (its config's ``total_ut_steps``), 1 for every other."""
    return int(getattr(cfg, "total_ut_steps", 1))


def kv_lane_dim(head_dim: int, kv_cache_dtype: str = "none") -> int:
    """Width of a K/V head AS THE POOL STORES IT: heads of 64 in a
    full-width pool are zero-padded to the kernels' 128 lanes, every other
    head is stored as it is."""
    full_width = (kv_cache_dtype or "none") == "none"
    return _LANES if head_dim == 64 and full_width else head_dim


def kv_heads_stored(kv_heads: int) -> int:
    """KV heads AS THE POOL STORES THEM: the next multiple of 8 (whole
    sublane tiles of combined heads, which the kernels need) where that
    costs at most an eighth more, else the heads as they are."""
    up = -(-kv_heads // 8) * 8
    return up if (up - kv_heads) * 8 <= kv_heads else kv_heads


def layer_window(cfg, layer_kind: Optional[str] = None) -> Optional[int]:
    """The keys a layer of ``layer_kind`` (its entry of the config's
    ``layer_types``; None: the model's layers are of one kind) attends
    behind a row: ``cfg.sliding_window``, but for a ``full_attention``
    layer, which attends them all."""
    window = getattr(cfg, "sliding_window", None)
    return None if layer_kind == "full_attention" else window


def paged_update_and_attend(mdl, q: jax.Array, k: jax.Array, v: jax.Array,
                            ragged_meta: Dict[str, jax.Array], cfg,
                            layer_kind: Optional[str] = None) -> jax.Array:
    """Inside an attention module: scatter this tick's K/V rows into the
    layer's page buffer, then ragged-paged attention for all T tokens.

    q: [1, H, T, D]; k, v: [1, Hkv, T, D] (rotary already applied).
    Returns [1, H, T, D].  Requires ``mutable=["cache"]`` on apply.
    ``layer_kind``: the layer's entry of ``cfg.layer_types`` where the model
    has window and full layers (:func:`layer_window`); a window layer of
    such a model reads and writes the window layers' OWN pool through their
    own table (``window_pages.py``: the ``window_`` keys of the metadata).

    A looped model (``cfg.total_ut_steps = U > 1``) says which pass this
    is in ``ragged_meta["kv_slot"]``: the layer's pool has ``U`` slots a
    page and this call reads and writes slot ``u`` alone, in place.
    """
    _, H, T, D = q.shape
    sm_scale = float(1.0 / np.sqrt(D))
    head_dim = D
    D = kv_lane_dim(head_dim, getattr(cfg, "kv_cache_dtype", "none"))
    if D != head_dim:
        # heads of 64 live in the pool zero-padded to the kernels' lanes
        pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, D - head_dim),))
        q, k, v = pad(q), pad(k), pad(v)
    slots = kv_slots_per_layer(cfg)
    Hkv = k.shape[1]
    leaf, P, page, own = "kv_pages", cfg.kv_num_pages, cfg.kv_page_size, ""
    if layer_kind == "sliding_attention" and window_pool_layers(cfg):
        leaf, P, own = WINDOW_POOL, cfg.kv_window_num_pages, "window_"
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    new_kv_dest = ragged_meta[own + "new_kv_dest"]
    page_indices = ragged_meta[own + "page_indices"]
    pool_shape = (P, page, 2 * Hkv, D)
    if slots > 1:
        assert (getattr(cfg, "kv_cache_dtype", "none") or "none") == "none", (
            "a looped model's pool is full-width: no quantized pages")
        # slot u of page p is page p * U + u of the same buffer; a row
        # bound for no page (past the pool) stays past it, and the -1 of
        # an unused page-table entry stays -1
        slot = ragged_meta["kv_slot"]
        pool_shape = (P, slots, page, 2 * Hkv, D)
        new_kv_dest = ((new_kv_dest // page * slots + slot) * page
                       + new_kv_dest % page)
        page_indices = jnp.where(page_indices >= 0,
                                 page_indices * slots + slot, page_indices)
        P = P * slots

    # KV-cache quantization (reference csrc/fp_quantizer selective_dequant):
    # pages persist in fp8 e4m3 or int8 with a per-(row, head) fp32 scale and
    # are READ quantized (ops/ragged_paged_quant.py on TPU, the gathered-
    # pages XLA reference below); never a full-width pool operand
    kv_quant = getattr(cfg, "kv_cache_dtype", "none") or "none"
    if kv_quant in ("fp8", "fp8_e4m3"):
        store_dtype, qmax = jnp.float8_e4m3fn, float(
            jnp.finfo(jnp.float8_e4m3fn).max)
    elif kv_quant == "int8":
        store_dtype, qmax = jnp.int8, 127.0
    else:
        assert kv_quant == "none", f"unknown kv_cache_dtype {kv_quant!r}"
        store_dtype, qmax = k.dtype, None

    pages_var = mdl.variable("cache", leaf, jnp.zeros, pool_shape, store_dtype)

    # interleave K/V onto combined heads: [T, 2Hkv, D], K even, V odd
    k_rows = k[0].transpose(1, 0, 2)                   # [T, Hkv, D]
    v_rows = v[0].transpose(1, 0, 2)
    combined = jnp.stack([k_rows, v_rows], axis=2).reshape(T, 2 * Hkv, D)

    flat = pages_var.value.reshape(P * page, 2 * Hkv, D)
    if qmax is None:
        flat = flat.at[new_kv_dest].set(
            combined.astype(flat.dtype), mode="drop")
        pages = flat.reshape(P, page, 2 * Hkv, D)
        pages_var.value = pages.reshape(pool_shape)
    else:
        scales_var = mdl.variable(
            "cache", "kv_scales", jnp.zeros, (P, page, 2 * Hkv),
            jnp.float32)
        cf = combined.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(cf), axis=-1)         # [T, 2Hkv]
        # floor the QUOTIENT at the smallest normal f32: fp8's qmax=448
        # can push absmax/qmax subnormal, and a subnormal scale's
        # reciprocal overflows qv = cf/scale to inf before the cast
        scale = jnp.maximum(absmax / qmax,
                            jnp.float32(np.finfo(np.float32).tiny))
        qv = cf / scale[..., None]
        if store_dtype == jnp.int8:
            qv = jnp.clip(jnp.round(qv), -qmax, qmax)
        flat = flat.at[new_kv_dest].set(
            qv.astype(store_dtype), mode="drop")
        flat_s = scales_var.value.reshape(P * page, 2 * Hkv)
        flat_s = flat_s.at[new_kv_dest].set(scale, mode="drop")
        scales_var.value = flat_s.reshape(P, page, 2 * Hkv)
        pages = flat.reshape(P, page, 2 * Hkv, D)
        pages_var.value = pages
        # NO transient dequant: the attention variants below read the
        # quantized pool directly (O(attended rows), never O(pool))
        kv_scales = scales_var.value

    qt = q[0].transpose(1, 0, 2)                       # [T, H, D]
    kv_lens = ragged_meta["kv_lens"]
    cu_q_lens = ragged_meta["cu_q_lens"]
    num_seqs = ragged_meta["num_seqs"]
    window = layer_window(cfg, layer_kind)

    # a decode program says so itself (build_decode_block: one query a
    # slot, ``cu_q_lens`` an arange): its attention is the decode kernel's
    # wherever that kernel's layout holds for this shard of the pool, and
    # otherwise, as for every other program, the call in attend_ragged
    one_query_a_slot = (ragged_meta.get("one_query_a_slot") is True
                        and qmax is None)
    # so does a fused tick (programs._fused_forward: decode rows and
    # prompt chunks packed by ``cu_q_lens``): its chunks are the chunk
    # kernel's and its one-query rows the decode kernel's, under the same
    # rule.  Every other caller keeps the call in attend_ragged
    fused_tick = ragged_meta.get("fused_tick") is True and qmax is None

    def attend(qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
               live=None):
        ragged = (qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs)
        if fused_tick:
            return fused_tick_attention(
                *ragged, sm_scale=sm_scale, sliding_window=window,
                fallback=lambda: attend_ragged(*ragged),
                fallback_route=paged_attention_path(head_dim))
        if not one_query_a_slot:
            return attend_ragged(*ragged)
        return paged_decode_attention(
            qt, pages, kv_lens, page_indices, live, sm_scale=sm_scale,
            sliding_window=window, fallback=lambda: attend_ragged(*ragged),
            fallback_route=paged_attention_path(head_dim))

    def attend_ragged(qt, pages, kv_lens, page_indices, cu_q_lens,
                      num_seqs):
        # the vLLM-TPU kernel is built for 128 lanes: heads of 128 take it
        # as they are, heads of 64 zero-padded to 128 (``kv_lane_dim``),
        # every other width the XLA reference — correct but O(T *
        # total_page_rows)
        if paged_attention_path(head_dim).startswith("pallas-ragged"):
            from jax.experimental.pallas.ops.tpu.ragged_paged_attention \
                import kernel as rpa

            # explicit blocks: for a shape outside its tuned table the
            # upstream lookup answers (128 pages, 32 queries), which from
            # max_seq_len ~2k up no longer fits the chip's scoped VMEM.
            # 512 KV tokens x 32 queries at up to 8 KV heads (fewer tokens
            # at more heads) compiles at every page size the engine offers
            return rpa.ragged_paged_attention(
                qt, pages, kv_lens, jnp.maximum(page_indices, 0),
                cu_q_lens, num_seqs, sm_scale=sm_scale,
                sliding_window=window,
                num_kv_pages_per_block=rpa_kv_pages_per_block(
                    pages.shape[2] // 2, page, page_indices.shape[1]),
                num_queries_per_block=min(T, _RPA_QUERY_BLOCK))
        if routes.on_tpu():
            from deepspeed_tpu.utils.logging import logger

            logger.warning(
                f"paged attention: head_dim={head_dim} — the Pallas "
                "kernels serve heads of 128, and heads of 64 padded to "
                "128; this width takes the dense XLA route "
                "(serving_stages()['kernels'] reports it)")
        return ref_paged_attention(
            qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, sliding_window=window)

    def attend_quant(qt, pages, scales, kv_lens, page_indices, cu_q_lens,
                     num_seqs):
        # quantized pool: both routes read the 1-byte pages + scale rows
        # directly (see kv_dequant_path for the route the engine reports)
        if kv_dequant_path(D) == "pallas-quant":
            from deepspeed_tpu.ops.ragged_paged_quant import \
                ragged_paged_attention_quant

            return ragged_paged_attention_quant(
                qt, pages, scales, kv_lens, page_indices, cu_q_lens,
                num_seqs, sm_scale=sm_scale, sliding_window=window)
        return ref_paged_attention_quant(
            qt, pages, scales, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, sliding_window=window)

    # TP serving (reference v2 sharding/attn.py: heads split over the TP
    # group): attention is embarrassingly parallel over heads, so under a
    # >1 `tensor` mesh axis run it shard_map-manual over `tensor` with q
    # and the KV pages head-sharded and the ragged metadata replicated —
    # required for the Pallas kernel, which composes with shard_map, not
    # with GSPMD auto-sharding
    tp = _serving_tp(cfg)
    # which slots of a decode program are in use, where it says
    live = ([ragged_meta["live"]]
            if one_query_a_slot and "live" in ragged_meta else [])
    if tp > 1:
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.sequence.layer import resolve_mesh

        assert H % tp == 0 and Hkv % tp == 0, (
            f"TP serving requires heads divisible by tp={tp} "
            f"(H={H}, Hkv={Hkv})")
        mesh = resolve_mesh(None, "tensor")
        if qmax is None:
            y = _shard_map_compat(
                attend, mesh=mesh,
                in_specs=(P(None, "tensor", None),
                          P(None, None, "tensor", None), P(), P(), P(),
                          P()) + (P(),) * len(live),
                out_specs=P(None, "tensor", None),
                axis_names={"tensor"}, check_vma=False)(
                    qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
                    *live)
        else:
            # the scale buffer shards its combined-head dim with the pool
            y = _shard_map_compat(
                attend_quant, mesh=mesh,
                in_specs=(P(None, "tensor", None),
                          P(None, None, "tensor", None),
                          P(None, None, "tensor"), P(), P(), P(), P()),
                out_specs=P(None, "tensor", None),
                axis_names={"tensor"}, check_vma=False)(
                    qt, pages, kv_scales, kv_lens, page_indices,
                    cu_q_lens, num_seqs)
    elif qmax is None:
        y = attend(qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
                   *live)
    else:
        y = attend_quant(qt, pages, kv_scales, kv_lens, page_indices,
                         cu_q_lens, num_seqs)
    y = y.transpose(1, 0, 2)[None]                     # [1, H, T, D]
    return y if D == head_dim else y[..., :head_dim]


def kv_dequant_path(head_dim: int) -> str:
    """Which dequant-free read path a quantized pool takes on this
    backend: the Pallas quantized-pages kernel
    (:mod:`deepspeed_tpu.ops.ragged_paged_quant`; TPU, head_dim 128) or
    the gathered-pages XLA reference
    (:func:`ref_paged_attention_quant`).  Neither materializes a
    full-width pool operand.  The engine reports this in its
    ``serving_stages()['kv_quant']`` block."""
    return routes.take("paged_attention_quant", eligible=head_dim == 128,
                       pallas="pallas-quant", xla="xla-gather")


def paged_attention_path(head_dim: int) -> str:
    """Which read path a full-width pool takes on this backend: the
    upstream Pallas ragged kernel (TPU) for heads of 128
    (``pallas-ragged``) and for heads of 64 stored zero-padded to 128
    (``pallas-ragged-pad128``), or the dense XLA reference
    (:func:`ref_paged_attention`: ``xla-dense``, over the padded pool
    ``xla-dense-pad128``).  The engine reports this in
    ``serving_stages()['kernels']``."""
    pad = "-pad128" if kv_lane_dim(head_dim) != head_dim else ""
    return routes.take("paged_attention",
                       eligible=kv_lane_dim(head_dim) == _LANES,
                       pallas="pallas-ragged" + pad, xla="xla-dense" + pad)


def _serving_tp(cfg) -> int:
    """Tensor-parallel degree for the paged path: the model must be
    TP-annotated AND a multi-device `tensor` mesh axis installed."""
    if not getattr(cfg, "tensor_parallel", False):
        return 1
    import deepspeed_tpu.comm as dist

    topo = dist.peek_topology()
    if topo is None:
        return 1
    return int(topo.mesh.shape.get("tensor", 1))


LOOP_IS = "{model} runs its layers {p.slots} times a token"
LOOP_REFUSES = {
    "quantized": "; its pool is full-width (the quantized pool's scale rows "
    "have one slot a layer)",
    "tensor_parallel": ", and the pool's sharding rule knows pages of one "
    "slot"}


@dataclasses.dataclass(frozen=True)
class KVPages(Part):
    """Layers that keep per-head K/V pages, ``slots`` a layer."""
    slots: int

    def refusals(self, model):
        if self.slots > 1:
            return LOOP_IS.format(model=model, p=self), LOOP_REFUSES

    def span_attrs(self, cache):
        # a looped model's passes a token and the K/V rows a cached token
        # owns (passes x layers); nothing for other models
        return ({} if self.slots == 1 else
                {"ut_steps": self.slots, "kv_slots": self.slots * self.layers})

    def kernels(self, route):
        # and what each fused tick and each decode program traced so far
        # took for its own
        return {"paged_attention": route,
                **routes.taken(prefix=paged_prefill.KERNEL),
                **routes.taken(prefix=paged_decode.KERNEL)}

    def stages(self, st, cache, cfg, kernels, pages_peak):
        # the exit gate's sums as far as fetches have brought them
        return {} if self.slots == 1 else {"loop": {
            **self.span_attrs(cache), "rows": st.loop_rows,
            "exit_cdf": list(st.loop_exit_cdf)}}
