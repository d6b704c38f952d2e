"""Latent rows: the cache kind of a latent-attention layer
(``models/glm4_moe_lite.py``), which keeps ONE row a token and no heads:
the normalised compressed vector beside the rotated key its heads share,
``W = kv_lora_rank + qk_rope_head_dim`` wide (:func:`latent_width`).  Its
pool is ``[num_pages, page_size, W]`` under the leaf name
:data:`LATENT_PAGES`; the row is every head's key and its first
``kv_lora_rank`` columns the value (:func:`latent_update_and_attend`,
``ops/latent_attention.py``).  It leads with the page dim like every pool,
so whatever copies, spills or shares ``leaf[page]`` moves it unchanged
(prefix sharing and tiering serve it), and a page's bytes are its leaf's
own; what reads a pool as heads of keys and values is refused by name.
``W`` is the row **zero-padded to whole lane tiles** (576 -> 640 at the
published widths: 1,280 bytes a token a layer in bf16, a ninth over the
unpadded 1,152): the chip lays a minor dim out in tiles of 128 lanes
whatever the program declares, so an unpadded pool would take the same
memory, and Mosaic moves whole tiles only (it refuses a page copy 576
wide).  A padded key column adds ``0 * 0`` to a score and no padded column
is a value, so the result is exact."""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paged.reference import ref_paged_attention
from deepspeed_tpu.inference.paged.tree import _LANES, Part
from deepspeed_tpu.ops import latent_attention, routes

# the name of a latent-attention layer's pool of one row a token
LATENT_PAGES = "latent_pages"


def latent_width(cfg) -> int:
    """Width of the one row a latent-attention layer caches for a token AS
    THE POOL STORES IT: its config's ``kv_lora_rank + qk_rope_head_dim``
    zero-padded to whole 128-lane tiles (576 -> 640); 0 for a model whose
    layers keep per-head keys and values."""
    rank = getattr(cfg, "kv_lora_rank", None)
    if not rank:
        return 0
    return -(-(int(rank) + int(cfg.qk_rope_head_dim)) // _LANES) * _LANES


def ref_latent_paged_attention(q: jax.Array, pages: jax.Array,
                               kv_lens: jax.Array, page_indices: jax.Array,
                               cu_q_lens: jax.Array, num_seqs: jax.Array, *,
                               rank: int, sm_scale: float) -> jax.Array:
    """The XLA twin of ``ops/latent_attention.py``: ``q [T, H, W]`` over
    the latent pool ``pages [P, page, W]``, ``[T, H, rank]``.  Latent
    attention is attention with ONE key/value head whose key is the row and
    whose value is its first ``rank`` columns, so this is
    :func:`ref_paged_attention` over the pool seen that way (a copy of the
    pool: test scale, as that function is)."""
    W = pages.shape[-1]
    value = jnp.where(jnp.arange(W) < rank, pages, 0)
    kv = jnp.stack([pages, value], axis=2)              # K even, V odd
    return ref_paged_attention(q, kv, kv_lens, page_indices, cu_q_lens,
                               num_seqs, sm_scale=sm_scale)[..., :rank]


def latent_update_and_attend(mdl, q: jax.Array, rows: jax.Array,
                             ragged_meta: Dict[str, jax.Array], cfg, *,
                             value_width: int, sm_scale: float
                             ) -> jax.Array:
    """Inside a latent-attention module: write this tick's rows into the
    layer's latent page buffer, then attend for all T tokens.

    ``q [T, H, w]``: every head's ABSORBED query (the up-projection of the
    keys folded in, the rotated part behind it); ``rows [T, w]``: ``[c |
    k_r]`` of this tick's tokens; narrower than the pool's ``W``
    (:func:`latent_width`) they are zero-padded here.  Returns ``[T, H,
    value_width]``: the weighted sums of the rows' first ``value_width``
    columns, which the caller up-projects.  A FUSED TICK (``ragged_meta``
    says which program it is) hands ``q`` as the pair ``ops/
    latent_attention.py:fused_tick_attention`` takes (heads first as the
    module holds it, and the packed sequences' first rows), and gets that
    function's answer in the layout the up-projection reads; a decode
    program takes the decode kernel, every other caller the XLA twin."""
    W = latent_width(cfg)
    pad = lambda a: a if a.shape[-1] == W else jnp.pad(  # noqa: E731
        a, ((0, 0),) * (a.ndim - 1) + ((0, W - a.shape[-1]),))
    q, rows = jax.tree_util.tree_map(pad, (q, rows))
    P, page = cfg.kv_num_pages, cfg.kv_page_size
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    pages_var = mdl.variable("cache", LATENT_PAGES, jnp.zeros, (P, page, W),
                             rows.dtype)
    flat = pages_var.value.reshape(P * page, W)
    pages = flat.at[ragged_meta["new_kv_dest"]].set(
        rows.astype(flat.dtype), mode="drop").reshape(P, page, W)
    pages_var.value = pages
    ragged = (pages, ragged_meta["kv_lens"], ragged_meta["page_indices"],
              ragged_meta["cu_q_lens"], ragged_meta["num_seqs"])
    twin = lambda q=q: ref_latent_paged_attention(      # noqa: E731
        q, *ragged, rank=value_width, sm_scale=sm_scale)
    if ragged_meta.get("fused_tick") is True:
        return latent_attention.fused_tick_attention(
            *q, *ragged, rank=value_width, sm_scale=sm_scale, fallback=twin)
    if ragged_meta.get("one_query_a_slot") is True:
        return latent_attention.decode_attention(
            q, *ragged[:3], ragged_meta.get("live"), rank=value_width,
            sm_scale=sm_scale, fallback=twin)
    return twin()


LATENT_IS = "{model} caches one latent row of {p.width} values a token a layer"
LATENT_REFUSES = {
    "quantized": "; the quantized pool's scale rows and kernel are per head",
    "tensor_parallel": ", which has no head dim for the pool's sharding rule "
    "to split"}


@dataclasses.dataclass(frozen=True)
class Latent(Part):
    """Layers that keep one latent row of ``width`` values a token."""
    width: int

    def refusals(self, model):
        return LATENT_IS.format(model=model, p=self), LATENT_REFUSES

    def span_attrs(self, cache):
        # what a cached token costs the pool as its leaves are allocated
        return {"latent_bytes_per_token": sum(
            int(np.prod(leaf.shape[2:])) * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(cache))}

    def kernels(self, route):
        return routes.taken(prefix=latent_attention.KERNEL)

    def stages(self, st, cache, cfg, kernels, pages_peak):
        row_bytes = self.span_attrs(cache)["latent_bytes_per_token"]
        return {"latent": {
            "width": self.width, "layers": self.of,
            "bytes_per_token": row_bytes,
            "bytes_per_token_layer": row_bytes // self.of,
            "pages_peak": pages_peak}}
