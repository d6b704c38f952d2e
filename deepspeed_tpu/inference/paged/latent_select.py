"""Chosen latent rows: the cache kind of a latent-attention layer that
CHOOSES its keys by token with an indexer of its own
(``models/glm_moe_dsa.py``: a config with both ``kv_lora_rank`` and an
``indexer``).  It keeps two pool leaves, each under the name its elder
gives it: :data:`~.latent.LATENT_PAGES` ``[num_pages, page_size, W]``, the
one row ``[c | k_r]`` a token of ``latent.py`` (576 stored as 640), and
:data:`~.index_select.INDEX_KEY_PAGES`, the ONE index key a token of
``index_select.py`` (``[num_pages, page_size, 128]`` at an index head of 128:
a key a row of lanes), which rides its page wherever ``KVState`` takes the
page.  1,536 bytes a token a layer in bf16.  Every query scores every cached
key of its sequence and its heads attend the ``topk`` best latent rows and
no other (:func:`chosen_latent_update_and_attend`; ``ops/
token_select_attention.py`` scores and chooses, ``ops/
latent_select_attention.py`` attends).  Both leaves lead with the page dim;
what reads a pool as heads of keys and values is refused by name."""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paged.index_select import (
    INDEX_KEY_PAGES, INDEX_ROW_SCOPE, SELECT_ROW_SCOPE, IndexSelect,
    token_selection)
from deepspeed_tpu.inference.paged.latent import LATENT_PAGES, latent_width
from deepspeed_tpu.inference.paged.tree import _one_row_seqs, scoped_step
from deepspeed_tpu.ops import latent_select_attention as lsa
from deepspeed_tpu.ops import linear_attention, routes
from deepspeed_tpu.ops import token_select_attention as tsa

# the one-row path's gather by token and attention in a device trace (its
# scores and its choice enter ``index_select.py``'s scopes; a chunk's are
# ``index``, ``select`` and the two kernels' own names: ``index_select``,
# ``latent_select_chunk_attention``)
ROW_SCOPE = "latent_select_row_attention"


def chosen_latent_update_and_attend(
        mdl, absorbed: Callable, rows: jax.Array, qI: jax.Array,
        kI: jax.Array, w: jax.Array, pos: jax.Array,
        ragged_meta: Dict[str, jax.Array], cfg, *, value_width: int,
        sm_scale: float
        ) -> Union[jax.Array, Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]]:
    """Inside an indexed latent-attention module: write this tick's latent
    rows and index keys into the layer's pools, score every cached key of
    each query's sequence, choose and attend.

    ``absorbed(take, heads_first)``: the module's absorbed queries (the
    keys' up-projection folded in, the rotated part behind it, zeros to the
    pool's ``W``) of the tick's rows ``take [n]`` (``T``: no row, zeros),
    ``[H, n, W]`` or ``[n, H, W]``: the module makes them from what it holds
    narrow, so nothing ``T x H x W`` is gathered or turned.  ``rows [T, w]``:
    ``[c | k_r]`` of this tick's tokens; ``qI [T, HI, dI]``, ``kI [T, dI]``,
    ``w [T, HI]`` float32 (scaled), ``pos [T]``: the indexer's, as
    :func:`~.index_select.tokensel_update_and_attend` takes them.  The new
    rows are in both pools BEFORE anything is scored.

    A decode program's rows (one a slot) take the one-row path and the
    answer is ``[S, H, value_width]``.  Every other batch is separated by
    ``cu_q_lens``: sequences of two rows or more take ``index_select`` and
    the chunk kernel in ``tile_layout``'s tiles, sequences of one row the
    one-row path, and the answer is ``(o_tiles [H, NT * TILE, value_width],
    tile_rows [NT * TILE], o_one [S, H, value_width], at [S])``: the tiles'
    answers heads first as the value up-projection reads them, the tick's
    row each tile row holds (``T``: none), and the one-row sequences'
    answers with the row each belongs at (``T``: the sequence has none)."""
    sa = token_selection(cfg)
    T = rows.shape[0]
    W = latent_width(cfg)
    HI, dI = qI.shape[1], qI.shape[2]
    P, page = cfg.kv_num_pages, cfg.kv_page_size
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    pages_var = mdl.variable("cache", LATENT_PAGES, jnp.zeros, (P, page, W),
                             rows.dtype)
    index_var = mdl.variable("cache", INDEX_KEY_PAGES, jnp.zeros,
                             tsa.index_leaf_shape(P, page, dI), kI.dtype)
    page_indices = ragged_meta["page_indices"]
    S = page_indices.shape[0]
    live = ragged_meta.get("live")

    dest = ragged_meta["new_kv_dest"]
    rows = jnp.pad(rows, ((0, 0), (0, W - rows.shape[1])))
    pages = pages_var.value.reshape(P * page, W).at[dest].set(
        rows.astype(pages_var.value.dtype), mode="drop").reshape(P, page, W)
    pages_var.value = pages
    index_pages = tsa.write_index_keys(index_var.value, dest, kI)
    index_var.value = index_pages
    lsa.decode_route(sa.topk)

    def one_row(take, on, any_on=None):
        """One query a sequence (the tick's rows ``take [S]``) over its
        chosen rows; a fused tick with no such sequence (``any_on`` false)
        pays for no step of it."""
        step = functools.partial(scoped_step, any_on=any_on)
        at = lambda a: jnp.take(a, take, axis=0)              # noqa: E731
        K = tsa.padded_keys(page_indices.shape[1], page)
        k = min(sa.topk, K)
        spec = jax.ShapeDtypeStruct
        scores = step(INDEX_ROW_SCOPE, lambda: tsa.row_index_scores(
            at(qI), at(w), index_pages, page_indices, at(pos), on),
            spec((S, K), jnp.float32))
        tokens, taken = step(
            SELECT_ROW_SCOPE, lambda: tsa.row_choice(scores, sa.topk),
            (spec((S, k), jnp.int32), spec((S, k), jnp.bool_)))
        H = cfg.num_attention_heads
        return step(ROW_SCOPE, lambda: lsa.row_attention(
            absorbed(take, False), pages, page_indices, tokens, taken,
            rank=value_width, sm_scale=sm_scale),
            spec((S, H, value_width), rows.dtype))

    if ragged_meta.get("one_query_a_slot") is True:
        return one_row(jnp.arange(S), jnp.ones((S,), bool) if live is None
                       else live)
    one, first = _one_row_seqs(ragged_meta, T)
    lay = linear_attention.tile_layout(
        ragged_meta["cu_q_lens"], ragged_meta["num_seqs"],
        jnp.take(pos, first), T, tsa.TILE)
    NT = lay["seq"].shape[0]
    tiled = lambda a: linear_attention.to_tiles(a, lay["rows"])  # noqa: E731
    where = (lay["seq"], lay["pos0"], lay["len"])
    with jax.named_scope("index"):
        # index head h of a tile's row i at h * TILE + i
        heads_first = lambda a: a.reshape(                    # noqa: E731
            (NT, tsa.TILE, HI) + a.shape[2:]).swapaxes(1, 2)
        qI_t = heads_first(tiled(qI)).reshape(NT, HI * tsa.TILE, dI)
        w_t = heads_first(tiled(w.astype(jnp.float32))).reshape(
            NT, 1, HI * tsa.TILE)
    with jax.named_scope("select"):
        # scores, threshold and bias in ONE kernel; the scores never reach
        # HBM
        _, bias = tsa.index_select(qI_t, w_t, index_pages, page_indices,
                                   *where, topk=sa.topk)
    o_tiles = lsa.chunk_attention(
        absorbed(lay["rows"], True), pages, bias, *where, page_indices,
        rank=value_width, sm_scale=sm_scale)
    if live is not None:
        one = jnp.logical_and(one, jnp.take(live, first))
    return (o_tiles, lay["rows"], one_row(first, one, jnp.any(one)),
            jnp.where(one, first, T))


CHOSEN_IS = ("{model} caches one latent row of {p.width} values a token a "
             "layer, of which the {p.indexer.topk} an indexer of "
             "{p.indexer.indexer_num_heads} heads chooses are attended")
CHOSEN_REFUSES = {
    "quantized": "; the quantized pool's scale rows and kernel are per head, "
    "and an index key has no scale",
    "tensor_parallel": ", which has no head dim for the pool's sharding rule "
    "to split, and one index key a token"}


@dataclasses.dataclass(frozen=True)
class LatentSelect(IndexSelect):
    """Layers that keep one latent row of ``width`` values a token and
    attend the rows ``indexer`` chooses: an :class:`~.index_select.
    IndexSelect` (its counts of what the queries see and attend are this
    kind's too) over a latent leaf where that keeps keys and values."""
    width: int = 0

    def refusals(self, model):
        return CHOSEN_IS.format(model=model, p=self), CHOSEN_REFUSES

    def _leaf_bytes(self, cache, name: str) -> int:
        """Bytes a cached token costs over the leaves called ``name`` (a
        page's bytes over its tokens: the latent leaf holds a token a
        row)."""
        page = self._leaves(cache, LATENT_PAGES)[0].shape[1]
        return sum(int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
                   for leaf in self._leaves(cache, name)) // page

    def span_attrs(self, cache):
        # what a cached token costs each pool as its leaves are allocated
        return {"latent_bytes_per_token":
                self._leaf_bytes(cache, LATENT_PAGES),
                "index_bytes_per_token":
                self._leaf_bytes(cache, INDEX_KEY_PAGES)}

    def kernels(self, route):
        return {**routes.taken(prefix=tsa.KERNEL_INDEX),
                **routes.taken(prefix=tsa.KERNEL_SELECT),
                **routes.taken(prefix=tsa.KERNEL_ROW_INDEX),
                **routes.taken(prefix=lsa.KERNEL)}

    def stages(self, st, cache, cfg, kernels, pages_peak):
        attrs = self.span_attrs(cache)
        return {"latent_select": {
            "width": self.width, "layers": self.of,
            **attrs,
            "bytes_per_token_layer": sum(attrs.values()) // self.of,
            "index_rows": st.index_rows,
            "index_visible_tokens": st.index_visible_tokens,
            "sel_rows": st.sel_rows,
            "sel_key_tokens": st.sel_key_tokens,
            "sel_visible_tokens": st.sel_visible_tokens,
            "pages_peak": pages_peak,
            **self.indexer._asdict()}}
