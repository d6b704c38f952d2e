"""Block selection: the cache kind of an attention layer that CHOOSES its
keys by block (``models/minicpm_sala.py``).  It keeps two pool leaves:
:data:`SPARSE_KV_PAGES` ``[num_pages, 2 * Hkv, page_size, D]`` (heads lead
the tokens: with two KV heads a slab a head is whole tiles, where ``[page,
4, D]`` would be laid out in sixteen sublanes) and :data:`CKEY_PAGES`
``[num_pages, Hkv * page_size / stride, D]`` float32, the mean key of every
window of ``kernel`` tokens, kept in the page the window starts in.  From
``dense_len`` on a query attends the ``topk`` pages its compressed scores
choose (:func:`sparse_update_and_attend`,
``ops/block_select_attention.py``).  Both lead with the page dim."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paged.tree import (MATRIX_STATE_REFUSES, Part,
                                                _one_row_seqs,
                                                _seq_of_rows)
from deepspeed_tpu.ops import block_select_attention, linear_attention, routes

# a block-selecting layer's two pools: keys and values, heads first, and the
# compressed keys its selection scores
SPARSE_KV_PAGES = "sparse_kv_pages"
CKEY_PAGES = "ckey_pages"


def sparse_selection(cfg) -> Optional[block_select_attention.Selection]:
    """The block selection of a model whose attention layers choose their
    keys (its config's ``selection``), None for every other."""
    return getattr(cfg, "selection", None)


def sparse_key_counts(positions: np.ndarray, sel,
                      one_row: Optional[np.ndarray] = None
                      ) -> Dict[str, int]:
    """What the queries at ``positions`` ask of a block-selecting layer, a
    KV head.  Of those at or past ``dense_len``: how many they are
    (``sparse_rows``), the keys their chosen blocks hold at or before them
    (``sparse_key_tokens``) and the keys a dense pass would read
    (``sparse_visible_tokens``).  Of all of them: the keys attended, chosen
    or every earlier one, by the queries of prompt chunks
    (``attend_prefill_tokens``) and by those that are their sequence's one
    row (``one_row`` true; ``attend_decode_tokens``).  Host-side, from
    positions alone: the chosen set's SIZE is the architecture's, which
    blocks are in it the device's to say."""
    p = np.asarray(positions, np.int64).reshape(-1)
    one = (np.ones(p.shape, bool) if one_row is None
           else np.asarray(one_row, bool).reshape(-1))
    far = p >= sel.dense_len
    blocks = np.minimum(sel.topk, p // sel.block_size + 1)
    chosen = blocks * sel.block_size - (sel.block_size - 1
                                        - p % sel.block_size)
    attended = np.where(far, chosen, p + 1)
    return {"sparse_rows": int(far.sum()),
            "sparse_key_tokens": int(chosen[far].sum()),
            "sparse_visible_tokens": int((p[far] + 1).sum()),
            "attend_prefill_tokens": int(attended[~one].sum()),
            "attend_decode_tokens": int(attended[one].sum())}


def _write_compressed_keys(flat_kv, ckeys, pos, seq_of_t, live,
                           page_indices, sel, Hkv: int, page: int):
    """The compressed key of every window a live row of this call
    completes: row at position ``p`` with ``(p + 1) % stride == 0`` ends
    window ``(p + 1 - kernel) / stride``, whose ``kernel`` keys are read
    back from the pool (this call's rows are in it) and whose mean goes to
    the page the window starts in."""
    T = pos.shape[0]
    S = page_indices.shape[0]
    D = flat_kv.shape[-1]
    kernel, stride, wpb = (sel.kernel_size, sel.kernel_stride,
                           sel.windows_per_block)
    done = jnp.logical_and((pos + 1) % stride == 0, pos >= kernel - 1)
    if live is not None:
        done = jnp.logical_and(done, live)
    W = T // stride + S + 1
    rows = jnp.nonzero(done, size=W, fill_value=T)[0]
    ok = rows < T
    p_w = jnp.take(pos, rows, mode="fill", fill_value=kernel - 1)
    s_w = jnp.take(seq_of_t, rows, mode="fill", fill_value=0)
    key_pos = p_w[:, None] - (kernel - 1) + jnp.arange(kernel)[None]
    pg = jnp.maximum(page_indices[s_w[:, None], key_pos // page], 0)
    g = jnp.arange(Hkv)
    src = ((pg[:, :, None] * 2 * Hkv + 2 * g[None, None]) * page
           + (key_pos % page)[:, :, None])                 # [W, kernel, Hkv]
    mean = jnp.mean(jnp.take(flat_kv, src, axis=0).astype(jnp.float32),
                    axis=1)                                 # [W, Hkv, D]
    j = (p_w - (kernel - 1)) // stride
    home = jnp.maximum(page_indices[s_w, j // wpb], 0)
    dest = (home[:, None] * Hkv + g[None]) * wpb + (j % wpb)[:, None]
    dest = jnp.where(ok[:, None], dest, ckeys.shape[0] * ckeys.shape[1])
    P = ckeys.shape[0]
    return ckeys.reshape(P * Hkv * wpb, D).at[dest.reshape(-1)].set(
        mean.reshape(-1, D), mode="drop").reshape(ckeys.shape)


def sparse_update_and_attend(mdl, q: jax.Array, k: jax.Array, v: jax.Array,
                             pos: jax.Array,
                             ragged_meta: Dict[str, jax.Array], cfg, *,
                             sm_scale: float) -> jax.Array:
    """Inside a block-selecting attention module: write this tick's keys
    and values into the layer's pool and the windows they complete into
    its compressed keys, choose each query's blocks and attend them.

    ``q [T, H, D]``, ``k, v [T, Hkv, D]`` (normed; no rotation), ``pos
    [T]`` -> ``[T, H, D]``.  As :func:`paged_update_and_attend`:
    ``kv_lens`` includes this tick's tokens, page 0 is the trash page,
    ``mutable=["cache"]`` on apply.  A query before ``dense_len`` attends
    every earlier key, one from there on the keys of its chosen blocks
    (``ops/block_select_attention.py``; scopes ``compress`` and
    ``select``).  A decode program's rows take the chosen-page kernel;
    every other batch is separated by ``cu_q_lens`` as
    :func:`ragged_linear_attention` separates it: sequences of two rows or
    more take the chunk kernel in tiles (the choice handed over a block a
    bit), sequences of one row the chosen-page kernel."""
    bsa = block_select_attention
    sel = sparse_selection(cfg).check(cfg.kv_page_size)
    T, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    P, page = cfg.kv_num_pages, cfg.kv_page_size
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    wpb = sel.windows_per_block
    pages_var = mdl.variable("cache", SPARSE_KV_PAGES, jnp.zeros,
                             (P, 2 * Hkv, page, D), k.dtype)
    ckey_var = mdl.variable("cache", CKEY_PAGES, jnp.zeros,
                            (P, Hkv * wpb, D), jnp.float32)
    page_indices = ragged_meta["page_indices"]
    S, pp = page_indices.shape
    cu = ragged_meta["cu_q_lens"]
    live = ragged_meta.get("live")
    decode = ragged_meta.get("one_query_a_slot") is True

    dest = ragged_meta["new_kv_dest"]
    combined = jnp.stack([k, v], axis=2).reshape(T * 2 * Hkv, D)
    flat_idx = (((dest // page)[:, None] * 2 * Hkv
                 + jnp.arange(2 * Hkv)[None]) * page
                + (dest % page)[:, None]).reshape(-1)
    flat = pages_var.value.reshape(P * 2 * Hkv * page, D).at[flat_idx].set(
        combined.astype(k.dtype), mode="drop")
    pages = flat.reshape(P, 2 * Hkv, page, D)
    pages_var.value = pages
    seq_of_t = (jnp.arange(S, dtype=jnp.int32) if decode
                else _seq_of_rows(cu, T))
    with jax.named_scope("compress"):
        ckeys = _write_compressed_keys(flat, ckey_var.value, pos, seq_of_t,
                                       live, page_indices, sel, Hkv, page)
        ckey_var.value = ckeys
        # every sequence's windows, in order: [S, Hkv, pp * wpb, D]
        seq_keys = jnp.take(ckeys, jnp.maximum(page_indices, 0), axis=0
                            ).reshape(S, pp, Hkv, wpb, D).transpose(
                                0, 2, 1, 3, 4).reshape(S, Hkv, pp * wpb, D)

    def one_row(q_rows, pos_rows, on):
        """One query a sequence (``[S, H, D]``) over its chosen pages."""
        with jax.named_scope("select"):
            blocks, count = bsa.block_list(
                q_rows[:, None], seq_keys, pos_rows[:, None], sel, sm_scale)
            page_list = jnp.take_along_axis(
                jnp.broadcast_to(page_indices[:, None], (S, Hkv, pp)),
                jnp.minimum(blocks[:, 0], pp - 1), axis=-1)
            count = jnp.where(on, count[:, 0], 0)
        return bsa.decode_attention(
            q_rows.reshape(S, Hkv, G, D), pages, page_list, count,
            pos_rows % page + 1, sm_scale=sm_scale).reshape(S, H, D)

    if decode:
        return one_row(q, pos, jnp.ones((S,), bool) if live is None
                       else live)
    one, first = _one_row_seqs(ragged_meta, T)
    first_pos = jnp.take(pos, first)
    lay = linear_attention.tile_layout(cu, ragged_meta["num_seqs"],
                                       first_pos, T, bsa.TILE)
    NT = lay["seq"].shape[0]
    rows = lay["rows"]
    q_t = linear_attention.to_tiles(q, rows)               # [NT*TILE, H, D]
    pos_t = linear_attention.to_tiles(pos, rows).reshape(NT, bsa.TILE)
    blocks = bsa.chunk_blocks(pp)
    with jax.named_scope("select"):
        # a block a bit, blocks down and a tile's rows across; the kernel
        # reads it in the tiles with a row at or past ``dense_len`` alone
        chosen = jax.lax.cond(
            jnp.any(jnp.logical_and(
                lay["len"] > 0, lay["pos0"] + lay["len"] > sel.dense_len)),
            lambda: jnp.pad(
                bsa.block_mask(
                    q_t.reshape(NT, bsa.TILE, H, D),
                    jnp.take(seq_keys, lay["seq"], axis=0), pos_t, sel,
                    sm_scale).transpose(2, 0, 3, 1).astype(jnp.float32),
                ((0, 0), (0, 0), (0, blocks - pp), (0, 0))),
            lambda: jnp.zeros((Hkv, NT, blocks, bsa.TILE), jnp.float32))
    y_t = bsa.chunk_attention(
        q_t.reshape(NT * bsa.TILE, Hkv, G, D).transpose(1, 2, 0, 3), pages,
        chosen, lay["seq"], lay["pos0"], lay["len"], page_indices,
        dense_len=sel.dense_len, sm_scale=sm_scale)
    y = linear_attention.from_tiles(
        y_t.transpose(2, 0, 1, 3).reshape(NT * bsa.TILE, H, D), rows, T)
    if live is not None:
        one = jnp.logical_and(one, jnp.take(live, first))
    y_one = one_row(jnp.take(q, first, axis=0), first_pos, one)
    return y.at[jnp.where(one, first, T)].set(y_one, mode="drop")


# the model's other layers keep a matrix state a head (tree.py:
# MATRIX_STATE_REFUSES)
SELECT_IS = ("{model} keeps a matrix state a head in {state} of its {p.of} "
             "layers and chooses the pages its other layers attend")


@dataclasses.dataclass(frozen=True)
class BlockSelect(Part):
    """Layers that attend the blocks ``selection`` chooses."""
    selection: block_select_attention.Selection

    def refusals(self, model):
        return SELECT_IS.format(model=model, p=self,
                                state=self.of - self.layers), MATRIX_STATE_REFUSES

    def counts(self, st, positions, q_lens=None):
        one_row = None if q_lens is None else np.repeat(q_lens == 1, q_lens)
        counts = sparse_key_counts(positions, self.selection, one_row)
        st.sparse_rows += counts["sparse_rows"]
        st.sparse_key_tokens += counts["sparse_key_tokens"]
        st.sparse_visible_tokens += counts["sparse_visible_tokens"]
        if q_lens is not None:
            # the sequences that take the two one-token kernels
            counts["one_row_seqs"] = int((q_lens == 1).sum())
        return counts

    def kernels(self, route):
        return routes.taken(prefix=block_select_attention.KERNEL)

    def stages(self, st, cache, cfg, kernels, pages_peak):
        # what the compressed keys cost, and what the selection spared
        flat = jax.tree_util.tree_flatten_with_path(cache)[0]
        return {"sparse": {
            "kv_layers": self.layers,
            "compressed_bytes": sum(
                int(leaf.nbytes) for path, leaf in flat
                if any(getattr(k, "key", None) == CKEY_PAGES for k in path)),
            "sparse_rows": st.sparse_rows,
            "sparse_key_tokens": st.sparse_key_tokens,
            "sparse_visible_tokens": st.sparse_visible_tokens,
            **self.selection._asdict()}}
