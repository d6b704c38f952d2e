"""Index selection: the cache kind of an attention layer that CHOOSES its
keys by token with an indexer of its own (``models/keye_vl2.py``; the
config's ``sa_config``).  It keeps two pool leaves: :data:`TOKENSEL_KV_PAGES`
``[num_pages, page_size, 2 * Hkv * D]`` (a token a row: its K of every KV
head, then its V; 2,048 bytes a token a layer at four KV heads of 128,
nothing padded, and the gather of a chosen token is one row) and
:data:`INDEX_KEY_PAGES` ``[num_pages, page_size // pack, pack * dI]``: ONE
index key a token, ``pack`` of them a row of 128 lanes (two at ``dI`` 64:
``ops/token_select_attention.py:index_pack`` says where a token's lies and
why), 128 bytes a token and nothing padded.  An index key is a
function of the token and its position alone, so it rides its page
wherever ``KVState`` takes the page: a shared prefix, a copy-on-write
page, a spilled or exported request carry it unchanged.  Every query
scores every cached key of its sequence and attends the ``topk`` best
(:func:`tokensel_update_and_attend`, ``ops/token_select_attention.py``).
Both leaves lead with the page dim."""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paged.tree import (Part, _one_row_seqs,
                                                scoped_step)
from deepspeed_tpu.ops import linear_attention, routes
from deepspeed_tpu.ops import token_select_attention as tsa

# an indexed layer's two pools: keys and values, a token a row, and the
# index keys its selection scores
TOKENSEL_KV_PAGES = "tokensel_kv_pages"
INDEX_KEY_PAGES = "index_key_pages"
# the scopes of the one-row path in a device trace: its scores, its choice,
# its gather by token and attention (a chunk's are ``index``, ``select`` and
# its two kernels' own names: ``index_select``, ``tokensel_chunk_attention``)
INDEX_ROW_SCOPE, SELECT_ROW_SCOPE = "index_row", "select_row"
ROW_SCOPE = "tokensel_row_attention"


def token_selection(cfg) -> Optional[tsa.Indexer]:
    """The indexer of a model whose attention layers choose their keys by
    token (its config's ``sa_config``), None for every other."""
    return getattr(cfg, "indexer", None)


class TickShape(NamedTuple):
    """The fused program as an engine builds it: its packed rows and
    sequences, and a sequence's pages."""
    rows: int
    max_seqs: int
    page_size: int
    pages_per_seq: int


def index_key_steps(first_pos: np.ndarray, q_lens: np.ndarray,
                    tick: TickShape) -> Dict[str, int]:
    """The key steps ``ops/token_select_attention.py:index_select`` spans
    in a fused tick of sequences of ``q_lens`` rows from ``first_pos`` on
    (``index_key_steps``: every tile the program holds by every step a
    sequence's pages fill) and those it works on (``index_live_key_steps``:
    a tile of a sequence of two rows or more, up to its last row's own
    key)."""
    keys = tsa.key_step(tick.page_size)
    spanned = tsa.padded_keys(tick.pages_per_seq, tick.page_size) // keys
    live = 0
    for p0, n in zip(first_pos, q_lens):
        if n >= 2:
            ends = np.minimum(np.arange(tsa.TILE, n + tsa.TILE, tsa.TILE), n)
            live += int(((int(p0) + ends - 1) // keys + 1).sum())
    tiles = -(-tick.rows // tsa.TILE) + tick.max_seqs
    return {"index_key_steps": tiles * spanned, "index_live_key_steps": live}


def token_select_counts(positions: np.ndarray, topk: int,
                        one_row: Optional[np.ndarray] = None
                        ) -> Dict[str, int]:
    """What the queries at ``positions`` ask of an indexed layer.  Of all of
    them: how many they are (``index_rows``) and the keys the indexer
    scores for them (``index_visible_tokens``: ``t + 1`` a query), split
    into those of prompt chunks and of one-row sequences (``one_row`` true;
    ``index_prefill_tokens`` / ``index_decode_tokens``); the (query, key)
    pairs attended, ``min(t + 1, topk)`` a query, split the same way
    (``attend_prefill_tokens`` / ``attend_decode_tokens``).  Of those with
    more than ``topk`` keys to choose from: how many (``sel_rows``), the
    keys they attend (``sel_key_tokens``) and the keys a dense pass would
    read (``sel_visible_tokens``).  Host-side, from positions alone: the
    chosen set's SIZE is the architecture's, which tokens are in it the
    device's to say."""
    p = np.asarray(positions, np.int64).reshape(-1)
    one = (np.ones(p.shape, bool) if one_row is None
           else np.asarray(one_row, bool).reshape(-1))
    visible = p + 1
    attended = np.minimum(visible, topk)
    far = visible > topk
    return {"index_rows": int(p.size),
            "index_visible_tokens": int(visible.sum()),
            "index_prefill_tokens": int(visible[~one].sum()),
            "index_decode_tokens": int(visible[one].sum()),
            "sel_rows": int(far.sum()),
            "sel_key_tokens": int(attended[far].sum()),
            "sel_visible_tokens": int(visible[far].sum()),
            "attend_prefill_tokens": int(attended[~one].sum()),
            "attend_decode_tokens": int(attended[one].sum())}


def tokensel_update_and_attend(mdl, q: jax.Array, k: jax.Array, v: jax.Array,
                               qI: jax.Array, kI: jax.Array, w: jax.Array,
                               pos: jax.Array,
                               ragged_meta: Dict[str, jax.Array], cfg, *,
                               sm_scale: float) -> jax.Array:
    """Inside an indexed attention module: write this tick's keys, values
    and index keys into the layer's pools, score every cached key of each
    query's sequence, choose and attend.

    ``q [T, H, D]``, ``k, v [T, Hkv, D]`` (normed and rotated), ``qI [T,
    HI, dI]``, ``kI [T, dI]`` (the index query heads and the index key),
    ``w [T, HI]`` float32 (the head weights, scaled), ``pos [T]`` -> ``[T,
    H, D]``.  As :func:`paged_update_and_attend`: ``kv_lens`` includes this
    tick's tokens, page 0 is the trash page, ``mutable=["cache"]`` on apply;
    the new rows are in both pools BEFORE anything is scored.  A decode
    program's rows take the one-row path (scores, ``lax.top_k``, the gather
    by token); every other batch is separated by ``cu_q_lens`` as
    :func:`ragged_linear_attention` separates it: sequences of two rows or
    more take the two chunk kernels in tiles (``index_select``, which
    scores and chooses, under the scopes ``index``, the queries' layout,
    and ``select``; then ``tokensel_chunk_attention``), sequences of one
    row the one-row path (scopes ``index_row``, ``select_row``,
    ``tokensel_row_attention``)."""
    sa = token_selection(cfg)
    T, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    HI, dI = qI.shape[1], qI.shape[2]
    P, page = cfg.kv_num_pages, cfg.kv_page_size
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    pages_var = mdl.variable("cache", TOKENSEL_KV_PAGES, jnp.zeros,
                             (P, page, 2 * Hkv * D), k.dtype)
    index_var = mdl.variable("cache", INDEX_KEY_PAGES, jnp.zeros,
                             tsa.index_leaf_shape(P, page, dI), kI.dtype)
    page_indices = ragged_meta["page_indices"]
    S = page_indices.shape[0]
    live = ragged_meta.get("live")
    decode = ragged_meta.get("one_query_a_slot") is True

    dest = ragged_meta["new_kv_dest"]
    rows = jnp.concatenate([k.reshape(T, Hkv * D), v.reshape(T, Hkv * D)],
                           axis=1)
    pages = pages_var.value.reshape(P * page, 2 * Hkv * D).at[dest].set(
        rows.astype(k.dtype), mode="drop").reshape(P, page, 2 * Hkv * D)
    pages_var.value = pages
    index_pages = tsa.write_index_keys(index_var.value, dest, kI)
    index_var.value = index_pages
    tsa.decode_route(sa.topk)

    def one_row(q_rows, qI_rows, w_rows, pos_rows, on, any_on=None):
        """One query a sequence (``[S, ...]``) over its chosen tokens; a
        fused tick with no such sequence (``any_on`` false) pays for no
        step of it."""
        step = functools.partial(scoped_step, any_on=any_on)
        K = tsa.padded_keys(page_indices.shape[1], page)
        k = min(sa.topk, K)
        spec = jax.ShapeDtypeStruct
        scores = step(INDEX_ROW_SCOPE, lambda: tsa.row_index_scores(
            qI_rows, w_rows, index_pages, page_indices, pos_rows, on),
            spec((S, K), jnp.float32))
        tokens, taken = step(
            SELECT_ROW_SCOPE, lambda: tsa.row_choice(scores, sa.topk),
            (spec((S, k), jnp.int32), spec((S, k), jnp.bool_)))
        return step(ROW_SCOPE, lambda: tsa.row_attention(
            q_rows.reshape(S, Hkv, G, D), pages, page_indices, tokens,
            taken, sm_scale=sm_scale).reshape(S, H, D),
            spec((S, H, D), q.dtype))

    if decode:
        return one_row(q, qI, w, pos, jnp.ones((S,), bool) if live is None
                       else live)
    cu = ragged_meta["cu_q_lens"]
    one, first = _one_row_seqs(ragged_meta, T)
    first_pos = jnp.take(pos, first)
    lay = linear_attention.tile_layout(cu, ragged_meta["num_seqs"],
                                       first_pos, T, tsa.TILE)
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
        # scores, threshold and bias in ONE kernel (``index_select``); the
        # scores never reach HBM
        _, bias = tsa.index_select(qI_t, w_t, index_pages, page_indices,
                                   *where, topk=sa.topk)
    y_t = tsa.chunk_attention(
        tiled(q).reshape(NT * tsa.TILE, Hkv, G, D).transpose(1, 2, 0, 3),
        pages, bias, *where, page_indices, sm_scale=sm_scale)
    y = linear_attention.from_tiles(
        y_t.transpose(2, 0, 1, 3).reshape(NT * tsa.TILE, H, D), lay["rows"],
        T)
    if live is not None:
        one = jnp.logical_and(one, jnp.take(live, first))
    at = lambda a: jnp.take(a, first, axis=0)                 # noqa: E731
    y_one = one_row(at(q), at(qI), at(w), first_pos, one, jnp.any(one))
    return y.at[jnp.where(one, first, T)].set(y_one, mode="drop")


INDEX_IS = ("{model} attends the {p.indexer.topk} keys an indexer of "
            "{p.indexer.indexer_num_heads} heads chooses, token by token, "
            "in {p.layers} of its {p.of} layers")
INDEX_REFUSES = {
    "quantized": "; the quantized pool's scale rows and kernel are per head "
    "of a pool laid out heads first, and an index key has no scale",
    "tensor_parallel": ", and the cache's sharding rule splits a pool's "
    "head dim, which a token's row of every head and the one index key a "
    "token do not have"}


@dataclasses.dataclass(frozen=True)
class IndexSelect(Part):
    """Layers that attend the tokens ``indexer`` chooses; ``tick``: the
    fused program's shape, where an engine has said it."""
    indexer: tsa.Indexer
    tick: Optional[TickShape] = None

    def refusals(self, model):
        return INDEX_IS.format(model=model, p=self), INDEX_REFUSES

    @staticmethod
    def _leaves(cache, name: str):
        """The pool leaves called ``name``, a layer each."""
        return [leaf for path, leaf in
                jax.tree_util.tree_flatten_with_path(cache)[0]
                if any(getattr(k, "key", None) == name for k in path)]

    def _leaf_bytes(self, cache, name: str) -> int:
        """Bytes a cached token costs over the leaves called ``name`` (a
        page's bytes over its tokens: the K/V leaf holds a token a row)."""
        page = self._leaves(cache, TOKENSEL_KV_PAGES)[0].shape[1]
        return sum(int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
                   for leaf in self._leaves(cache, name)) // page

    def span_attrs(self, cache):
        # what a cached token costs each pool as its leaves are allocated
        return {"index_bytes_per_token":
                self._leaf_bytes(cache, INDEX_KEY_PAGES),
                "tokensel_kv_bytes_per_token":
                self._leaf_bytes(cache, TOKENSEL_KV_PAGES)}

    def counts(self, st, positions, q_lens=None):
        one_row = None if q_lens is None else np.repeat(q_lens == 1, q_lens)
        counts = token_select_counts(positions, self.indexer.topk, one_row)
        for name in ("index_rows", "index_visible_tokens", "sel_rows",
                     "sel_key_tokens", "sel_visible_tokens"):
            setattr(st, name, getattr(st, name) + counts[name])
        if q_lens is not None:
            # the sequences that take the one-row path
            counts["one_row_seqs"] = int((q_lens == 1).sum())
            if self.tick is not None:
                first = np.cumsum(q_lens) - q_lens
                counts.update(index_key_steps(
                    np.asarray(positions).reshape(-1)[first], q_lens,
                    self.tick))
        return counts

    def kernels(self, route):
        return routes.taken(prefix=tsa.KERNEL)

    def stages(self, st, cache, cfg, kernels, pages_peak):
        # what the index keys cost, and what the selection spared
        return {"token_select": {
            "kv_layers": self.layers,
            "index_pool_bytes": sum(
                int(leaf.nbytes) for leaf in
                self._leaves(cache, INDEX_KEY_PAGES)),
            **self.span_attrs(cache),
            "index_rows": st.index_rows,
            "index_visible_tokens": st.index_visible_tokens,
            "sel_rows": st.sel_rows,
            "sel_key_tokens": st.sel_key_tokens,
            "sel_visible_tokens": st.sel_visible_tokens,
            "pages_peak": pages_peak,
            **self.indexer._asdict()}}
