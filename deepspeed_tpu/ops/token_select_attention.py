"""Learned selection of keys BY TOKEN over a paged pool, and attention over
the chosen tokens (Pallas TPU kernels beside ``jax.numpy`` forms).

An indexed layer (``models/keye_vl2.py``) scores every cached token of a
sequence with a narrow indexer of its own: ``I(t, s) = sum_j w(t, j) *
relu(qI(t, j) . kI(s))`` over ``HI`` index heads of ``dI`` against ONE index
key a token, accumulated in float32, and attends the ``topk`` keys ``s <=
t`` of largest ``I(t, s)`` (ties to the lower ``s``; every ``s <= t`` where
``t + 1 <= topk``).  The rule is by query, so a prompt gives the same
logits however it is chunked, and it is EXACT: no approximate top-k and no
choice by page.

The layer keeps two pool leaves: K/V pages ``[P, page, 2 * Hkv * D]`` (a
token a row: its K of every KV head, then its V; a page a slab of ``page``
rows, whole tiles and nothing padded, a head a lane-aligned slice of it,
and the gather of a chosen token one row: heads first, as ``ops/
block_select_attention.py`` lays two KV heads out, a token's K and V are
eight rows of 256 bytes in eight slabs, and XLA's gather of them measured
four times slower on the chip) and the index keys ``[P, page // pack,
pack * dI]``: ``pack = 128 // dI`` keys a row of 128 lanes (:func:`index_pack`;
two at ``dI`` 64), token ``j`` of a page in row ``j % rows`` at lanes ``(j
// rows) * dI`` on, so that a row is whole lanes, nothing is padded, and the
keys of a lane block are a page's consecutive tokens.  (As ``[P, page, 64]``
the chip gave the leaf a layout of its own choosing, the page dim minor-most,
and every layer of every tick copied the whole pool into the kernels'
layout and back: 11 % of the busy time, PERF.md section 6, PR 47.)  Both lead
with the page dim.

Scores are compared as **sortable integers** (:func:`sortable`: a float32's
bits, folded so that signed integer order is the floats' order), a key a
row cannot see at the least integer.

The prompt chunks of a fused tick, rows in ``ops/linear_attention.py:
tile_layout``'s tiles of :data:`TILE`, scores held transposed (keys down
the sublanes, a tile's rows across the lanes):

- :func:`index_select` (kernel ``index_select``): a grid step a tile, and in
  it three loops over the tile's LIVE key steps (those up to its last row's
  own key; a tile of no row makes none, fetches nothing and writes
  nothing but its row of statistics).  First the scores: a key step's eight
  pages of index keys, fetched through the page table a step ahead, against
  the tile's ``[HI * TILE, dI]`` index queries, one product a lane block;
  relu, the head weights, the sum over heads down lane slices, into a
  ``[K, TILE]`` int32 scratch that never leaves VMEM.  Then the exact
  ``topk``-th largest a row by bisection over the 32 bits (32 counts of
  ``score >= candidate``); where a row's threshold is shared by more keys
  than it has room for, the lower positions win, as ``lax.top_k`` has it:
  a second bisection, over the position's bits, in the tiles that hold
  such a row (:func:`resolve_ties` is its twin).  Last the additive bias
  ``[keys, TILE]`` bfloat16 the chosen keys leave at 0, a block a step
  copied out to HBM behind the next block's making.  (As two kernels the
  scores crossed HBM twice as ``[NT, K, TILE]`` int32 and every tile and
  key step was paid for, live or not: 1.40 ms a call where this takes 0.62
  at eight live tiles of 24 key steps of seventeen and 65, PERF.md section
  6, PR 49.)
- :func:`chunk_attention` (kernel ``tokensel_chunk_attention``): the dense
  pass under that bias, with ``ops/block_select_attention.py``'s own key
  step, fold and output (``step_copies``, ``fold_head``, ``finish_tile``: a
  grid step a tile, a loop over key steps of eight pages up to the tile's
  last row, every query head of a KV head one product, scores transposed);
  what is this kernel's own is the pool's slices (a token a row) and the
  bias, a ``[keys, TILE]`` array a (row, key) where that one has a bit a
  block, fetched a key step at a time with the step's pages: a step past
  a tile's last row's, which :func:`index_select` does not write, is
  never fetched.
  With weights from a seed each row's choice is a random subset, so the
  union over a tile's rows is every key and nothing can be skipped: a
  masked dense pass is the honest first form.

Sequences of one row (decode rows) take :func:`row_index_scores` (kernel
``row_index_scores``: a row's sixteen index heads against the pages it sees,
through the page table), then :func:`row_choice` and :func:`row_attention`:
``jax.numpy`` on every backend.  The choice is ``lax.top_k`` over the row's
scores and the attention GATHERS the chosen tokens' K and V by token and
reads no other (a page of 64 holds about four chosen tokens at 32k, so a
page list would save nothing).  A Pallas form would issue one 2 KB copy a
chosen token, two thousand descriptors a row a layer; XLA's gather brought
nine rows' tokens in 0.39 ms on the chip and is the better tool.

Each kernel has ``jax.numpy`` twins taken on the CPU (``index_select``
three: :func:`index_scores_twin`, :func:`select_twin`,
:func:`resolve_ties`, which are the definition it is tested against); which
ran is recorded under :data:`KERNEL_INDEX` and :data:`KERNEL_SELECT` (the
one kernel's two halves) / :data:`KERNEL_PREFILL` /
:data:`KERNEL_ROW_INDEX`, and the one-row path's gather under
:data:`KERNEL_DECODE`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes
# the chunk kernel is the block-selecting one's but for the pool's layout
# and where its bias comes from: one tile, one key step, one fold
from deepspeed_tpu.ops.block_select_attention import (_STEP_PAGES, TILE,
                                                      finish_tile, fold_head,
                                                      step_copies)

KERNEL = "token_select"
KERNEL_INDEX = KERNEL + "_index"
KERNEL_SELECT = KERNEL + "_threshold"
KERNEL_PREFILL = KERNEL + "_prefill"
KERNEL_DECODE = KERNEL + "_decode"
KERNEL_ROW_INDEX = KERNEL + "_row_index"
ROW_INDEX_NAME = "row_index_scores"
SELECT_NAME = "index_select"
CHUNK_NAME = "tokensel_chunk_attention"

# exactly a bfloat16 (and a float32): the bias is stored in bfloat16
_MASK_VALUE = -float(2.0 ** 127)
_INT_MIN = int(np.iinfo(np.int32).min)
_LANES = 128


class Indexer(NamedTuple):
    """The source's ``sa_config``."""
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    topk: int = 2048
    q_chunk_size: int = 512     # the source kernel's tiling: no part in
    kv_chunk_size: int = 512    # the mathematics

    @property
    def head_scale(self) -> float:
        """``HI^(-1/2) * dI^(-1/2)``, on the head weights."""
        return float(self.indexer_num_heads ** -0.5
                     * self.indexer_head_dim ** -0.5)


def sortable(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' (``-0.0`` first
    made ``0.0``: the two are one score)."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def key_step(page: int) -> int:
    """Keys a step of the chunk kernels holds: eight pages."""
    return _STEP_PAGES * page


def padded_keys(pages_per_seq: int, page: int) -> int:
    """Keys a tile's scores span: its sequence's pages in whole key steps."""
    step = key_step(page)
    return -(-pages_per_seq * page // step) * step


def index_pack(page: int, dI: int) -> int:
    """Index keys a row of the index pool: as many as fill 128 lanes, where
    ``dI`` divides them and that number divides the page; else one."""
    pack = _LANES // dI if dI < _LANES and _LANES % dI == 0 else 1
    return pack if page % pack == 0 else 1


def index_leaf_shape(P: int, page: int, dI: int) -> Tuple[int, int, int]:
    """The index pool of ``P`` pages of ``page`` tokens."""
    pack = index_pack(page, dI)
    return P, page // pack, pack * dI


def unpack_index_keys(pages: jax.Array, dI: int) -> jax.Array:
    """Pool rows as a page's keys in order: ``[..., rows, pack * dI]`` (token
    ``j`` in row ``j % rows`` at lanes ``(j // rows) * dI`` on) -> ``[...,
    page, dI]``."""
    *lead, rows, width = pages.shape
    pack = width // dI
    return pages.reshape(*lead, rows, pack, dI).swapaxes(-3, -2).reshape(
        *lead, rows * pack, dI)


def write_index_keys(index_pages: jax.Array, dest: jax.Array, kI: jax.Array
                     ) -> jax.Array:
    """``index_pages [P, rows, pack * dI]`` with the keys ``kI [T, dI]`` of
    the tokens at ``dest [T]`` (flat token slots, ``page_id * page + j``; one
    past the pool is written nowhere).  Whole rows are written (a scatter of
    part of a row is a loop over the tokens on the chip): a token's row is
    its own key beside, in each other lane block, the key of the tick's token
    that lives there or else what the pool holds.  Two tokens of a tick that
    share a row must therefore find each other, and they do where a page's
    tokens are consecutive rows of ``kI`` in their order, as a sequence's
    rows of a tick are: the token ``n * rows`` slots on is ``n * rows`` rows
    on."""
    P, rows, width = index_pages.shape
    T, dI = kI.shape
    pack = width // dI
    page = rows * pack
    kI = kI.astype(index_pages.dtype)
    flat = index_pages.reshape(P * rows, width)
    j = dest % page
    row, block = (dest // page) * rows + j % rows, j // rows
    if pack > 1:
        old = jnp.take(flat, row, axis=0, mode="clip")
        t = jnp.arange(T)
        parts = []
        for p in range(pack):
            src = t + (p - block) * rows
            at = jnp.clip(src, 0, T - 1)
            mine = jnp.logical_and(
                src == at, jnp.logical_and(jnp.take(row, at) == row,
                                           jnp.take(block, at) == p))
            parts.append(jnp.where(mine[:, None], jnp.take(kI, at, axis=0),
                                   old[:, p * dI:(p + 1) * dI]))
        kI = jnp.concatenate(parts, axis=1)
    return flat.at[row].set(kI, mode="drop").reshape(index_pages.shape)


def token_mask(scores: jax.Array, visible: jax.Array, topk: int
               ) -> jax.Array:
    """The chosen keys of every query as a mask: ``scores [..., K]`` float32,
    ``visible [..., K]`` -> of the visible keys the ``topk`` best-scored,
    equals shared out to the lower positions first (``lax.top_k``'s rule);
    every visible key where there are ``topk`` or fewer."""
    key = jnp.where(visible, sortable(scores), _INT_MIN)
    k = min(int(topk), key.shape[-1])
    kth = jnp.maximum(jax.lax.top_k(key, k)[0][..., -1:], _INT_MIN + 1)
    above = key > kth
    tied = key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return jnp.logical_or(above, jnp.logical_and(
        tied, jnp.cumsum(tied, axis=-1) <= room))


# -- one row a sequence (decode rows): jax.numpy on every backend -------------

def row_scores(qI, w, seq_keys, pos):
    """``qI [S, HI, dI]``, ``w [S, HI]`` float32 (scaled), ``seq_keys [S, K,
    dI]`` (a sequence's index keys in order), ``pos [S]`` -> ``[S, K]``
    float32, ``-inf`` at the keys after the row's own."""
    s = jnp.einsum("shd,skd->shk", qI, seq_keys,
                   preferred_element_type=jnp.float32)
    score = jnp.einsum("sh,shk->sk", w.astype(jnp.float32),
                       jnp.maximum(s, 0.0))
    seen = jnp.arange(seq_keys.shape[1])[None] <= pos[:, None]
    return jnp.where(seen, jnp.where(score == 0, 0.0, score), -jnp.inf)


def row_choice(scores, topk: int) -> Tuple[jax.Array, jax.Array]:
    """``scores [S, K]`` (``-inf``: not visible) -> ``(tokens [S, k], taken
    [S, k])``: the positions of the ``k = min(topk, K)`` best-scored keys,
    equals to the lower position (``lax.top_k`` is stable), and which of
    them are keys at all (a row with fewer than ``k`` visible keys takes
    them all)."""
    vals, idx = jax.lax.top_k(scores, min(int(topk), scores.shape[-1]))
    return idx.astype(jnp.int32), vals > -jnp.inf


def row_attention(q, pages, page_indices, tokens, taken, *, sm_scale: float):
    """``q [S, Hkv, G, D]`` over the tokens ``tokens [S, k]`` (positions in
    the row's sequence, ``taken`` of them real) of ``pages [P, page, 2 Hkv
    D]`` through ``page_indices [S, pp]`` -> ``[S, Hkv, G, D]``.  Gathers
    the chosen tokens' rows and reads no other token of the pool (an entry
    not taken reads the row's first chosen token and weighs 0)."""
    S, Hkv, G, D = q.shape
    P, page, width = pages.shape
    tok = jnp.where(taken, tokens, tokens[:, :1])
    pg = jnp.maximum(jnp.take_along_axis(page_indices, tok // page, axis=1),
                     0)
    got = jnp.take(pages.reshape(P * page, width), pg * page + tok % page,
                   axis=0).reshape(S, -1, 2, Hkv, D)
    k, v = got[:, :, 0], got[:, :, 1]                       # [S, k, Hkv, D]
    s = jnp.einsum("shgd,skhd->shgk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    ok = taken[:, None, None]
    s = jnp.where(ok, s, _MASK_VALUE)
    p = jnp.where(ok, jax.nn.softmax(s, axis=-1), 0.0)
    y = jnp.einsum("shgk,skhd->shgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return y.astype(q.dtype)


# -- the twins ----------------------------------------------------------------

def _visible(K: int, tile_pos0, tile_len):
    """``[NT, K, TILE]``: key ``c`` at or before row ``i`` of a tile, a live
    row."""
    c = jnp.arange(K)[None, :, None]
    i = jnp.arange(TILE)[None, None, :]
    return jnp.logical_and(c <= tile_pos0[:, None, None] + i,
                           i < tile_len[:, None, None])


def sequence_keys(index_pages, page_indices, dI: int):
    """``index_pages [P, rows, pack * dI]`` through ``page_indices [S, pp]``
    -> ``[S, padded_keys(pp, page), dI]``: every sequence's index keys in
    order, zeros behind its pages."""
    S, pp = page_indices.shape
    got = unpack_index_keys(
        jnp.take(index_pages, jnp.maximum(page_indices, 0), axis=0), dI)
    page = got.shape[2]
    return jnp.pad(got.reshape(S, pp * page, dI),
                   ((0, 0), (0, padded_keys(pp, page) - pp * page), (0, 0)))


def index_scores_twin(qI, w, index_pages, page_indices, tile_seq, tile_pos0,
                      tile_len):
    """``qI [NT, HI * TILE, dI]`` (index head ``h`` of a tile's row ``i`` at
    ``h * TILE + i``), ``w [NT, 1, HI * TILE]`` float32, ``index_pages [P,
    rows, pack * dI]`` through ``page_indices [S, pp]`` -> ``[NT, K, TILE]``
    int32 (``K = padded_keys(pp, page)``): row ``i``'s sortable score of key
    ``c``, the least integer where it does not see it."""
    NT, _, dI = qI.shape
    seq_keys = sequence_keys(index_pages, page_indices, dI)
    K = seq_keys.shape[1]
    keys = jnp.take(seq_keys, tile_seq, axis=0)             # [NT, K, dI]
    s = jnp.einsum("nkd,nrd->nkr", keys, qI,
                   preferred_element_type=jnp.float32)
    s = (jnp.maximum(s, 0.0) * w).reshape(NT, K, -1, TILE)
    return jnp.where(_visible(K, tile_pos0, tile_len),
                     sortable(jnp.sum(s, axis=2)), _INT_MIN)


def select_twin(scores, topk: int):
    """``scores [NT, K, TILE]`` int32 -> ``(stats [NT, 8, TILE] int32, bias
    [NT, K, TILE] bfloat16)``: row 0 of ``stats`` the largest ``tau`` with
    ``topk`` scores or more at or above it (at least one over the least
    integer: a key a row cannot see is never chosen), row 1 how many are;
    the bias 0 where ``score >= tau``."""
    k = min(int(topk), scores.shape[1])
    kth = jax.lax.top_k(scores.transpose(0, 2, 1), k)[0][..., -1]
    tau = jnp.maximum(kth, _INT_MIN + 1)[:, None]           # [NT, 1, TILE]
    hit = scores >= tau
    count = jnp.sum(hit, axis=1, keepdims=True, dtype=jnp.int32)
    stats = jnp.concatenate(
        [tau, count, jnp.zeros((scores.shape[0], 6, TILE), jnp.int32)], 1)
    return stats, jnp.where(hit, 0.0, _MASK_VALUE).astype(jnp.bfloat16)


def resolve_ties(scores, stats, bias, topk: int):
    """``bias`` with a row's threshold shared out where more keys hold it
    than the row has room for: the lower positions first.  Such a row is
    rare (two float32 scores equal at the threshold), so the pass runs
    only where one exists."""
    tau, count = stats[:, 0:1], stats[:, 1:2]
    over = count > topk                                      # [NT, 1, TILE]

    def shared():
        above = scores > tau
        tied = scores == tau
        room = topk - jnp.sum(above, axis=1, keepdims=True)
        picked = jnp.logical_or(above, jnp.logical_and(
            tied, jnp.cumsum(tied, axis=1) <= room))
        picked = jnp.where(over, picked, scores >= tau)
        return jnp.where(picked, 0.0, _MASK_VALUE).astype(bias.dtype)

    return jax.lax.cond(jnp.any(over), shared, lambda: bias)


def _gather_kv(pages, table, Hkv: int):
    """``pages [P, page, 2 Hkv D]`` through ``table [NT, n]`` (page ids)
    -> ``(k, v) [NT, Hkv, n * page, D]``."""
    got = jnp.take(pages, jnp.maximum(table, 0), axis=0)
    NT, n = table.shape
    _, page, width = pages.shape
    got = got.reshape(NT, n * page, 2, Hkv, width // (2 * Hkv)).transpose(
        2, 0, 3, 1, 4)
    return got[0], got[1]


def chunk_twin(q, pages, bias, tile_table, sm_scale: float):
    """``q [Hkv, G, NT * TILE, D]``, ``bias [NT, K, TILE]`` (0 at the keys
    the row across attends, :data:`_MASK_VALUE` at the others), ``tile_table
    [NT, pp]`` (each tile's sequence's pages) -> ``[Hkv, G, NT * TILE, D]``;
    a row with no key comes back zero."""
    Hkv, G, rows, D = q.shape
    NT = tile_table.shape[0]
    k, v = _gather_kv(pages, tile_table, Hkv)              # [NT, Hkv, K', D]
    K = k.shape[2]
    ok = (bias[:, :K].transpose(0, 2, 1) > 0.5 * _MASK_VALUE)[None, None]
    qt = q.reshape(Hkv, G, NT, TILE, D)
    s = jnp.einsum("hgnrd,nhkd->hgnrk", qt.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = jnp.where(ok, s, _MASK_VALUE)
    p = jnp.where(ok, jax.nn.softmax(s, axis=-1), 0.0)
    y = jnp.einsum("hgnrk,nhkd->hgnrd", p, v.astype(jnp.float32))
    return y.reshape(q.shape).astype(q.dtype)


# -- the kernels --------------------------------------------------------------

def _packed_queries(q, pack: int):
    """``q [..., dI]`` -> ``[..., pack, <rows>, pack * dI]`` on a new axis
    before the last two: copy ``p`` holds ``q`` at lanes ``p * dI`` on and
    zeros elsewhere, so that its product with a row of ``pack`` keys is the
    product with the ``p``-th of them."""
    dI = q.shape[-1]
    pad = [(0, 0)] * (q.ndim - 1)
    return jnp.stack([jnp.pad(q, pad + [(p * dI, (pack - 1 - p) * dI)])
                      for p in range(pack)], axis=-3)


def _index_eligible(qI, index_pages) -> bool:
    """Mosaic's layout: bf16 rows of 128 lanes, a page whole tiles of them."""
    _, rows, width = index_pages.shape
    return (qI.dtype == jnp.bfloat16 and index_pages.dtype == jnp.bfloat16
            and width == _LANES and rows % 16 == 0)


def _row_index_kernel(pos_ref, on_ref, table_ref,           # scalar prefetch
                      q_ref, w_ref, *rest, pack: int):
    # q_ref: [1, pack, HI, W] (:func:`_packed_queries`); w_ref: [1, HI, 1]
    # float32; then _STEP_PAGES pages of index keys [1, rows, W]; o_ref: [1,
    # pack, _STEP_PAGES * rows] float32 (lane block p's keys in row p);
    # kbuf: [_STEP_PAGES * rows, W]
    pages = rest[:_STEP_PAGES]
    o_ref, kbuf = rest[_STEP_PAGES:]
    s_, b = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[s_]
    rows = pages[0].shape[1]
    page = rows * pack
    keys = _STEP_PAGES * page
    dead = jnp.logical_or(on_ref[s_] == 0, b * keys > pos)

    @pl.when(dead)
    def _():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)

    @pl.when(jnp.logical_not(dead))
    def _():
        for i in range(_STEP_PAGES):
            kbuf[pl.ds(i * rows, rows), :] = pages[i][0]
        for p in range(pack):
            s = jax.lax.dot_general(
                q_ref[0, p], kbuf[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [HI, 8 rows]
            score = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                            keepdims=True)
            r = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
            c = b * keys + (r // rows) * page + p * rows + r % rows
            o_ref[0, pl.ds(p, 1), :] = jnp.where(
                c <= pos, jnp.where(score == 0, 0.0, score), -jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _row_index(qI, w, index_pages, page_indices, pos, on, *,
               interpret: bool):
    S, HI, dI = qI.shape
    _, rows, width = index_pages.shape
    pack = width // dI
    page = rows * pack
    pp = page_indices.shape[1]
    keys = _STEP_PAGES * page
    K = padded_keys(pp, page)

    def page_block(i):
        def index(s, b, pos, on, table):
            # a dead step fetches the row's last live step's pages again
            step = jnp.minimum(b, jnp.maximum(pos[s], 0) // keys)
            at = jnp.minimum(step * _STEP_PAGES + i, pp - 1)
            return jnp.maximum(table[s * pp + at], 0), 0, 0
        return pl.BlockSpec((1, rows, width), index)

    out = pl.pallas_call(
        functools.partial(_row_index_kernel, pack=pack),
        out_shape=jax.ShapeDtypeStruct((S, pack, K // pack), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((1, pack, HI, width),
                                   lambda s, b, *_: (s, 0, 0, 0)),
                      pl.BlockSpec((1, HI, 1), lambda s, b, *_: (s, 0, 0))]
            + [page_block(i) for i in range(_STEP_PAGES)],
            out_specs=pl.BlockSpec((1, pack, keys // pack),
                                   lambda s, b, *_: (s, 0, b)),
            grid=(S, K // keys),
            scratch_shapes=[pltpu.VMEM((_STEP_PAGES * rows, width),
                                       index_pages.dtype)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=ROW_INDEX_NAME,
    )(pos, on, page_indices.reshape(-1), _packed_queries(qI, pack),
      w[..., None], *([index_pages] * _STEP_PAGES))
    # [S, pack, pages, rows] -> a sequence's keys in order
    return out.reshape(S, pack, K // page, rows).swapaxes(1, 2).reshape(S, K)


def row_index_scores(qI, w, index_pages, page_indices, pos, on, *,
                     interpret: bool = False) -> jax.Array:
    """One query a sequence: ``qI [S, HI, dI]``, ``w [S, HI]`` float32,
    ``pos [S]``, ``on [S]`` (which rows are queries at all) -> ``[S, K]``
    float32 index scores over each row's sequence through the page table,
    ``-inf`` at the keys after the row's own and in a row that is not on
    (:func:`row_scores` over :func:`sequence_keys`, which is the twin).  The
    kernel reads the pages a row sees and no other, where the twin gathers
    every slot's whole table; recorded under :data:`KERNEL_ROW_INDEX`."""
    dI = qI.shape[-1]
    page = index_pages.shape[1] * index_pages.shape[2] // dI
    route = routes.take(
        KERNEL_ROW_INDEX, eligible=_index_eligible(qI, index_pages)
        and qI.shape[1] % 8 == 0, interpret=interpret,
        pallas=f"pallas-row-index-scores-{_STEP_PAGES * page}")
    if route == routes.XLA:
        scores = row_scores(
            qI, w, sequence_keys(index_pages, page_indices, dI), pos)
        return jnp.where(on[:, None], scores, -jnp.inf)
    return _row_index(qI, w.astype(jnp.float32), index_pages,
                      page_indices.astype(jnp.int32), pos.astype(jnp.int32),
                      on.astype(jnp.int32), interpret=interpret)


def _live_tile(tile_len):
    """``[NT]``: each tile's own number, a tile of no row the number of the
    live tile before it (live tiles come first in ``tile_layout``): a block
    indexed by it does not move, so a tile of no row fetches none."""
    n = jnp.arange(tile_len.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(tile_len > 0, n, 0))


def _select_kernel(src_ref, seq_ref, pos0_ref, len_ref, table_ref,  # prefetch
                   q_ref, w_ref, pages_hbm, stats_ref, bias_hbm,
                   s_sc, kbuf, ksems, bbuf, bsems, *,
                   topk: int, heads: int, pack: int, pages_per_seq: int):
    # q_ref: [1, pack, HI * TILE, W] (:func:`_packed_queries`); w_ref: [1, 1,
    # HI * TILE] float32; pages_hbm: [P, rows, W] (the index pool, in HBM);
    # stats_ref: [1, 8, TILE] int32; bias_hbm: [NT, K, TILE] bfloat16 (in
    # HBM); s_sc: [K, TILE] int32 (the tile's scores, never out of VMEM);
    # kbuf: [2, _STEP_PAGES, rows, W]; bbuf: [2, keys, TILE] bfloat16
    n = pl.program_id(0)
    seq, pos0, length = seq_ref[n], pos0_ref[n], len_ref[n]
    rows, width = pages_hbm.shape[1], pages_hbm.shape[2]
    page = rows * pack
    keys = key_step(page)
    # the steps up to the tile's last row's own key: one past them is never
    # scored, searched or written
    steps = (pos0 + length - 1) // keys + 1
    row = jax.lax.broadcasted_iota(jnp.int32, (8, TILE), 0)

    copies = functools.partial(step_copies, table_ref, pages_hbm, kbuf,
                               ksems, seq, pages_per_seq)

    def score(b, _):
        slot = b % 2

        @pl.when(b + 1 < steps)
        def _():
            for copy in copies(b + 1, 1 - slot):
                copy.start()

        for copy in copies(b, slot):
            copy.wait()
        k = kbuf[slot].reshape(_STEP_PAGES * rows, width)
        at = pl.multiple_of(b * keys, keys)
        for p in range(pack):
            # the keys of lane block p: tokens p * rows on of each page
            s = jax.lax.dot_general(
                k, q_ref[0, p], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [8 rows, HI * TILE]
            s = jnp.maximum(s, 0.0) * w_ref[0]
            acc = s[:, :TILE]
            for h in range(1, heads):
                acc = acc + s[:, h * TILE:(h + 1) * TILE]
            acc = jnp.where(acc == 0, 0.0, acc)
            bits = jax.lax.bitcast_convert_type(acc, jnp.int32)
            r = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            c = b * keys + (r // rows) * page + p * rows + r % rows
            i = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
            seen = jnp.logical_and(c <= pos0 + i, i < length)
            val = jnp.where(seen, bits ^ ((bits >> 31) & 0x7FFFFFFF),
                            _INT_MIN)
            for g in range(_STEP_PAGES):
                s_sc[pl.ds(at + g * page + p * rows, rows), :] = val[
                    g * rows:(g + 1) * rows]

    def block(j):
        return s_sc[pl.ds(pl.multiple_of(j * keys, keys), keys), :]

    def count_ge(cand):
        """How many of a row's scores are at or above ``cand [1, TILE]``."""
        def step(j, acc):
            hit = jnp.where(block(j) >= cand, 1, 0).astype(jnp.int32)
            return acc + jnp.sum(hit.reshape(keys // 8, 8, TILE), axis=0)
        acc = jax.lax.fori_loop(0, steps, step,
                                jnp.zeros((8, TILE), jnp.int32))
        return jnp.sum(acc, axis=0, keepdims=True)

    def bit(i, tau):
        # from the sign bit down, as an unsigned search offset by 2^31: the
        # least integer plus 2^31 is 0
        cand = jnp.where(i == 0, jnp.zeros_like(tau),
                         tau + jnp.left_shift(jnp.int32(1), 31 - i))
        return jnp.where(count_ge(cand) >= topk, cand, tau)

    def bias_copy(j, slot):
        return pltpu.make_async_copy(
            bbuf.at[slot],
            bias_hbm.at[n, pl.ds(pl.multiple_of(j * keys, keys), keys)],
            bsems.at[slot])

    @pl.when(length == 0)
    def _():
        stats_ref[0] = jnp.where(row == 0, _INT_MIN + 1, 0)

    @pl.when(length > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()
        jax.lax.fori_loop(0, steps, score, None)

        tau = jax.lax.fori_loop(0, 32, bit,
                                jnp.full((1, TILE), _INT_MIN, jnp.int32))
        tau = jnp.maximum(tau, _INT_MIN + 1)
        held = count_ge(tau)
        stats_ref[0] = jnp.where(row == 0, tau, jnp.where(row == 1, held, 0))

        # a row whose threshold more keys hold than it has room for shares
        # it out to the lower positions: the largest ``cut`` with no more
        # than ``room`` of them before it, by bisection over the position's
        # bits; a tile with no such row (nearly every one) makes no pass
        room = topk - count_ge(tau + 1)
        bits = max(int(s_sc.shape[0]).bit_length(), 1)

        def tied_before(cut):
            def step(j, acc):
                blk = block(j)
                c = j * keys + jax.lax.broadcasted_iota(jnp.int32, blk.shape,
                                                        0)
                hit = jnp.where(jnp.logical_and(blk == tau, c < cut), 1, 0)
                return acc + jnp.sum(hit.astype(jnp.int32).reshape(
                    keys // 8, 8, TILE), axis=0)
            acc = jax.lax.fori_loop(0, steps, step,
                                    jnp.zeros((8, TILE), jnp.int32))
            return jnp.sum(acc, axis=0, keepdims=True)

        def position_bit(i, cut):
            cand = cut + jnp.left_shift(jnp.int32(1), bits - 1 - i)
            return jnp.where(tied_before(cand) <= room, cand, cut)

        shared = jnp.max(jnp.where(held > topk, 1, 0)) > 0
        cut = jax.lax.fori_loop(
            0, jnp.where(shared, bits, 0), position_bit,
            jnp.where(shared, jnp.zeros_like(tau), jnp.int32(2 ** bits - 1)))

        def write(j, _):
            slot = j % 2
            blk = block(j)
            c = j * keys + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
            hit = jnp.logical_or(blk > tau,
                                 jnp.logical_and(blk == tau, c < cut))

            @pl.when(j >= 2)
            def _():
                bias_copy(j - 2, slot).wait()

            bbuf[slot] = jnp.where(hit, 0.0, _MASK_VALUE).astype(bbuf.dtype)
            bias_copy(j, slot).start()

        jax.lax.fori_loop(0, steps, write, None)

        @pl.when(steps >= 2)
        def _():
            bias_copy(steps - 2, steps % 2).wait()

        bias_copy(steps - 1, (steps - 1) % 2).wait()


def select_vmem_bytes(K: int, qrows: int, keys: int, pack: int) -> int:
    """What a call of the selection kernel keeps in VMEM: a tile's scores;
    its packed queries, two buffers; four results of a product (a key
    step's rows against every index head of the tile) of working room; the
    key steps and bias blocks in flight, the weights and the statistics
    inside two MiB more."""
    return (K * TILE * 4 + 2 * pack * qrows * _LANES * 2
            + 4 * (keys // pack) * qrows * 4 + 2 * 2 ** 20)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select(qI, w, index_pages, page_indices, tile_seq, tile_pos0, tile_len,
            *, topk: int, interpret: bool):
    NT, qrows, dI = qI.shape
    _, rows, width = index_pages.shape
    pack = width // dI
    page = rows * pack
    pp = page_indices.shape[1]
    keys, K = key_step(page), padded_keys(pp, page)
    # a tile of no row fetches the queries the tile before it fetched: none
    mine = lambda n, src, *_: (src[n],) + (0,) * 3          # noqa: E731
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, heads=qrows // TILE,
                          pack=pack, pages_per_seq=pp),
        out_shape=(jax.ShapeDtypeStruct((NT, 8, TILE), jnp.int32),
                   jax.ShapeDtypeStruct((NT, K, TILE), jnp.bfloat16)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[pl.BlockSpec((1, pack, qrows, width), mine),
                      pl.BlockSpec((1, 1, qrows),
                                   lambda n, src, *_: (src[n], 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec((1, 8, TILE), lambda n, *_: (n, 0, 0)),
                       pl.BlockSpec(memory_space=pl.ANY)),
            grid=(NT,),
            scratch_shapes=[
                pltpu.VMEM((K, TILE), jnp.int32),
                pltpu.VMEM((2, _STEP_PAGES, rows, width), index_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((2, keys, TILE), jnp.bfloat16),
                pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=select_vmem_bytes(K, qrows, keys, pack)),
        interpret=interpret, name=SELECT_NAME,
    )(_live_tile(tile_len), tile_seq, tile_pos0, tile_len,
      page_indices.reshape(-1), _packed_queries(qI, pack), w, index_pages)


def index_select(qI, w, index_pages, page_indices, tile_seq, tile_pos0,
                 tile_len, *, topk: int, interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    """A tick's tiled prompt rows' choice: every row's threshold
    (:func:`select_twin`'s ``stats [NT, 8, TILE]``) and the bias ``[NT, K,
    TILE]`` bfloat16 its choice leaves over its sequence's keys
    (:func:`index_scores_twin` says of what scores, ``qI``, ``w`` and the
    tiles as there), a shared threshold given to the lower positions
    (:func:`resolve_ties`).  ONE Pallas kernel on a TPU, the scores never
    out of VMEM, and it writes the bias of a tile's LIVE key steps alone:
    what lies past a tile's last row, and the whole of a tile of no row, is
    whatever the buffer held (nothing reads it: :func:`chunk_attention`
    stops at the same step).  Else the three twins.  Recorded under
    :data:`KERNEL_INDEX` and :data:`KERNEL_SELECT`: what the one kernel
    does of each."""
    page = index_pages.shape[1] * index_pages.shape[2] // qI.shape[-1]
    ok = dict(eligible=_index_eligible(qI, index_pages), interpret=interpret)
    route = routes.take(
        KERNEL_INDEX, pallas=f"pallas-index-scores-{TILE}x{key_step(page)}",
        **ok)
    routes.take(KERNEL_SELECT, pallas=f"pallas-index-select-{TILE}-bisect32",
                **ok)
    if route == routes.XLA:
        scores = index_scores_twin(qI, w, index_pages, page_indices,
                                   tile_seq, tile_pos0, tile_len)
        stats, bias = select_twin(scores, topk)
        return stats, resolve_ties(scores, stats, bias, topk)
    return _select(qI, w.astype(jnp.float32), index_pages,
                   page_indices.astype(jnp.int32),
                   tile_seq.astype(jnp.int32), tile_pos0.astype(jnp.int32),
                   tile_len.astype(jnp.int32), topk=int(topk),
                   interpret=interpret)


def _chunk_kernel(seq_ref, pos0_ref, len_ref, table_ref,    # scalar prefetch
                  q_ref, bias_hbm, pages_hbm, o_ref,
                  buf, sems, bbuf, bsems, vt_sc, m_sc, l_sc, acc_sc, *,
                  sm_scale: float, pages_per_seq: int):
    # q_ref, o_ref: [Hkv, G, TILE, D] (one tile); bias_hbm: [NT, K, TILE]
    # bfloat16 (in HBM: a key step's block comes with the step's pages, so
    # what lies past a tile's last step, and a tile of no row, is never
    # fetched); pages_hbm: [P, page, 2 Hkv D]; buf: [2, _STEP_PAGES, page,
    # 2 Hkv D]; bbuf: [2, keys, TILE] bfloat16; vt_sc: [Hkv, D, keys];
    # m_sc, l_sc: [Hkv, 1, R]; acc_sc: [Hkv, D, R] with R = G * TILE (query
    # head g's i-th row at lane ``g * TILE + i``).  Scores, weights and
    # the accumulator are held TRANSPOSED (keys down, the tile's rows x
    # heads across)
    Hkv, G, _, D = q_ref.shape
    page = pages_hbm.shape[1]
    keys = _STEP_PAGES * page
    n = pl.program_id(0)
    seq, pos0, length = seq_ref[n], pos0_ref[n], len_ref[n]
    R = G * TILE
    last = pos0 + length - 1

    def copies(b, slot):
        yield from step_copies(table_ref, pages_hbm, buf, sems, seq,
                               pages_per_seq, b, slot)
        yield pltpu.make_async_copy(
            bias_hbm.at[n, pl.ds(pl.multiple_of(b * keys, keys), keys)],
            bbuf.at[slot], bsems.at[slot])

    def one_step(b, _):
        slot = b % 2

        @pl.when((b + 1) * keys <= last)
        def _():
            for copy in copies(b + 1, 1 - slot):
                copy.start()

        for copy in copies(b, slot):
            copy.wait()
        # masked entries take _MASK_VALUE (a score added to it IS it) and
        # weigh exp(_MASK_VALUE - m) = 0 once a row has met a key it
        # attends; what a row gathered before that is scaled by the same 0
        # then, and every live row attends itself
        bias = bbuf[slot].astype(jnp.float32)                  # [keys, TILE]
        bias = jnp.concatenate([bias] * G, axis=1)
        for h in range(Hkv):
            vt = buf[slot, :, :, pl.ds((Hkv + h) * D, D)].reshape(
                keys, D).astype(jnp.float32).T.astype(vt_sc.dtype)
            vt_sc[h] = vt
            q = q_ref[h].reshape(R, D)
            k = buf[slot, :, :, pl.ds(h * D, D)].reshape(keys, D)
            fold_head(h, q, k, bias, 1, vt_sc, m_sc, l_sc, acc_sc, sm_scale)

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        for copy in copies(0, 0):
            copy.start()
        jax.lax.fori_loop(0, last // keys + 1, one_step, None)
        finish_tile(o_ref, m_sc, l_sc, acc_sc, length, _MASK_VALUE)


def chunk_vmem_bytes(Hkv: int, G: int, D: int, page: int) -> int:
    """What a call of the chunk kernel keeps in VMEM: a tile of q and of
    the output, two buffers each; two key steps as fetched, with their
    blocks of the bias, and V of one transposed; the accumulator of every
    head; and seven score tiles (a key step against every query head of a
    KV head) of working room."""
    keys, R = _STEP_PAGES * page, G * TILE
    tile = Hkv * R * D * 2
    return (2 * 2 * tile + 2 * keys * TILE * 2
            + 2 * keys * 2 * Hkv * D * 2 + Hkv * D * keys * 2
            + Hkv * D * R * 4 + 8 * keys * R * 4)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _chunk(q, pages, bias, tile_seq, tile_pos0, tile_len, page_indices, *,
           sm_scale: float, interpret: bool):
    Hkv, G, rows, D = q.shape
    _, page, width = pages.shape
    NT = rows // TILE
    pp = page_indices.shape[1]
    keys, R = _STEP_PAGES * page, G * TILE
    K = bias.shape[1]
    assert width == 2 * Hkv * D, (pages.shape, q.shape)
    assert bias.shape == (NT, K, TILE) and K == padded_keys(pp, page), (
        bias.shape, pp)
    tile = pl.BlockSpec((Hkv, G, TILE, D), lambda n, *_: (0, 0, n, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sm_scale=sm_scale,
                          pages_per_seq=pp),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile,
            grid=(NT,),
            scratch_shapes=[
                pltpu.VMEM((2, _STEP_PAGES, page, width), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((2, keys, TILE), bias.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((Hkv, D, keys), pages.dtype),
                pltpu.VMEM((Hkv, 1, R), jnp.float32),
                pltpu.VMEM((Hkv, 1, R), jnp.float32),
                pltpu.VMEM((Hkv, D, R), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=chunk_vmem_bytes(Hkv, G, D, page)),
        interpret=interpret, name=CHUNK_NAME,
    )(tile_seq, tile_pos0, tile_len, page_indices.reshape(-1), q, bias,
      pages)


def eligible(D: int, page: int, dtype) -> bool:
    """Mosaic's layout: heads of 128 in bf16, slabs of whole tiles."""
    return (D == _LANES and jnp.dtype(dtype) == jnp.bfloat16
            and page % 16 == 0)


def chunk_attention(q, pages, bias, tile_seq, tile_pos0, tile_len,
                    page_indices, *, sm_scale: float,
                    interpret: bool = False) -> jax.Array:
    """Attention of the tiled prompt rows of a tick under their choice: ``q
    [Hkv, G, NT * TILE, D]`` over ``pages [P, page, 2 Hkv D]`` through
    ``page_indices [S, pp]``, tile ``n`` rows ``tile_len[n]`` (0: no row,
    and zeros come back) of sequence ``tile_seq[n]`` from position
    ``tile_pos0[n]`` on; ``bias [NT, padded_keys(pp, page), TILE]``
    bfloat16, 0 where the row across attends the key down (which is never
    one after it) and :data:`_MASK_VALUE` elsewhere; of a tile's bias only
    the key steps up to its last row's are read, and of a tile of no row
    nothing (:func:`index_select` writes no more).  The Pallas kernel on a
    TPU, else the twin (which reads every step: give it a whole bias);
    recorded under :data:`KERNEL_PREFILL`."""
    page, D = pages.shape[1], q.shape[-1]
    route = routes.take(
        KERNEL_PREFILL, eligible=eligible(D, page, pages.dtype)
        and q.dtype == pages.dtype, interpret=interpret,
        pallas=f"pallas-tokensel-chunk-{TILE}x{_STEP_PAGES * page}")
    if route == routes.XLA:
        return chunk_twin(q, pages, bias,
                          jnp.take(page_indices, tile_seq, axis=0), sm_scale)
    return _chunk(q, pages, bias.astype(jnp.bfloat16),
                  tile_seq.astype(jnp.int32), tile_pos0.astype(jnp.int32),
                  tile_len.astype(jnp.int32), page_indices.astype(jnp.int32),
                  sm_scale=sm_scale, interpret=interpret)


def decode_route(topk: int) -> str:
    """Record and return the one-row path's route: the gather by token."""
    return routes.take(KERNEL_DECODE, eligible=False,
                       xla=f"xla-token-gather-{int(topk)}")
