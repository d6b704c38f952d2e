"""Learned block selection over a paged pool, and attention over the chosen
blocks (Pallas TPU kernels).

A ``minicpm4`` layer (``models/minicpm_sala.py``) attends, from position
``dense_len`` on, only the keys of ``topk`` blocks of ``block`` tokens: the
first ``init_blocks``, those that overlap the last ``window`` positions, and
the best-scored others.  **A block is a page** (``block == page_size``), so
what is chosen is a set of pages a (query, KV head).

The pool of such a layer is ``[P, 2 * Hkv, page, D]``: each page a slab of
``page`` rows for the K and for the V of every KV head (K of head ``g`` at
``2 g``, V at ``2 g + 1``).  Heads lead the tokens because there are two of
them: ``[P, page, 4, D]`` would lay four combined heads out in sixteen
sublanes, four times the bytes; here a slab is whole tiles.  Beside it
lives the pool of **compressed keys** ``[P, Hkv * page / stride, D]``
float32: the mean key of every window of ``kernel`` tokens at ``stride``,
kept in the page its window starts in (row ``g * page / stride + w``).  Both
lead with the page dim, so ``inference/v2/kv_state.py``'s verbs move them
as they move every pool.

- :func:`block_mask` / :func:`block_list` (``jax.numpy``; scope
  ``select``): compressed scores ``softmax_j(q . c_j / sqrt(D))`` over the windows that END at or before
  the query, summed over a group's heads, a block's score the maximum over
  the windows that overlap it (a max-pool of ``2 stride`` -> 5 at stride
  ``page / stride`` -> 4, padding 1), forced blocks at ``+inf``, the
  ``topk`` largest.  A query before ``dense_len`` chooses every block up to
  its own.
- :func:`chunk_attention` (kernel ``sparse_chunk_attention``): the prompt
  chunks of a fused tick, rows in ``ops/linear_attention.py:tile_layout``'s
  tiles, a dense pass over the sequence's pages **under the per-query
  mask** of chosen blocks (key blocks past a tile's last row are skipped;
  at 8k-16k the pass is a few percent of a tick and exactly (vi)).
- :func:`decode_attention` (kernel ``sparse_decode_attention``): one query a
  row over a **page list a (row, KV head)**: it fetches the listed pages
  and no other.

Each kernel has a ``jax.numpy`` twin taken on the CPU; which ran is
recorded under :data:`KERNEL_PREFILL` / :data:`KERNEL_DECODE`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes

KERNEL = "sparse_attention"
KERNEL_PREFILL = KERNEL + "_prefill"
KERNEL_DECODE = KERNEL + "_decode"
CHUNK_NAME = "sparse_chunk_attention"
DECODE_NAME = "sparse_decode_attention"

_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_LANES = 128
# query rows a tile (ops/linear_attention.py's CHUNK: one layout for both
# mixers) and pages a key step
TILE = 128
_STEP_PAGES = 8
_VMEM_LIMIT = 48 * 1024 * 1024


class Selection(NamedTuple):
    """The family's ``sparse_config``; the defaults are MiniCPM4's."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def check(self, page_size: int) -> "Selection":
        assert self.block_size == page_size, (
            f"a chosen block is a page: block_size {self.block_size}, "
            f"page_size {page_size}")
        assert self.kernel_size == 2 * self.kernel_stride and \
            self.block_size % self.kernel_stride == 0, self
        return self

    @property
    def windows_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    def list_width(self) -> int:
        """Pages a decode row's list may hold: the chosen ``topk``, or
        every page of a context under ``dense_len``."""
        return max(self.topk, -(-self.dense_len // self.block_size))


# -- the selection ------------------------------------------------------------

def block_scores(q, ckeys, pos, sel: Selection, sm_scale: float):
    """``q [B, R, H, D]``, ``ckeys [B, Hkv, NW, D]`` float32 (window ``j``
    of sequence ``b``), ``pos [B, R]`` -> ``(score [B, R, Hkv, NB], visible
    [B, R, 1, NB])``: a block's score for a query (forced blocks ``+inf``,
    blocks after the query's own ``-inf``), and which blocks hold a key at
    or before the query.  A query before ``dense_len`` scores its blocks by
    their number: it chooses them all."""
    B, R, H, D = q.shape
    Hkv, NW = ckeys.shape[1], ckeys.shape[2]
    wpb = sel.windows_per_block
    NB = NW // wpb
    s = jnp.einsum("brgid,bgjd->brgij",
                   q.reshape(B, R, Hkv, H // Hkv, D).astype(jnp.float32),
                   ckeys, precision=jax.lax.Precision.HIGHEST) * sm_scale
    n_win = (pos - sel.kernel_size + 1) // sel.kernel_stride + 1
    seen = (jnp.arange(NW)[None, None] < n_win[..., None])[:, :, None]
    s = jnp.where(seen[:, :, :, None], s, _MASK_VALUE)
    p = jnp.where(seen[:, :, :, None], jax.nn.softmax(s, axis=-1), 0.0)
    r = jnp.where(seen, jnp.sum(p, axis=3), -jnp.inf)        # [B,R,Hkv,NW]
    # windows 4 m - 1 .. 4 m + 3 overlap block m
    pad = jnp.pad(r, ((0, 0),) * 3 + ((1, wpb),), constant_values=-jnp.inf)
    score = functools.reduce(jnp.maximum, [
        pad[..., i:i + NB * wpb].reshape(B, R, Hkv, NB, wpb)[..., 0]
        for i in range(wpb + 1)])
    m = jnp.arange(NB)[None, None, None]
    at = lambda a: a[:, :, None, None]
    forced = jnp.logical_or(
        m < sel.init_blocks,
        m >= at(jnp.maximum(pos - sel.window_size + 1, 0) // sel.block_size))
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(at(pos < sel.dense_len), m.astype(jnp.float32), score)
    visible = m <= at(pos // sel.block_size)
    return jnp.where(visible, score, -jnp.inf), visible


def block_mask(q, ckeys, pos, sel: Selection, sm_scale: float):
    """The chosen blocks of every query as a mask ``[B, R, Hkv, NB]``:
    every visible block before ``dense_len``, from there on the ``topk``
    best-scored.  Neighbouring blocks often tie (the window that straddles
    them is the best of both), so the ``topk``-th score is shared out as
    ``lax.top_k`` shares it: to the lower block numbers first."""
    score, visible = block_scores(q, ckeys, pos, sel, sm_scale)
    k = min(sel.topk, score.shape[-1])
    kth = jax.lax.top_k(score, k)[0][..., -1:]
    above = score > kth
    tied = score == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    picked = jnp.logical_or(above, jnp.logical_and(
        tied, jnp.cumsum(tied, axis=-1) <= room))
    dense = (pos < sel.dense_len)[:, :, None, None]
    return jnp.logical_and(visible, jnp.logical_or(dense, picked))


def block_list(q, ckeys, pos, sel: Selection, sm_scale: float):
    """The chosen blocks of every query as a list: ``(blocks [B, R, Hkv,
    L], count [B, R])`` with ``L = sel.list_width()``, a query's ``count``
    chosen blocks in ascending order first (so its own block, which is
    always chosen, is the last of them) and ``NB`` behind them."""
    score, _ = block_scores(q, ckeys, pos, sel, sm_scale)
    NB = score.shape[-1]
    L = min(sel.list_width(), NB)
    n_blocks = pos // sel.block_size + 1
    count = jnp.minimum(jnp.where(pos < sel.dense_len, n_blocks,
                                  jnp.minimum(sel.topk, n_blocks)), L)
    _, idx = jax.lax.top_k(score, L)                           # [B,R,Hkv,L]
    taken = jnp.arange(L)[None, None, None] < count[..., None, None]
    blocks = jnp.sort(jnp.where(taken, idx, NB), axis=-1)
    return blocks.astype(jnp.int32), count.astype(jnp.int32)


# -- the twins ----------------------------------------------------------------

def _gather_kv(pages, table):
    """``pages [P, 2 Hkv, page, D]`` through ``table [..., n]`` (page ids)
    -> ``(k, v) [..., Hkv, n * page, D]``."""
    got = jnp.take(pages, jnp.maximum(table, 0), axis=0)
    lead = table.shape[:-1]
    n, (_, c, page, D) = table.shape[-1], pages.shape
    got = jnp.moveaxis(got, -4, -3).reshape(lead + (c, n * page, D))
    return got[..., 0::2, :, :], got[..., 1::2, :, :]


def chunk_twin(q, pages, allowed, tile_table, sm_scale: float):
    """``q [Hkv, G, NT * TILE, D]``, ``allowed [Hkv, NT * TILE, K]`` (0 / 1
    a key), ``tile_table [NT, pp]`` (each tile's sequence's pages) ->
    ``[Hkv, G, NT * TILE, D]``."""
    Hkv, G, rows, D = q.shape
    NT = tile_table.shape[0]
    k, v = _gather_kv(pages, tile_table)                   # [NT, Hkv, K, D]
    K = k.shape[2]
    qt = q.reshape(Hkv, G, NT, rows // NT, D)
    ok = allowed[:, :, :K].reshape(Hkv, NT, rows // NT, K) > 0.5
    s = jnp.einsum("hgnrd,nhkd->hgnrk", qt.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = jnp.where(ok[:, None], s, _MASK_VALUE)
    p = jnp.where(ok[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    y = jnp.einsum("hgnrk,nhkd->hgnrd", p, v.astype(jnp.float32))
    return y.reshape(q.shape).astype(q.dtype)


def decode_twin(q, pages, page_list, count, last_len, sm_scale: float):
    """``q [S, Hkv, G, D]``, ``page_list [S, Hkv, L]`` (page ids, the first
    ``count [S]`` of each attended: whole but the last, of which the first
    ``last_len [S]`` keys) -> ``[S, Hkv, G, D]``."""
    S, Hkv, G, D = q.shape
    L, page = page_list.shape[-1], pages.shape[2]
    got = jnp.take(pages, jnp.maximum(page_list, 0), axis=0)
    # [S, Hkv, L, 2 Hkv, page, D]: a KV head reads its own list
    g = jnp.arange(Hkv)
    k = got[:, g, :, 2 * g].reshape(Hkv, S, L * page, D)
    v = got[:, g, :, 2 * g + 1].reshape(Hkv, S, L * page, D)
    e = jnp.arange(L * page) // page
    ok = jnp.logical_or(
        e[None] < count[:, None] - 1,
        jnp.logical_and(e[None] == count[:, None] - 1,
                        jnp.arange(L * page)[None] % page
                        < last_len[:, None]))                  # [S, K]
    s = jnp.einsum("shgd,hskd->shgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = jnp.where(ok[:, None, None], s, _MASK_VALUE)
    p = jnp.where(ok[:, None, None], jax.nn.softmax(s, axis=-1), 0.0)
    y = jnp.einsum("shgk,hskd->shgd", p, v.astype(jnp.float32))
    return y.astype(q.dtype)


# -- the kernels --------------------------------------------------------------

def _fold(s, ok, v, m_prev, l_prev, acc_prev, dtype):
    """One step of the running softmax: scores ``s [R, K]`` under ``ok``
    against values ``v [K, D]``."""
    s = jnp.where(ok, s, _MASK_VALUE)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc = alpha * acc_prev + jnp.dot(p.astype(dtype), v,
                                     preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _chunk_kernel(seq_ref, nblk_ref, table_ref,             # scalar prefetch
                  q_ref, mask_ref, *rest, sm_scale: float, steps: int):
    # q, o: [1, G, TILE, D]; mask: [1, TILE, keys]; then _STEP_PAGES K
    # slabs and as many V slabs, each [1, 1, page, D]
    kv = rest[:2 * _STEP_PAGES]
    o_ref, kbuf, vbuf, m_sc, l_sc, acc_sc = rest[2 * _STEP_PAGES:]
    n, b = pl.program_id(1), pl.program_id(2)
    G = q_ref.shape[1]
    page = kv[0].shape[2]

    @pl.when(b == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(b < nblk_ref[n])
    def _():
        for i in range(_STEP_PAGES):
            kbuf[pl.ds(i * page, page), :] = kv[i][0, 0]
            vbuf[pl.ds(i * page, page), :] = kv[_STEP_PAGES + i][0, 0]
        ok = mask_ref[0].astype(jnp.float32) > 0.5
        k, v = kbuf[...], vbuf[...]

        def head(h, _):
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            m_sc[h], l_sc[h], acc_sc[h] = _fold(
                s, ok, v, m_sc[h], l_sc[h], acc_sc[h], k.dtype)
            return None

        jax.lax.fori_loop(0, G, head, None)

    @pl.when(b == steps - 1)
    def _():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _chunk(q, pages, allowed, tile_seq, tile_nblk, page_indices, *,
           sm_scale: float, interpret: bool):
    Hkv, G, rows, D = q.shape
    _, combined, page, _ = pages.shape
    NT = rows // TILE
    pp = page_indices.shape[1]
    keys = _STEP_PAGES * page
    steps = allowed.shape[-1] // keys
    assert allowed.shape[-1] == steps * keys, (allowed.shape, keys)

    def step_of(n, b, nblk):
        return jnp.minimum(b, jnp.maximum(nblk[n] - 1, 0))

    def slab(i, c):
        def index(g, n, b, seq, nblk, table):
            p = jnp.minimum(step_of(n, b, nblk) * _STEP_PAGES + i, pp - 1)
            return (jnp.maximum(table[seq[n] * pp + p], 0), 2 * g + c, 0, 0)
        return pl.BlockSpec((1, 1, page, D), index)

    tile = pl.BlockSpec((1, G, TILE, D), lambda g, n, b, *_: (g, 0, n, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sm_scale=sm_scale, steps=steps),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[tile,
                      pl.BlockSpec((1, TILE, keys),
                                   lambda g, n, b, seq, nblk, table:
                                   (g, n, step_of(n, b, nblk)))]
            + [slab(i, 0) for i in range(_STEP_PAGES)]
            + [slab(i, 1) for i in range(_STEP_PAGES)],
            out_specs=tile,
            grid=(Hkv, NT, steps),
            scratch_shapes=[
                pltpu.VMEM((keys, D), pages.dtype),
                pltpu.VMEM((keys, D), pages.dtype),
                pltpu.VMEM((G, TILE, 1), jnp.float32),
                pltpu.VMEM((G, TILE, 1), jnp.float32),
                pltpu.VMEM((G, TILE, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=CHUNK_NAME,
    )(tile_seq, tile_nblk, page_indices.reshape(-1), q, allowed,
      *([pages] * (2 * _STEP_PAGES)))


def eligible(D: int, page: int, dtype) -> bool:
    """Mosaic's layout: heads of 128 in bf16, slabs of whole tiles."""
    return (D == _LANES and jnp.dtype(dtype) == jnp.bfloat16
            and page % 16 == 0)


def mask_width(pages_per_seq: int, page: int) -> int:
    """Keys the chunk kernel's mask spans: whole key steps."""
    return -(-pages_per_seq // _STEP_PAGES) * _STEP_PAGES * page


def chunk_attention(q, pages, allowed, tile_seq, tile_last, page_indices, *,
                    sm_scale: float, interpret: bool = False) -> jax.Array:
    """Attention of the tiled prompt rows of a tick: ``q [Hkv, G, NT *
    TILE, D]`` over ``pages [P, 2 Hkv, page, D]``; ``allowed [Hkv, NT *
    TILE, mask_width]`` says which keys (by position in the tile's
    sequence, ``tile_seq [NT]``, through ``page_indices [S, pp]``) a row
    of a KV head attends: chosen block, causal and live all in one;
    ``tile_last [NT]`` the position of a tile's last live row (-1: no row).
    The Pallas kernel on a TPU, else the twin; recorded under
    :data:`KERNEL_PREFILL`."""
    page, D = pages.shape[2], pages.shape[3]
    route = routes.take(
        KERNEL_PREFILL, eligible=eligible(D, page, pages.dtype)
        and q.dtype == pages.dtype, interpret=interpret,
        pallas=f"pallas-sparse-chunk-{TILE}x{_STEP_PAGES * page}")
    if route == routes.XLA:
        return chunk_twin(q, pages, allowed,
                          jnp.take(page_indices, tile_seq, axis=0), sm_scale)
    nblk = (tile_last + _STEP_PAGES * page) // (_STEP_PAGES * page)
    return _chunk(q, pages, allowed.astype(pages.dtype), tile_seq,
                  nblk.astype(jnp.int32), page_indices.astype(jnp.int32),
                  sm_scale=sm_scale, interpret=interpret)


def _decode_kernel(list_ref, count_ref, last_ref,           # scalar prefetch
                   q_ref, *rest, sm_scale: float, steps: int):
    # q, o: [1, 1, G, D]; then _STEP_PAGES K slabs and as many V slabs
    kv = rest[:2 * _STEP_PAGES]
    o_ref, kbuf, vbuf, m_sc, l_sc, acc_sc = rest[2 * _STEP_PAGES:]
    s_, b = pl.program_id(0), pl.program_id(2)
    G = q_ref.shape[2]
    page = kv[0].shape[2]
    keys = _STEP_PAGES * page
    count = count_ref[s_]

    @pl.when(b == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(b * _STEP_PAGES < count)
    def _():
        for i in range(_STEP_PAGES):
            kbuf[pl.ds(i * page, page), :] = kv[i][0, 0]
            vbuf[pl.ds(i * page, page), :] = kv[_STEP_PAGES + i][0, 0]
        col = jax.lax.broadcasted_iota(jnp.int32, (G, keys), 1)
        entry = b * _STEP_PAGES + col // page
        ok = jnp.logical_or(
            entry < count - 1,
            jnp.logical_and(entry == count - 1, col % page < last_ref[s_]))
        k, v = kbuf[...], vbuf[...]
        s = jax.lax.dot_general(
            q_ref[0, 0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        m_sc[...], l_sc[...], acc_sc[...] = _fold(
            s, ok, v, m_sc[...], l_sc[...], acc_sc[...], k.dtype)

    @pl.when(b == steps - 1)
    def _():
        l = l_sc[...]
        o_ref[0, 0] = jnp.where(l > 0, acc_sc[...] / jnp.maximum(l, 1e-30),
                                0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _decode(q, pages, page_list, count, last_len, *, sm_scale: float,
            interpret: bool):
    S, Hkv, G, D = q.shape
    _, combined, page, _ = pages.shape
    L = page_list.shape[-1]
    steps = -(-L // _STEP_PAGES)

    def slab(i, c):
        def index(s, g, b, lst, count, last):
            e = jnp.minimum(b * _STEP_PAGES + i,
                            jnp.maximum(count[s] - 1, 0))
            return (jnp.maximum(lst[(s * Hkv + g) * L + e], 0),
                    2 * g + c, 0, 0)
        return pl.BlockSpec((1, 1, page, D), index)

    row = pl.BlockSpec((1, 1, G, D), lambda s, g, b, *_: (s, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, steps=steps),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[row] + [slab(i, 0) for i in range(_STEP_PAGES)]
            + [slab(i, 1) for i in range(_STEP_PAGES)],
            out_specs=row,
            grid=(S, Hkv, steps),
            scratch_shapes=[
                pltpu.VMEM((_STEP_PAGES * page, D), pages.dtype),
                pltpu.VMEM((_STEP_PAGES * page, D), pages.dtype),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=DECODE_NAME,
    )(page_list.reshape(-1), count, last_len, q,
      *([pages] * (2 * _STEP_PAGES)))


def decode_attention(q, pages, page_list, count, last_len, *,
                     sm_scale: float, interpret: bool = False) -> jax.Array:
    """Attention of one query a row over its chosen pages: ``q [S, Hkv, G,
    D]``, ``page_list [S, Hkv, L]`` page ids of which row ``s`` attends the
    first ``count[s]`` (0: an idle row, which comes back zero and fetches
    nothing new), all whole but the last, of which the first ``last_len[s]``
    keys.  The Pallas kernel on a TPU, else the twin; recorded under
    :data:`KERNEL_DECODE`."""
    page, D = pages.shape[2], pages.shape[3]
    L = page_list.shape[-1]
    route = routes.take(
        KERNEL_DECODE, eligible=eligible(D, page, pages.dtype)
        and q.dtype == pages.dtype and q.shape[2] % 8 == 0,
        interpret=interpret, pallas=f"pallas-sparse-decode-{L}x{page}")
    if route == routes.XLA:
        return decode_twin(q, pages, page_list, count, last_len, sm_scale)
    return _decode(q, pages, page_list.astype(jnp.int32),
                   count.astype(jnp.int32), last_len.astype(jnp.int32),
                   sm_scale=sm_scale, interpret=interpret)
