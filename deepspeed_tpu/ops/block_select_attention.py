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
  tiles, laid out as ``ops/paged_prefill.py`` is.  A grid step is a tile;
  inside it a loop over the key steps (eight pages) up to the tile's last
  row, the pages fetched whole and double-buffered, so no step runs that
  no row of the tile can see.  **Every query head of a KV head rides one
  product** (``G x TILE`` MXU rows against a step's keys) and **scores are
  held transposed** (keys down the sublanes: maxima and sums are
  reductions down them, the statistics rows of lanes).  **The choice comes
  a block at a time** (``chosen [Hkv, NT, blocks, TILE]`` float32, blocks
  down and the tile's rows across: a key step's eight blocks are one vreg)
  and the kernel expands it: a block's row broadcast down its page's
  sublanes, folded with the causal edge into one additive ``[keys, TILE]``
  bias a KV head that the sixteen heads share.  **Only a step a mask edge
  crosses pays for one**: a tile wholly before ``dense_len`` reads no
  choice and takes the copy of the code without a bias on every step under
  its first row; a tile with a row at or past ``dense_len`` takes the bias
  on every step.  The pass stays DENSE over the keys a tile can see: its
  128 rows share a key block, and the union of what 128 rows choose is
  every visible block (PERF.md, PR 44), so there is nothing to skip.  It
  was 14.5 % of ``sala-longdoc16k-closed``'s busy time as first built (a
  product a head, a mask a key) and is about 5 % so (PERF.md, PR 44).
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


def chunk_twin(q, pages, chosen, tile_table, tile_pos0, tile_len,
               dense_len: int, sm_scale: float):
    """``q [Hkv, G, NT * TILE, D]``, ``chosen [Hkv, NT, blocks, TILE]`` (0 /
    1 a block, blocks down and a tile's rows across), ``tile_table [NT,
    pp]`` (each tile's sequence's pages), a tile's first position and live
    rows -> ``[Hkv, G, NT * TILE, D]``.  Row ``i`` of tile ``n`` attends
    key ``c`` where ``c <= tile_pos0[n] + i``, ``i < tile_len[n]`` and,
    **in a tile with a row at or past** ``dense_len``, block ``c // page``
    is chosen; a tile wholly before it reads no choice."""
    Hkv, G, rows, D = q.shape
    NT = tile_table.shape[0]
    page = pages.shape[2]
    k, v = _gather_kv(pages, tile_table)                   # [NT, Hkv, K, D]
    K = k.shape[2]
    i = jnp.arange(rows // NT)
    ok = jnp.logical_and(
        jnp.arange(K)[None, None] <= (tile_pos0[:, None] + i[None])[..., None],
        (i[None] < tile_len[:, None])[..., None])           # [NT, rows, K]
    picked = jnp.repeat(chosen.transpose(0, 1, 3, 2), page, axis=-1
                        )[..., :K] > 0.5                    # [Hkv,NT,rows,K]
    sparse = tile_pos0 + tile_len - 1 >= dense_len
    ok = jnp.logical_and(ok[None], jnp.logical_or(
        picked, jnp.logical_not(sparse)[None, :, None, None]))
    qt = q.reshape(Hkv, G, NT, rows // NT, D)
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


def step_copies(table_ref, pages_hbm, buf, sems, seq, pages_per_seq: int,
                b, slot):
    """The copies that bring key step ``b`` of sequence ``seq`` (its
    ``_STEP_PAGES`` pages through the table) into buffer ``slot``.  A page
    past the sequence's last holds nothing a row attends (the mask covers
    it); the clamps keep the address legal."""
    for i in range(_STEP_PAGES):
        page_id = jnp.maximum(table_ref[seq * pages_per_seq + jnp.minimum(
            b * _STEP_PAGES + i, pages_per_seq - 1)], 0)
        yield pltpu.make_async_copy(pages_hbm.at[page_id],
                                    buf.at[slot, i], sems.at[slot])


def fold_head(h, q, k, bias, repeat: int, vt_sc, m_sc, l_sc, acc_sc,
              sm_scale: float):
    """Every query head of KV head ``h`` (``q [R, D]``) against a step's
    keys ``k [keys, D]``, scores held TRANSPOSED (keys down, the tile's
    rows x heads across): one product for the scores, one for ``p V``
    (``vt_sc[h]`` holds the step's values transposed), folded into the
    running maximum, sum and accumulator of the head.  ``bias [keys, R /
    repeat]`` is additive and repeated across the lanes, or None."""
    s = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale        # [keys, R]
    if bias is not None:
        s = s + (bias if repeat == 1
                 else jnp.concatenate([bias] * repeat, axis=1))
    m_prev = m_sc[h]                                          # [1, R]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=0, keepdims=True)
    acc_sc[h] = acc_sc[h] * alpha + jnp.dot(
        vt_sc[h], p.astype(vt_sc.dtype),
        preferred_element_type=jnp.float32)                   # [D, R]
    m_sc[h] = m_new


def finish_tile(o_ref, m_sc, l_sc, acc_sc, length, mask_value: float):
    """The tile's output from the folded heads: ``o_ref [Hkv, G, TILE, D]``
    from ``acc_sc [Hkv, D, G * TILE]``; zeros for a row past ``length`` and
    for one that met no key it attends (it holds the first maximum)."""
    Hkv, G, _, D = o_ref.shape
    live = jax.lax.broadcasted_iota(jnp.int32, (D, TILE), 1) < length
    for h in range(Hkv):
        for g in range(G):
            lanes = pl.ds(g * TILE, TILE)
            ok = jnp.logical_and(live, m_sc[h, :, lanes] > mask_value)
            o_ref[h, g] = jnp.where(
                ok, acc_sc[h, :, lanes] / l_sc[h, :, lanes], 0.0
            ).T.astype(o_ref.dtype)


def _chunk_kernel(seq_ref, pos0_ref, len_ref, table_ref,    # scalar prefetch
                  q_ref, chosen_ref, pages_hbm, o_ref,
                  buf, sems, vt_sc, delta_sc, m_sc, l_sc, acc_sc, *,
                  sm_scale: float, dense_len: int, pages_per_seq: int):
    # q_ref, o_ref: [Hkv, G, TILE, D] (one tile); chosen_ref: [Hkv, 1,
    # blocks, TILE] float32; pages_hbm: [P, 2 Hkv, page, D]; buf: [2,
    # _STEP_PAGES, 2 Hkv, page, D]; vt_sc: [Hkv, D, keys]; delta_sc: [keys,
    # TILE] int32; m_sc, l_sc: [Hkv, 1, R]; acc_sc: [Hkv, D, R] with R = G *
    # TILE (query head g's i-th row at lane ``g * TILE + i``).  Scores,
    # weights and the accumulator are held TRANSPOSED (keys down, the
    # tile's rows x heads across), as ``ops/paged_prefill.py`` holds them
    Hkv, G, _, D = q_ref.shape
    page = pages_hbm.shape[2]
    keys = _STEP_PAGES * page
    n = pl.program_id(0)
    seq, pos0, length = seq_ref[n], pos0_ref[n], len_ref[n]
    R = G * TILE
    last = pos0 + length - 1
    sparse = last >= dense_len

    copies = functools.partial(step_copies, table_ref, pages_hbm, buf, sems,
                               seq, pages_per_seq)

    @pl.when(n == 0)
    def _():
        # the key's column minus the row's number in the tile: the causal
        # mask's only vector operand
        delta_sc[...] = (
            jax.lax.broadcasted_iota(jnp.int32, delta_sc.shape, 0)
            - jax.lax.broadcasted_iota(jnp.int32, delta_sc.shape, 1))

    def every_head(slot, biases):
        for h in range(Hkv):
            q = q_ref[h].reshape(R, D)
            k = buf[slot, :, 2 * h].reshape(keys, D)
            fold_head(h, q, k, None if biases is None else biases[h], G,
                      vt_sc, m_sc, l_sc, acc_sc, sm_scale)

    def one_step(b, _):
        slot = b % 2

        @pl.when((b + 1) * keys <= last)
        def _():
            for copy in copies(b + 1, 1 - slot):
                copy.start()

        for copy in copies(b, slot):
            copy.wait()
        for h in range(Hkv):
            vt_sc[h] = buf[slot, :, 2 * h + 1].reshape(keys, D).astype(
                jnp.float32).T.astype(vt_sc.dtype)
        # key ``b * keys + c`` lies at or before the tile's i-th row where
        # ``c - i <= edge``; a step that ends at or before the tile's first
        # row has no key past any row
        edge = pos0 - b * keys
        diagonal = keys - 1 > edge

        def masked():
            # masked entries take _MASK_VALUE (a score added to it IS it)
            # and weigh exp(_MASK_VALUE - m) = 0 once a row has met a key
            # it attends; what a row gathered before that is scaled by the
            # same 0 then, and a row that never meets one is zeroed at the
            # end by its maximum
            causal = jnp.where(
                delta_sc[...] <= jnp.where(diagonal, edge, keys),
                0.0, _MASK_VALUE)                              # [keys, TILE]
            biases = []
            for h in range(Hkv):
                c = chosen_ref[h, 0, pl.ds(
                    pl.multiple_of(b * _STEP_PAGES, _STEP_PAGES),
                    _STEP_PAGES), :]                           # [8, TILE]
                c = jnp.where(sparse, (1.0 - c) * _MASK_VALUE, 0.0)
                biases.append(jnp.minimum(causal, jnp.concatenate([
                    jnp.broadcast_to(c[i:i + 1], (page, TILE))
                    for i in range(_STEP_PAGES)], axis=0)))
            every_head(slot, biases)

        # only a step a mask edge crosses pays for a mask: the choice in a
        # tile with a row at or past ``dense_len``, the diagonal in all
        is_masked = jnp.logical_or(sparse, diagonal)
        pl.when(is_masked)(masked)
        pl.when(jnp.logical_not(is_masked))(
            functools.partial(every_head, slot, None))

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


def chunk_vmem_bytes(Hkv: int, G: int, D: int, page: int,
                     blocks: int) -> int:
    """What a call of the chunk kernel keeps in VMEM, for
    ``vmem_limit_bytes``: a tile of q and of the output and the tile's
    choice, two buffers each (the pipeline's); two key steps as fetched
    and V of one transposed; the accumulator of every head; the causal
    table and a bias a KV head; and seven score tiles (a key step against
    every query head of a KV head) of working room."""
    keys, R = _STEP_PAGES * page, G * TILE
    tile = Hkv * R * D * 2
    return (2 * 2 * tile + 2 * Hkv * blocks * TILE * 4
            + 2 * keys * 2 * Hkv * D * 2 + Hkv * D * keys * 2
            + Hkv * D * R * 4 + (1 + Hkv) * keys * TILE * 4
            + 7 * keys * R * 4)


@functools.partial(jax.jit, static_argnames=(
    "dense_len", "sm_scale", "interpret"))
def _chunk(q, pages, chosen, tile_seq, tile_pos0, tile_len, page_indices, *,
           dense_len: int, sm_scale: float, interpret: bool):
    Hkv, G, rows, D = q.shape
    _, combined, page, _ = pages.shape
    NT = rows // TILE
    pp = page_indices.shape[1]
    keys, R = _STEP_PAGES * page, G * TILE
    blocks = chosen.shape[2]
    assert chosen.shape == (Hkv, NT, blocks, TILE) and \
        blocks == chunk_blocks(pp), (chosen.shape, pp)
    tile = pl.BlockSpec((Hkv, G, TILE, D), lambda n, *_: (0, 0, n, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sm_scale=sm_scale,
                          dense_len=dense_len, pages_per_seq=pp),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[tile,
                      pl.BlockSpec((Hkv, 1, blocks, TILE),
                                   lambda n, *_: (0, n, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile,
            grid=(NT,),
            scratch_shapes=[
                pltpu.VMEM((2, _STEP_PAGES, combined, page, D), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((Hkv, D, keys), pages.dtype),
                pltpu.VMEM((keys, TILE), jnp.int32),
                pltpu.VMEM((Hkv, 1, R), jnp.float32),
                pltpu.VMEM((Hkv, 1, R), jnp.float32),
                pltpu.VMEM((Hkv, D, R), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=chunk_vmem_bytes(Hkv, G, D, page, blocks)),
        interpret=interpret, name=CHUNK_NAME,
    )(tile_seq, tile_pos0, tile_len, page_indices.reshape(-1), q, chosen,
      pages)


def eligible(D: int, page: int, dtype) -> bool:
    """Mosaic's layout: heads of 128 in bf16, slabs of whole tiles."""
    return (D == _LANES and jnp.dtype(dtype) == jnp.bfloat16
            and page % 16 == 0)


def chunk_blocks(pages_per_seq: int) -> int:
    """Blocks the chunk kernel's choice spans: whole key steps."""
    return -(-pages_per_seq // _STEP_PAGES) * _STEP_PAGES


def chunk_attention(q, pages, chosen, tile_seq, tile_pos0, tile_len,
                    page_indices, *, dense_len: int, sm_scale: float,
                    interpret: bool = False) -> jax.Array:
    """Attention of the tiled prompt rows of a tick: ``q [Hkv, G, NT *
    TILE, D]`` over ``pages [P, 2 Hkv, page, D]`` through ``page_indices
    [S, pp]``, tile ``n`` rows ``tile_len[n]`` (0: no row, and zeros come
    back) of sequence ``tile_seq[n]`` from position ``tile_pos0[n]`` on.  A
    row attends the keys at or before it; in a tile with a row at or past
    ``dense_len``, of those only the keys of its chosen blocks: ``chosen
    [Hkv, NT, chunk_blocks(pp), TILE]`` float32, 1 where the row across
    chose the block down (a tile wholly before ``dense_len`` reads none of
    it).  The Pallas kernel on a TPU, else the twin; recorded under
    :data:`KERNEL_PREFILL`."""
    page, D = pages.shape[2], pages.shape[3]
    route = routes.take(
        KERNEL_PREFILL, eligible=eligible(D, page, pages.dtype)
        and q.dtype == pages.dtype, interpret=interpret,
        pallas=f"pallas-sparse-chunk-{TILE}x{_STEP_PAGES * page}")
    if route == routes.XLA:
        return chunk_twin(q, pages, chosen,
                          jnp.take(page_indices, tile_seq, axis=0),
                          tile_pos0, tile_len, dense_len, sm_scale)
    return _chunk(q, pages, chosen.astype(jnp.float32),
                  tile_seq.astype(jnp.int32), tile_pos0.astype(jnp.int32),
                  tile_len.astype(jnp.int32), page_indices.astype(jnp.int32),
                  dense_len=dense_len, sm_scale=sm_scale,
                  interpret=interpret)


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
