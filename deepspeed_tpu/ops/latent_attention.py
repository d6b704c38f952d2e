"""Paged LATENT attention (Pallas TPU kernels): the absorbed form of
multi-head latent attention over a pool of one row a token.

A latent-attention layer (``models/glm4_moe_lite.py``) caches ``[c | k_r]``
for a token: the normalised compressed vector (``rank`` wide) beside the
one rotated key its heads share, zero-padded to whole lane tiles (``W``:
640 for the published 512 + 64; ``paged/latent.py:latent_width``).  With
the up-projections absorbed into the queries, every head's query is as
wide as the row (its padding columns zero too), the row is the key of
EVERY head, and its first ``rank`` columns are the value.  So the pool is
``[P, page, W]`` with no heads in it, a page is one contiguous ``[page,
W]`` block, and nothing has to be picked out of it: both kernels multiply
the rows as they lie.  ``ops/paged_decode.py`` and
``ops/paged_prefill.py`` stay as they are for the pools of per-head keys and
values; these are entry points of their own.

- :func:`decode_attention`, **one query a slot** (a decode block's ticks,
  a fused tick's one-query rows): ``ops/paged_decode.py``'s shape.  The work
  of a call is its flat list of (slot, group of pages) (``work_list`` there:
  only the pages a sequence holds), double-buffered by group; all heads of
  the slot meet a group's rows in one product.  What it costs is the rows it
  reads (about 38 FLOP a byte against the v5e's 240), so its roofline is
  bytes.
- :func:`fused_tick_attention`, **the prompt chunks of a fused tick**.  The
  absorbed form over the pages: 2 x (W + rank) FLOP a query-key pair a head
  (2,176 at 576 + 512 unpadded) and no expansion, no workspace.  The work
  of a call is a flat list of (sequence, tile of ``rt`` query rows); a
  tile's ``H x rt`` rows (every head of its tokens) meet a block of ``kb``
  pages ONCE, block after block up to the tile's last row's own key, the
  pages double-buffered.  A block is read again by each tile of the chunk,
  which costs nothing here: a block of 512 rows is 0.6 MB and meets 2,560
  query rows, 4,800 FLOP a byte.  **Operands as the module holds them**: q
  comes HEADS FIRST (``[H, Tp, W]``: the absorbing product emits it so,
  ``models/glm4_moe_lite.py``) and stays in HBM, a row tile of it fetched
  under the tile before; the answer is ``[H, Tp, rank]``, which the
  up-projection multiplies as it lies; nothing as large as ``T x H x W`` is
  turned or padded on either side of the call.  **A block wholly before the
  tile's first row** (twelve of thirteen at the serving traffic) is one
  product of all 2,560 rows, no mask: 16.4 us on the v5e where its products
  take 15.3 at the peak, the vector work under them.  **A block a mask edge
  crosses** takes the diagonal and the sequence's end as ONE additive
  ``[rt, keys]`` bias built from the positions (no table), and multiplies
  only the block's first half where the tile's last row lies there.  The
  select that zeroes rows past a sequence's end runs once, on the
  sequence's last block, where the block lies.  **A tile that answers for
  a sublane tile of rows or fewer** takes a narrow copy of the same code
  (320 MXU rows for 2,560): the engine packs a tick in slot order, so a
  1,024-row chunk behind k decoding slots lies in NINE flat tiles, the
  ninth with k live rows, and that tile cost a whole one's 0.30 ms a call
  at 8k context and costs 0.05 so (PERF.md, PR 45).
  What the v5e said of the other forms (PERF.md, PR 45): scores held
  TRANSPOSED as ``ops/paged_prefill.py`` holds them take 17.8-18.6 us a
  step at best and 38 in one product (the value columns must be turned
  every step, and a score here costs 1,152 MACs where that kernel's costs
  256, so there was no softmax left to hide); and the copies of the step
  have to stay SMALL: five copies of the whole product (a cut at every
  lane tile) ran EVERY step at 35 us and three cost a call 0.1 ms more
  than they saved, as a program too long for the core's instruction memory
  would.  Hence one whole copy, and two masked ones of half the heads a
  product.

Arithmetic as the other kernels': q and rows as stored (bf16), float32
scores, statistics and accumulator, the weights of the p.V product rounded
to the pool's dtype.  Value rows past a sequence's end are zeroed (a stale
NaN would survive a zero weight, and as a key would survive the bias).

Mosaic needs a bf16 pool, ``rank`` and ``W`` whole lane tiles and pages of
whole sublane tiles (:func:`tiling` answers None otherwise and the caller takes
its XLA twin, ``inference/paged/latent.py:ref_latent_paged_attention``).  Under
``interpret=True`` the kernels run through the Pallas interpreter
(``tests/unit/ops/test_latent_attention.py``); ``tests/unit/ops/
test_tpu_compile.py`` hands them to the chip's compiler at the published
widths.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import paged_decode, routes

# the route keys: which implementation a decode program's and a fused
# tick's latent attention took
KERNEL = "latent_attention"
KERNEL_DECODE = KERNEL + "_decode"
KERNEL_PREFILL = KERNEL + "_prefill"
# the operations' names in the compiled program and in a device trace
NAME_DECODE = "latent_decode_attention"
NAME_PREFILL = "latent_chunk_attention"
# what the XLA twin is called in ``serving_stages()["kernels"]``
XLA_ROUTE = "xla-dense-latent"

_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_LANES = 128
# rows a DMA group of the decode kernel holds (two are in VMEM at a time);
# tokens a row tile and rows a key block of the chunk kernel hold
_GROUP_TOKENS = 1024
_TILE_TOKENS = 128
_BLOCK_TOKENS = 512
# rows of the narrow copy of a row tile's code: a sublane tile of bf16
_FEW_ROWS = 16
_VMEM_CEILING = 100 * 1024 * 1024


def tiling(page_size: int, pages_per_seq: int, width: int, rank: int,
           dtype) -> Optional[Tuple[int, int, int]]:
    """``(pages a decode group, tokens a row tile, pages a key block)`` from
    the shapes alone, or None where the kernels' layout does not hold."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return None
    if rank % _LANES or width % _LANES or rank > width or page_size % 16:
        return None
    if _BLOCK_TOKENS % page_size or _GROUP_TOKENS % page_size:
        return None
    return (min(_GROUP_TOKENS // page_size, pages_per_seq), _TILE_TOKENS,
            _BLOCK_TOKENS // page_size)


def chunk_vmem_bytes(T: int, heads: int, width: int, rank: int, rt: int,
                     keys: int) -> int:
    """What a chunk call keeps in VMEM: the output whole, two row tiles of
    q, two key blocks, a tile's accumulator and its two statistics (a
    column each, which lies in whole lane tiles), and three score tiles of
    working room.  At the published widths 50 MiB for a tick of 1,024 + 8
    rows where the chip's compiler asks 45.75, and 70 MiB for 2,048 + 8
    where it asks 65.9 (compile rehearsal, PR 45)."""
    R = heads * rt
    return (T * heads * rank * 2 + 2 * R * width * 2 + 2 * keys * width * 2
            + R * rank * 4 + 2 * R * _LANES * 4 + 3 * R * keys * 4)


def route_name(kind: str, tiles: Optional[Tuple[int, int, int]],
               heads: int, page_size: int) -> str:
    """``pallas-latent-decode-<rows a group>`` /
    ``pallas-latent-chunk-<query rows a row tile>x<rows a key block>``: a
    row tile is every head of ``rt`` tokens, and meets a key block in ONE
    product where no mask edge crosses the block (in two, half the heads
    each, over the block's first half or the whole of it where one
    does)."""
    g, rt, kb = tiles if tiles else (0, 0, 0)
    if kind == "decode":
        return f"pallas-latent-decode-{g * page_size}"
    return f"pallas-latent-chunk-{heads * rt}x{kb * page_size}"


# ---------------------------------------------------------------------------
# one query a slot
# ---------------------------------------------------------------------------

def _decode_kernel(slot_ref, page0_ref, count_ref, flags_ref, nwork_ref,
                   kv_lens_ref, table_ref,               # scalar prefetch
                   q_ref, pages_hbm, o_ref,
                   buf, sems, m_sc, l_sc, acc_sc, *,
                   sm_scale: float, pages_per_seq: int):
    # q_ref: [S, H, W]; o_ref: [S, H, rank]; pages_hbm: [P, page, W];
    # buf: [2, group * page, W]; m_sc, l_sc: [H, 1]; acc_sc: [H, rank]
    page = pages_hbm.shape[1]
    keys = buf.shape[1]
    group = keys // page
    H, rank = acc_sc.shape
    n_work = nwork_ref[0]

    def copies(w, slot):
        s, page0 = slot_ref[w], page0_ref[w]
        for i in range(group):
            # a page of the table's padding (-1) is in no group; the clamp
            # only keeps an address legal
            page_id = jnp.maximum(
                table_ref[s * pages_per_seq
                          + jnp.minimum(page0 + i, pages_per_seq - 1)], 0)
            yield i, pltpu.make_async_copy(
                pages_hbm.at[page_id], buf.at[slot, pl.ds(i * page, page)],
                sems.at[slot])

    def start(w, slot):
        for i, copy in copies(w, slot):
            pl.when(i < count_ref[w])(copy.start)

    def wait(w, slot):
        for i, copy in copies(w, slot):
            pl.when(i < count_ref[w])(copy.wait)

    # idle slots' rows: nothing below writes them
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_work > 0)
    def _():
        start(0, 0)

    def multiply(w, slot):
        s, flags = slot_ref[w], flags_ref[w]
        left = kv_lens_ref[s] - page0_ref[w] * page   # keys still to attend

        @pl.when(flags % 2 == 1)
        def _():
            m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc_sc[...] = jnp.zeros_like(acc_sc)

        q = q_ref[s]                                          # [H, W]
        rows = buf[slot]                                      # [keys, W]
        held = jax.lax.broadcasted_iota(jnp.int32, (keys, rank), 0) < left
        c = jnp.where(held, rows[:, :rank], 0).astype(q.dtype)
        scores = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        scores = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (H, keys), 1) < left,
            scores, _MASK_VALUE)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(q.dtype), c, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

        @pl.when(flags // 2 == 1)
        def _():
            o_ref[s] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)

    def one_group(w, _):
        slot = w % 2

        @pl.when(w + 1 < n_work)
        def _():
            start(w + 1, 1 - slot)

        wait(w, slot)
        multiply(w, slot)

    jax.lax.fori_loop(0, n_work, one_group, None)


@functools.partial(jax.jit, static_argnames=(
    "rank", "sm_scale", "group", "interpret"))
def _decode(q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
            page_indices: jax.Array, live: jax.Array, *, rank: int,
            sm_scale: float, group: int, interpret: bool) -> jax.Array:
    """The decode kernel at ``group`` pages a DMA group: ``q [S, H, W]`` to
    ``[S, H, rank]``.  The heads are padded to whole sublane tiles here."""
    S, H, W = q.shape
    _, page, _ = pages.shape
    pages_per_seq = page_indices.shape[1]
    Hp = -(-H // 16) * 16
    work, n_work = paged_decode.work_list(
        kv_lens, live, page_size=page, pages_per_seq=pages_per_seq,
        group=group, sliding_window=None)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale,
                          pages_per_seq=pages_per_seq),
        out_shape=jax.ShapeDtypeStruct((S, Hp, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            grid=(1,),
            scratch_shapes=[
                pltpu.VMEM((2, group * page, W), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((Hp, 1), jnp.float32),
                pltpu.VMEM((Hp, 1), jnp.float32),
                pltpu.VMEM((Hp, rank), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=NAME_DECODE,
    )(*work, n_work, kv_lens.astype(jnp.int32),
      page_indices.astype(jnp.int32).reshape(-1),
      jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0))), pages)[:, :H]


def decode_attention(q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
                     page_indices: jax.Array, live: Optional[jax.Array], *,
                     rank: int, sm_scale: float,
                     fallback: Callable[[], jax.Array],
                     interpret: bool = False) -> jax.Array:
    """Latent attention of a decode tick: ``q [S, H, W]``, row ``s`` the
    absorbed queries of slot ``s`` at position ``kv_lens[s] - 1``, over the
    pool ``pages [P, page, W]`` through ``page_indices [S, pp]`` (``-1``
    past a sequence's end); ``[S, H, rank]``.  Slots where ``live`` is false
    come back zero and touch no page.  The Pallas kernel on a TPU where
    :func:`tiling` finds its layout, else ``fallback()``; which is recorded
    under :data:`KERNEL_DECODE`."""
    S, H, W = q.shape
    _, page, _ = pages.shape
    tiles = None
    if q.dtype == pages.dtype:
        tiles = tiling(page, page_indices.shape[1], W, rank, pages.dtype)
    route = routes.take(
        KERNEL_DECODE, eligible=tiles is not None, interpret=interpret,
        every=True, xla=XLA_ROUTE,
        pallas=route_name("decode", tiles, H, page))
    if route == XLA_ROUTE:
        return fallback()
    if live is None:
        live = jnp.ones((S,), bool)
    return _decode(q, pages, kv_lens, page_indices, live, rank=rank,
                   sm_scale=sm_scale, group=tiles[0], interpret=interpret)


# ---------------------------------------------------------------------------
# the prompt chunks of a fused tick
# ---------------------------------------------------------------------------

def chunk_work_list(kv_lens: jax.Array, cu_q_lens: jax.Array,
                    num_seqs: jax.Array, *, rt: int, width: int):
    """``(seq, tile, flags) [width], n_work [1]``: the row tiles a call
    works through, in sequence order: tile ``r`` answers for flat rows ``r *
    rt`` to ``r * rt + rt - 1``, and a sequence has the tiles its rows lie
    in.  A sequence past ``num_seqs`` or with fewer than two query rows has
    none (its row is a decode tick's work).  ``width`` bounds the list (the
    tiles of the tick and one more a sequence); entries past ``n_work`` are
    never read."""
    S = kv_lens.shape[0]
    t0, t1 = cu_q_lens[:-1], cu_q_lens[1:]
    mine = jnp.logical_and(jnp.arange(S) < num_seqs[0], t1 - t0 >= 2)
    r0 = t0 // rt
    seq, g, flags, n_work = paged_decode.flat_list(
        jnp.where(mine, (jnp.maximum(t1, 1) - 1) // rt - r0 + 1, 0), width)
    return (seq, r0[seq] + g, flags), n_work


def _chunk_kernel(seq_ref, tile_ref, nwork_ref, kv_lens_ref, cu_ref,
                  table_ref,                            # scalar prefetch
                  q_hbm, pages_hbm, o_ref,
                  qbuf, qsems, buf, sems, m_sc, l_sc, acc_sc, *,
                  sm_scale: float, pages_per_seq: int):
    # q_hbm: [H, T, W]; o_ref: [H, T, rank]; pages_hbm: [P, page, W];
    # qbuf: [2, H, rt, W]; buf: [2, keys, W]; m_sc, l_sc: [R, 1]; acc_sc:
    # [R, rank]: row ``h * rt + i`` is head h of the tile's i-th token
    page = pages_hbm.shape[1]
    _, keys, W = buf.shape
    kb = keys // page
    _, H, rt, _ = qbuf.shape
    T = q_hbm.shape[1]
    R, rank = acc_sc.shape
    cols = max(1, keys // _LANES)
    lanes = keys // cols
    n_work = nwork_ref[0]

    def first_row(w):
        """Row tile ``r`` answers for flat rows ``r * rt`` to ``r * rt + rt
        - 1`` and holds ``rt`` rows from here: the last tile of a tick that
        is no whole number of tiles starts early, and the rows it shares
        with the tile before are masked out of its write."""
        return pl.multiple_of(jnp.minimum(tile_ref[w] * rt, T - rt), 16)

    def q_copy(w):
        return pltpu.make_async_copy(
            q_hbm.at[:, pl.ds(first_row(w), rt), :], qbuf.at[w % 2],
            qsems.at[w % 2])

    def copies(s, block, slot):
        held = (kv_lens_ref[s] + page - 1) // page
        for i in range(kb):
            # a page past the sequence's end is not fetched; the clamp
            # only keeps an address legal
            k = block * kb + i
            page_id = jnp.maximum(
                table_ref[s * pages_per_seq
                          + jnp.minimum(k, pages_per_seq - 1)], 0)
            yield k < held, pltpu.make_async_copy(
                pages_hbm.at[page_id], buf.at[slot, pl.ds(i * page, page)],
                sems.at[slot])

    def start(s, block, slot):
        for fetched, copy in copies(s, block, slot):
            pl.when(fetched)(copy.start)

    def wait(s, block, slot):
        for fetched, copy in copies(s, block, slot):
            pl.when(fetched)(copy.wait)

    @pl.when(n_work > 0)
    def _():
        q_copy(0).start()

    # rows no sequence of the list owns (one-query rows, padding)
    o_ref[...] = jnp.zeros_like(o_ref)

    def one_tile(w, _):
        s, r = seq_ref[w], tile_ref[w]
        t0, t1, kv_len = cu_ref[s], cu_ref[s + 1], kv_lens_ref[s]
        # position of the sequence's row at flat row t: ``t + shift``
        shift = kv_len - t1
        row0 = first_row(w)
        # the flat rows of THIS sequence the tile answers for, their
        # positions, and the sublane tile of rows the first of them lies in
        live0, live1 = jnp.maximum(r * rt, t0), jnp.minimum(r * rt + rt, t1)
        lo, hi = live0 + shift, live1 - 1 + shift
        n_blocks = hi // keys + 1
        few0 = live0 // _FEW_ROWS * _FEW_ROWS
        few = live1 <= few0 + _FEW_ROWS

        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        start(s, 0, 0)

        # the next tile's queries arrive under this tile's products
        @pl.when(w + 1 < n_work)
        def _():
            q_copy(w + 1).start()

        q_copy(w).wait()

        def attend(first, n_rows: int):
            """The tile's ``n_rows`` query rows from flat row ``first`` on
            (every head of them: ``Rn`` rows of the statistics and the
            accumulator) against the sequence's blocks."""
            Rn = H * n_rows
            q_tile = qbuf.at[w % 2, :, pl.ds(
                pl.multiple_of(first - row0, _FEW_ROWS), n_rows), :]

            def fold(rows, bias, hg: int):
                """One step of the running softmax: ``hg`` heads a product
                against ``rows [n, W]`` of keys, under ``bias [n_rows, n]``
                where a mask edge crosses them."""
                n = rows.shape[0]

                def product(q, at):
                    sc = jax.lax.dot_general(
                        q, rows, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
                    if bias is not None:
                        # every head's slab of the scores takes the one bias
                        sc = (sc.reshape(hg, n_rows, n) + bias[None]).reshape(
                            hg * n_rows, n)
                    m_prev = m_sc[at, :]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(sc, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(sc - m_new)
                    l_sc[at, :] = alpha * l_sc[at, :] + jnp.sum(
                        p, axis=1, keepdims=True)
                    acc_sc[at, :] = alpha * acc_sc[at, :] + jnp.dot(
                        p.astype(rows.dtype), rows[:, :rank],
                        preferred_element_type=jnp.float32)
                    m_sc[at, :] = m_new

                if hg == H:
                    product(q_tile[...].reshape(Rn, W), pl.ds(0, Rn))
                else:
                    jax.lax.fori_loop(0, H // hg, lambda g, _: product(
                        q_tile[pl.ds(g * hg, hg)].reshape(hg * n_rows, W),
                        pl.ds(pl.multiple_of(g * hg * n_rows, hg * n_rows),
                              hg * n_rows)), None)

            def one_block(b, _):
                slot = b % 2

                @pl.when(b + 1 < n_blocks)
                def _():
                    start(s, b + 1, 1 - slot)

                wait(s, b, slot)
                left = kv_len - b * keys

                # a sequence's last block: the rows past its end are zeroed
                # where they lie (a stale NaN would survive a zero weight,
                # and a NaN score the bias)
                @pl.when(left < keys)
                def _():
                    held = (jax.lax.broadcasted_iota(jnp.int32, (keys, W), 0)
                            < left)
                    buf[slot] = jnp.where(held, buf[slot], 0).astype(
                        buf.dtype)

                # a block wholly before the tile's first row: every head in
                # one product, no mask
                crossed = b * keys + keys - 1 > lo
                pl.when(jnp.logical_not(crossed))(
                    lambda: fold(buf[slot], None, H))

                # a block a mask edge crosses multiplies the keys up to the
                # lane tile that holds the tile's last row: the block's
                # first half or the whole of it, half the heads a product
                # (the copies of the step have to stay small: the module
                # docstring; a tile of few rows is small as it is).  Key
                # ``c`` is attended by the tile's i-th row where ``c - i <=
                # first + shift - b * keys``: the diagonal and the
                # sequence's end in ONE additive bias
                needed = (hi - b * keys) // lanes + 1
                half = cols // 2 if n_rows == rt else 0
                for n, taken in (((half, needed <= half),
                                  (cols, needed > half))
                                 if half else ((cols, True),)):
                    def masked(n=n):
                        delta = (
                            jax.lax.broadcasted_iota(
                                jnp.int32, (n_rows, n * lanes), 1)
                            - jax.lax.broadcasted_iota(
                                jnp.int32, (n_rows, n * lanes), 0))
                        fold(buf[slot, :n * lanes, :], jnp.where(
                            delta <= first + shift - b * keys, 0.0,
                            _MASK_VALUE),
                            H // 2 if half and H % 2 == 0 else H)

                    pl.when(jnp.logical_and(crossed, taken))(masked)

            jax.lax.fori_loop(0, n_blocks, one_block, None)

            token = first + jax.lax.broadcasted_iota(
                jnp.int32, (Rn, rank), 0) % n_rows
            mine = jnp.logical_and(token >= live0, token < live1)
            rows_out = pl.ds(pl.multiple_of(first, _FEW_ROWS), n_rows)
            o_ref[:, rows_out, :] = jnp.where(
                mine, acc_sc[:Rn] / l_sc[:Rn],
                o_ref[:, rows_out, :].reshape(Rn, rank).astype(jnp.float32)
            ).astype(o_ref.dtype).reshape(H, n_rows, rank)

        # a tile that answers for a sublane tile of rows or fewer (the rows
        # of a chunk packed behind one-query rows that spill into one tile
        # more; a short tail) multiplies those alone: a sixth of the time
        pl.when(few)(lambda: attend(few0, _FEW_ROWS))
        pl.when(jnp.logical_not(few))(lambda: attend(row0, rt))

    jax.lax.fori_loop(0, n_work, one_tile, None)


@functools.partial(jax.jit, static_argnames=(
    "rank", "sm_scale", "rt", "kb", "interpret"))
def _chunk(q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
           page_indices: jax.Array, cu_q_lens: jax.Array,
           num_seqs: jax.Array, *, rank: int, sm_scale: float, rt: int,
           kb: int, interpret: bool) -> jax.Array:
    """The chunk kernel at static ``(rt, kb)``, heads first: ``q [H, T, W]``
    to ``[H, T, rank]`` with the rows of every sequence of two queries or
    more, zero elsewhere.  ``T`` is a whole number of sublane tiles and at
    least ``rt``."""
    H, T, W = q.shape
    _, page, _ = pages.shape
    assert T % 16 == 0 and T >= rt, (T, rt)
    S, pages_per_seq = page_indices.shape
    R, keys = H * rt, kb * page
    (seq, tile, _), n_work = chunk_work_list(
        kv_lens, cu_q_lens, num_seqs, rt=rt, width=-(-T // rt) + S)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sm_scale=sm_scale,
                          pages_per_seq=pages_per_seq),
        out_shape=jax.ShapeDtypeStruct((H, T, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[in_hbm, in_hbm],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            grid=(1,),
            scratch_shapes=[
                pltpu.VMEM((2, H, rt, W), q.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((2, keys, W), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, rank), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_CEILING),
        interpret=interpret, name=NAME_PREFILL,
    )(seq, tile, n_work, kv_lens.astype(jnp.int32),
      cu_q_lens.astype(jnp.int32),
      page_indices.astype(jnp.int32).reshape(-1), q, pages)


def tick_rows(T: int) -> int:
    """Rows of the heads-first q a fused tick of ``T`` tokens hands
    :func:`fused_tick_attention`: ``T`` in whole sublane tiles, a row tile
    at least."""
    return max(-(-T // 16) * 16, _TILE_TOKENS)


def fused_tick_attention(
        q: jax.Array, q_one: jax.Array, pages: jax.Array, kv_lens: jax.Array,
        page_indices: jax.Array, cu_q_lens: jax.Array, num_seqs: jax.Array,
        *, rank: int, sm_scale: float,
        fallback: Callable[[jax.Array], jax.Array],
        interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Latent attention of a fused tick, HEADS FIRST as the module holds its
    queries: ``q [H, Tp, W]`` (``Tp = tick_rows(T)``, the rows past ``T``
    anything finite), the rows of packed sequence ``j`` at ``cu_q_lens[j]:
    cu_q_lens[j + 1]``, the last of them at position ``kv_lens[j] - 1``,
    over the pool ``pages [P, page, W]`` through ``page_indices [S, pp]``;
    and ``q_one [S, H, W]``, the FIRST row of every packed sequence again,
    tokens first as the decode kernel takes it (a gather along the tokens
    of ``q`` would have XLA turn the whole of it: the module makes these
    eight rows from what it holds tokens first).
    Answers ``(out [H, Tp, rank], rows [S, H, rank], at [S])``: ``out`` as
    the up-projection ``einsum("htc,chv->thv")`` reads it, the rows of
    every sequence of two query rows or more and zero elsewhere; ``rows``
    the answers of the sequences of ONE query row, which belong at token
    ``at[j]`` (``Tp``, past every token, for a sequence that has none).

    Separating by ``cu_q_lens`` as ``ops/paged_prefill.py`` does: sequences
    with one query row take the decode kernel (gathered to one row a
    sequence), sequences with more the chunk kernel.  Both on a TPU where
    :func:`tiling` finds their layout and the chunk fits VMEM, else
    ``fallback(q tokens first [Tp, H, W]) -> [Tp, H, rank]`` with every row
    in it; which is recorded under :data:`KERNEL_PREFILL`."""
    H, Tp, W = q.shape
    _, page, _ = pages.shape
    S = page_indices.shape[0]
    tiles = None
    if q.dtype == pages.dtype:
        tiles = tiling(page, page_indices.shape[1], W, rank, pages.dtype)
    if tiles is not None and (Tp % 16 or Tp < tiles[1] or chunk_vmem_bytes(
            Tp, H, W, rank, tiles[1], tiles[2] * page) > _VMEM_CEILING):
        tiles = None
    route = routes.take(
        KERNEL_PREFILL, eligible=tiles is not None, interpret=interpret,
        every=True, xla=XLA_ROUTE, pallas=route_name("chunk", tiles, H, page))
    if route == XLA_ROUTE:
        return (fallback(q.transpose(1, 0, 2)).transpose(1, 0, 2),
                jnp.zeros((S, H, rank), q.dtype), jnp.full((S,), Tp))
    group, rt, kb = tiles
    out = _chunk(q, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
                 rank=rank, sm_scale=sm_scale, rt=rt, kb=kb,
                 interpret=interpret)
    # the one-query sequences: row ``cu_q_lens[j]`` is sequence j's query
    first = cu_q_lens[:-1]
    one = jnp.logical_and(jnp.arange(S) < num_seqs[0],
                          cu_q_lens[1:] - first == 1)
    rows = _decode(q_one, pages, kv_lens, page_indices, one, rank=rank,
                   sm_scale=sm_scale, group=group, interpret=interpret)
    return out, rows, jnp.where(one, first, Tp)
