"""Paged attention for the prompt chunks of a fused tick (Pallas TPU kernel).

A fused tick (``inference/v2/programs.py:build_fused_step``) packs one
query row for every decoding sequence and a CHUNK of hundreds of rows for
each prefilling one.  The kernel it used
(``jax.experimental.pallas.ops.tpu.ragged_paged_attention``) is built for
many short sequences: a query block is 32 rows, so a 512-row chunk fetches
and unpacks each block of its keys 16 times, every time picking a KV
head's rows out of the combined-head page by sublane-strided loads
(PERF.md, PR 31 and PR 36).  This one is built for a tick that is one
long chunk:

- **a block of keys meets the whole chunk once.**  The work of a call is
  a flat list of (sequence, block of ``kb`` pages) built from the ragged
  metadata by a dozen dense operations (:func:`work_list`); a block is
  fetched once (one contiguous DMA a page, every KV head in it,
  double-buffered across blocks and across sequences), **laid out once**
  (each KV head's K and V rows picked out of the pages into ``[Hkv, keys,
  D]`` buffers, V rows past the sequence's end zeroed there) and then
  multiplied with every query row of the sequence, in row tiles of
  ``rt`` tokens;
- **all query heads of a KV head ride one product**: q comes heads first
  (``[H, T, D]``, as the model holds it), so the ``G = H / Hkv`` query
  heads of a KV head are ``G`` slabs of the same ``rt`` tokens and a row
  tile is ``rt * G`` MXU rows with no transpose on either side of the call;
- **scores held transposed** (keys down, query rows across): a row's
  maximum and sum are reductions down the sublanes, which the vector unit
  does, where across the lanes the v5e rotates seven times a vreg (a
  sixth of the first build's time, PERF.md PR 36);
- **causal and window skipping by block**: only the blocks from the one
  that holds the first key the chunk's first row attends to the one that
  holds its last row's own key are in the list, and inside a block a row
  tile whose rows all lie before it, or whose windows all start after it,
  is not multiplied; only a tile that a mask edge crosses pays for the
  mask (copies of the tile's code without it), and it multiplies only the
  lane tiles of keys up to its last row's own;
- **only sequences with two query rows or more**: the one-query rows of a
  tick are a decode tick's work and go to ``ops/paged_decode.py``; the
  two write disjoint rows (:func:`fused_tick_attention`).  One kernel for
  both was measured at two to four times the pair (PERF.md, PR 36).

Arithmetic as upstream's: q, K, V as stored (bf16), float32 scores,
softmax statistics and accumulator, the weights of the p·V product
rounded to the pool's dtype.  The pool layout ``[P, page, 2 * Hkv, D]``
and the ragged metadata are unchanged.

VMEM, reckoned in :func:`vmem_bytes` from the shapes and handed to the
compiler as ``vmem_limit_bytes`` (the default 16 MiB does not hold a
chunk): q and the output whole (``T * H * D`` bf16 each), the float32
accumulator of every row tile (twice that and the last tile's overhang;
the two statistics are a row of lanes a tile and weigh nothing), two
buffers of a key block as fetched and one as laid out, one ``[keys, rt *
G]`` table of column minus row for the masks, and seven score tiles of
working room.  At ``mistral-7b-serve``'s shapes (T 544, five row tiles of
128 tokens; 32 heads): 4.25 + 4.25 + 10 + 3 x 2 + 8 x 1 = 32.5 MiB of the
v5e's 128.

Mosaic needs ``D == 128``, a bf16 pool, whole sublane tiles of combined
heads a token (at FOUR KV heads a PAIR of tokens a tile,
``paged_decode.tokens_a_tile``: a block is fetched and buffered as ``[keys /
2, 16, D]`` and a head's rows are picked out of it at a stride of four) and
row tiles of whole sublane tiles (:func:`tiling` answers None otherwise, and
the caller keeps the path it had).  Under
``interpret=True`` the same kernel runs through the Pallas interpreter at
any head count (``tests/unit/ops/test_paged_prefill.py``);
``tests/unit/ops/test_tpu_compile.py`` hands it to the chip's compiler at
the four serving configurations' widths.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import paged_decode, routes

# the route key of a fused tick's attention: which kernel each call took
# (``paged_attention`` beside it names the family, ``paged_attention_decode``
# the decode programs' kernel)
KERNEL = "paged_attention_prefill"
# the operation's name in the compiled program and in a device trace
NAME = "paged_chunk_attention"

_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_LANES = 128
# Tokens a row tile holds, least and most: ``_TILE_ROWS / G`` between them,
# so ``rt * G`` MXU rows of one KV head a product (128 tokens x 4 query
# heads at 8 KV heads of 32; 256 x 1 at 16 of 16: fewer tokens waste less of
# a diagonal tile, more rows amortise a block of keys better).  KV heads a
# step of the loop over them, and tokens a key block holds (every KV head
# of them is fetched; two blocks are in VMEM at a time).  Settled on the
# v5e at mistral-7b-serve's and olmoe-1b-7b-serve's fused ticks (PERF.md,
# PR 36: 64 and 256 tokens a tile at 8 KV heads a tenth and a sixth
# slower than 128, 512 tokens at 16 a tenth slower than 256; key blocks of
# 256 and 1,024 tokens 7-10 % slower than 512)
_TILE_ROWS = 512
_TILE_TOKENS = (128, 256)
_HEADS_A_STEP = 4
_BLOCK_TOKENS = 512
# and the VMEM a call may ask for
_VMEM_CEILING = 100 * 1024 * 1024


def tiling(heads: int, kv_heads: int, page_size: int, head_dim: int,
           dtype) -> Optional[Tuple[int, int]]:
    """``(tokens a row tile, pages a key block)`` from the shapes alone, or
    None where the kernel's layout does not hold: head_dim 128, a bf16 pool
    (K and V of a head are the halves of one 32-bit word), whole sublane
    tiles of combined heads a token or, at four KV heads, a pair of tokens
    (``paged_decode.tokens_a_tile``; two KV heads keep the path they had),
    whole query-head groups, and key blocks of whole lane tiles."""
    dtype = jnp.dtype(dtype)
    if head_dim != _LANES or dtype != jnp.bfloat16:
        return None
    if kv_heads < 1 or heads % kv_heads:
        return None
    pair = paged_decode.tokens_a_tile(kv_heads)
    if pair is None or page_size % pair:
        return None
    least, most = _TILE_TOKENS
    rt = min(most, max(least, _TILE_ROWS // (heads // kv_heads)))
    kb = max(1, _BLOCK_TOKENS // page_size)
    if (kb * page_size) % _LANES:
        return None
    return rt, kb


def vmem_bytes(T: int, heads: int, kv_heads: int, page_size: int,
               head_dim: int, tiles: Tuple[int, int]) -> int:
    """What a call at ``tiles`` keeps in VMEM (the module docstring's
    reckoning), for ``vmem_limit_bytes``."""
    rt, kb = tiles
    rows, keys = rt * (heads // kv_heads), kb * page_size
    q_o = 2 * T * heads * head_dim * 2
    acc = -(-T // rt) * rt * heads * head_dim * 4
    block = keys * 2 * kv_heads * head_dim * 2
    return q_o + acc + 3 * block + 8 * rows * keys * 4


def route_name(tiles: Optional[Tuple[int, int]], heads: int, kv_heads: int,
               page_size: int) -> str:
    """What :data:`KERNEL` reads where the kernel runs at ``tiles``:
    ``pallas-chunk-<MXU rows a product>x<key tokens a block>``."""
    rt, kb = tiles if tiles else (0, 0)
    return f"pallas-chunk-{rt * (heads // max(kv_heads, 1))}x{kb * page_size}"


def work_list(kv_lens: jax.Array, cu_q_lens: jax.Array, num_seqs: jax.Array,
              *, page_size: int, pages_per_seq: int, kb: int,
              sliding_window: Optional[int]):
    """``(seq, block, flags) [W], n_work [1]``: the key blocks a call
    works through, in sequence order.  Block ``b`` of a sequence is pages
    ``b * kb`` to ``(b + 1) * kb - 1`` of its page-table row; ``flags`` bit
    0 marks a sequence's first block, bit 1 its last.  A sequence past
    ``num_seqs`` or with fewer than two query rows has none; another
    has the blocks from the one that holds the first key its first row
    attends (key 0, or ``first row's position - window + 1``) to the one
    that holds its last row's own key.  ``W = seqs * ceil(pages_per_seq /
    kb)`` bounds the list; entries past ``n_work`` are never read."""
    S = kv_lens.shape[0]
    keys = kb * page_size
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    mine = jnp.logical_and(jnp.arange(S) < num_seqs[0], q_lens >= 2)
    first_pos = kv_lens - q_lens
    first_key = (jnp.zeros_like(kv_lens) if sliding_window is None
                 else jnp.maximum(first_pos - sliding_window + 1, 0))
    b0 = first_key // keys
    b1 = jnp.minimum(jnp.maximum(kv_lens - 1, 0) // keys,
                     -(-pages_per_seq // kb) - 1)
    seq, g, flags, n_work = paged_decode.flat_list(
        jnp.where(mine, jnp.maximum(b1 - b0 + 1, 0), 0),
        S * -(-pages_per_seq // kb))
    return (seq, b0[seq] + g, flags), n_work


def _kernel(seq_ref, block_ref, flags_ref, nwork_ref, kv_lens_ref, cu_ref,
            table_ref,                                  # scalar prefetch
            q_ref, pages_hbm, o_ref,
            buf, sems, k_sc, vt_sc, delta_sc, m_sc, l_sc, acc_sc, *,
            sm_scale: float, sliding_window: Optional[int],
            pages_per_seq: int, page: int):
    # q_ref, o_ref: [Hkv, G, T, D]; pages_hbm: [P, page / pair, pair * 2Hkv,
    # D] and buf: [2, keys / pair, pair * 2Hkv, D] (``pair`` tokens a sublane
    # tile, ``paged_decode.tokens_a_tile``); k_sc: [Hkv, keys, D];
    # vt_sc: [Hkv, D, keys];
    # delta_sc: [keys, R] int32; m_sc, l_sc: [Hkv, tiles, 1, R];
    # acc_sc: [Hkv, tiles, D, R].  Scores, weights and the accumulator are
    # held TRANSPOSED (keys down, query rows across): a row's maximum and
    # sum are then reductions down the sublanes, which the vector unit
    # does, and not across the lanes, which the v5e does by seven rotations
    # a vreg (PERF.md, PR 36)
    Hkv, G, T, D = q_ref.shape
    combined = 2 * Hkv
    pair = buf.shape[2] // combined
    keys = buf.shape[1] * pair
    kb = keys // page
    R = delta_sc.shape[1]
    rt = R // G
    cols = max(1, keys // _LANES)
    lanes = keys // cols
    hs = math.gcd(Hkv, _HEADS_A_STEP)
    n_work = nwork_ref[0]

    def copies(w, slot):
        s, page0 = seq_ref[w], block_ref[w] * kb
        held = (kv_lens_ref[s] + page - 1) // page
        for i in range(kb):
            # a page past the sequence's end is not fetched; the clamp
            # only keeps an address legal
            page_id = jnp.maximum(
                table_ref[s * pages_per_seq
                          + jnp.minimum(page0 + i, pages_per_seq - 1)], 0)
            yield page0 + i < held, pltpu.make_async_copy(
                pages_hbm.at[page_id],
                buf.at[slot, pl.ds(i * (page // pair), page // pair)],
                sems.at[slot])

    def start(w, slot):
        for fetched, copy in copies(w, slot):
            pl.when(fetched)(copy.start)

    def wait(w, slot):
        for fetched, copy in copies(w, slot):
            pl.when(fetched)(copy.wait)

    def first_row(r):
        """Row tile ``r`` answers for tokens ``r * rt`` to ``r * rt + rt -
        1`` and holds ``rt`` rows from here: the last tile of a tick that
        is no whole number of tiles starts early, and the rows it shares
        with the tile before are that tile's."""
        return pl.multiple_of(jnp.minimum(r * rt, T - rt), 16)

    # rows no sequence of the list owns (one-query rows, padding)
    o_ref[...] = jnp.zeros_like(o_ref)
    # the key's column minus the row's token (rows of a tile: query head g
    # of the tile's i-th token at ``g * rt + i``), the masks' only vector
    # operand
    delta_sc[...] = (
        jax.lax.broadcasted_iota(jnp.int32, (keys, R), 0)
        - jax.lax.broadcasted_iota(jnp.int32, (keys, R), 1) % rt)

    @pl.when(n_work > 0)
    def _():
        start(0, 0)

    def lay_out(slot, kv_len, key0):
        """K, and V transposed, of each KV head out of the block as
        fetched: row ``t * Hkv + h`` of ``words`` is token t's K (low half)
        and V (high half) of head h."""
        words = buf.at[slot].reshape(keys * combined, D).bitcast(jnp.uint32)
        valid = (jax.lax.broadcasted_iota(jnp.int32, (keys, D), 0)
                 < kv_len - key0)
        for h in range(Hkv):
            w = words[h::Hkv, :]                              # [keys, D]
            k_sc[h] = pltpu.bitcast(w << 16, jnp.float32).astype(k_sc.dtype)
            # a stale NaN past the end would survive a zero weight
            vt_sc[h] = jnp.where(valid, pltpu.bitcast(
                w & jnp.uint32(0xFFFF0000), jnp.float32), 0.0
            ).T.astype(vt_sc.dtype)

    def tile(h, r, edge, masked: bool, n: int):
        """Row tile ``r`` of KV head ``h`` against the block's first ``n``
        lane tiles of keys."""
        used = n * lanes
        q = q_ref[h, :, pl.ds(first_row(r), rt), :].reshape(R, D)
        s = jax.lax.dot_general(
            k_sc[h, :used, :], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [used, R]
        if masked:
            delta = delta_sc[:used, :]
            attended = delta <= edge
            if sliding_window is not None:
                attended = jnp.logical_and(
                    attended, delta > edge - sliding_window)
            s = jnp.where(attended, s, _MASK_VALUE)
        m_prev = m_sc[h, r]                                   # [1, R]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[h, r] = alpha * l_sc[h, r] + jnp.sum(p, axis=0, keepdims=True)
        acc_sc[h, r] = acc_sc[h, r] * alpha + jnp.dot(
            vt_sc[h, :, :used], p.astype(vt_sc.dtype),
            preferred_element_type=jnp.float32)               # [D, R]
        m_sc[h, r] = m_new

    def one_block(w, _):
        slot = w % 2

        @pl.when(w + 1 < n_work)
        def _():
            start(w + 1, 1 - slot)

        s, flags = seq_ref[w], flags_ref[w]
        t0, t1, kv_len = cu_ref[s], cu_ref[s + 1], kv_lens_ref[s]
        key0 = block_ref[w] * keys
        # position of the sequence's row at flat row t: ``t + shift``
        shift = kv_len - t1
        r0, r1 = t0 // rt, (t1 - 1) // rt + 1

        @pl.when(flags % 2 == 1)
        def _():
            def reset(r, _):
                for h in range(Hkv):
                    m_sc[h, r] = jnp.full((1, R), _MASK_VALUE, jnp.float32)
                    l_sc[h, r] = jnp.zeros((1, R), jnp.float32)
                    acc_sc[h, r] = jnp.zeros((D, R), jnp.float32)

            jax.lax.fori_loop(r0, r1, reset, None)

        wait(w, slot)
        lay_out(slot, kv_len, key0)

        def one_tile(r, _):
            # positions of the first and last row of THIS sequence that the
            # tile answers for
            lo = jnp.maximum(r * rt, t0) + shift
            hi = jnp.minimum(r * rt + rt, t1) - 1 + shift
            # key ``key0 + c`` is attended by the tile's i-th row where
            # ``c - i <= edge`` (and ``> edge - window``)
            edge = first_row(r) + shift - key0
            run = key0 <= hi
            crossed = key0 + keys - 1 > lo
            if sliding_window is not None:
                run = jnp.logical_and(
                    run, key0 + keys - 1 > lo - sliding_window)
                crossed = jnp.logical_or(
                    crossed, key0 <= hi - sliding_window)
            # lane tiles of keys up to the last row's own
            needed = jnp.minimum((hi - key0) // lanes + 1, cols)

            def every_head(masked: bool, n: int):
                # ``_HEADS_A_STEP`` heads a step of the loop: independent
                # chains, so one head's products overlap another's softmax
                def some(i, _):
                    for j in range(hs):
                        tile(i * hs + j, r, edge, masked, n)

                jax.lax.fori_loop(0, Hkv // hs, some, None)

            # a tile a mask edge crosses pays for the mask, and multiplies
            # only the lane tiles of keys its last row reaches
            for n in range(1, cols + 1):
                pl.when(jnp.logical_and(jnp.logical_and(run, crossed),
                                        needed == n))(
                    functools.partial(every_head, True, n))
            pl.when(jnp.logical_and(run, jnp.logical_not(crossed)))(
                functools.partial(every_head, False, cols))

        jax.lax.fori_loop(r0, r1, one_tile, None)

        @pl.when(flags // 2 == 1)
        def _():
            def write(r, _):
                rows = pl.ds(first_row(r), rt)
                token = first_row(r) + jax.lax.broadcasted_iota(
                    jnp.int32, (R, D), 0) % rt
                mine = jnp.logical_and(token >= jnp.maximum(t0, r * rt),
                                       token < t1)
                for h in range(Hkv):
                    o_ref[h, :, rows, :] = jnp.where(
                        mine, (acc_sc[h, r] / l_sc[h, r]).T,
                        o_ref[h, :, rows, :].reshape(R, D).astype(
                            jnp.float32)
                    ).astype(o_ref.dtype).reshape(G, rt, D)

            jax.lax.fori_loop(r0, r1, write, None)

    jax.lax.fori_loop(0, n_work, one_block, None)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "sliding_window", "tiles", "interpret"))
def _chunk(q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
           page_indices: jax.Array, cu_q_lens: jax.Array,
           num_seqs: jax.Array, *, sm_scale: float,
           sliding_window: Optional[int], tiles: Tuple[int, int],
           interpret: bool) -> jax.Array:
    """The kernel at static ``(rt, kb)``, heads first as the model holds
    them: ``q [H, T, D]`` to ``[H, T, D]`` with the rows of every sequence
    of two queries or more, zero elsewhere.  ``T`` is a whole number
    of sublane tiles and at least ``rt``.  Jitted so that the calls of one
    program trace and lower once."""
    H, T, D = q.shape
    _, page, combined, _ = pages.shape
    Hkv = combined // 2
    G = H // Hkv
    rt, kb = tiles
    pair = paged_decode.tokens_a_tile(Hkv) or 1   # the interpreter: any count
    if pair > 1:
        # a sublane tile of a token pair: the same bytes where they lie
        pages = pages.reshape(-1, page // pair, pair * combined, D)
    assert T % 16 == 0 and T >= rt, (T, rt)
    n_tiles = -(-T // rt)
    R, keys = rt * G, kb * page
    pages_per_seq = page_indices.shape[1]
    work, n_work = work_list(
        kv_lens, cu_q_lens, num_seqs, page_size=page,
        pages_per_seq=pages_per_seq, kb=kb, sliding_window=sliding_window)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale,
                          sliding_window=sliding_window,
                          pages_per_seq=pages_per_seq, page=page),
        out_shape=jax.ShapeDtypeStruct((Hkv, G, T, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            grid=(1,),
            scratch_shapes=[
                pltpu.VMEM((2, keys // pair, pair * combined, D),
                           pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((Hkv, keys, D), pages.dtype),
                pltpu.VMEM((Hkv, D, keys), pages.dtype),
                pltpu.VMEM((keys, R), jnp.int32),
                pltpu.VMEM((Hkv, n_tiles, 1, R), jnp.float32),
                pltpu.VMEM((Hkv, n_tiles, 1, R), jnp.float32),
                pltpu.VMEM((Hkv, n_tiles, D, R), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(T, H, Hkv, page, D, tiles)),
        interpret=interpret, name=NAME,
    )(*work, n_work, kv_lens.astype(jnp.int32), cu_q_lens.astype(jnp.int32),
      page_indices.astype(jnp.int32).reshape(-1),
      q.reshape(Hkv, G, T, D), pages).reshape(H, T, D)


def fused_tick_attention(
        q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
        page_indices: jax.Array, cu_q_lens: jax.Array, num_seqs: jax.Array,
        *, sm_scale: float, sliding_window: Optional[int] = None,
        fallback: Callable[[], jax.Array], fallback_route: str,
        interpret: bool = False) -> jax.Array:
    """Attention of a fused tick: ``q [T, H, D]``, the rows of packed
    sequence ``j`` at ``cu_q_lens[j]:cu_q_lens[j + 1]``, the last of them
    at position ``kv_lens[j] - 1``, over the pool ``pages [P, page, 2 *
    Hkv, D]`` through ``page_indices [S, pp]``.  Rows past the last
    sequence come back zero.

    **Separating, by** ``cu_q_lens``: sequences with one query row are a
    decode tick's work and take ``ops/paged_decode.py`` (gathered to one
    row a sequence and scattered back), sequences with more take this
    module's kernel; the two write disjoint rows.  Both on a TPU where
    both kernels' layouts hold (:func:`tiling`,
    :func:`paged_decode.tiling`), else ``fallback()``; which is recorded
    under :data:`KERNEL` for every distinct answer."""
    T, H, D = q.shape
    _, page, combined, _ = pages.shape
    Hkv = combined // 2
    S, pages_per_seq = page_indices.shape
    tiles = decode_tiles = None
    width = T
    if q.dtype == pages.dtype:
        decode_tiles = paged_decode.tiling(Hkv, page, pages_per_seq, D,
                                           pages.dtype)
        tiles = tiling(H, Hkv, page, D, pages.dtype)
        if tiles is not None:
            # a whole number of sublane tiles of rows, a row tile at least
            width = max(-(-T // 16) * 16, tiles[0])
        if (decode_tiles is None or tiles is None or
                vmem_bytes(width, H, Hkv, page, D, tiles) > _VMEM_CEILING):
            tiles = None
    route = routes.take(
        KERNEL, eligible=tiles is not None, interpret=interpret, every=True,
        xla=fallback_route, pallas=route_name(tiles, H, Hkv, page))
    if route == fallback_route:
        return fallback()
    # heads first, as the model holds q and wants the answer: the caller's
    # own transposes cancel against these, and the pad is none where a
    # tick's width is a multiple of 16 and no less than a row tile
    out = _chunk(
        jnp.pad(q.transpose(1, 0, 2), ((0, 0), (0, width - T), (0, 0))),
        pages, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale, sliding_window=sliding_window, tiles=tiles,
        interpret=interpret)[:, :T].transpose(1, 0, 2)
    # the one-query sequences: row ``cu_q_lens[j]`` is sequence j's query
    first = cu_q_lens[:-1]
    one = jnp.logical_and(jnp.arange(S) < num_seqs[0],
                          cu_q_lens[1:] - first == 1)
    rows = paged_decode._decode(
        jnp.take(q, jnp.minimum(first, T - 1), axis=0), pages, kv_lens,
        page_indices, one, sm_scale=sm_scale,
        sliding_window=sliding_window, tiles=decode_tiles,
        interpret=interpret)
    return out.at[jnp.where(one, first, T)].set(rows, mode="drop")
