"""Paged attention for a decode tick (Pallas TPU kernel).

A decode tick has ONE query row a sequence slot
(``inference/v2/programs.py:build_decode_block``), and what it costs is
the K and V it reads.  The kernel the fused tick uses
(``jax.experimental.pallas.ops.tpu.ragged_paged_attention``) is built for
hundreds of query rows of one sequence: it moves and multiplies whole
blocks of 256-512 keys whatever a sequence holds, gives an idle slot a
block too, pads the score tile to 32 queries, and at 16 KV heads reads
the pool in two strided passes (PERF.md, PR 31).  This one does only what
a decode tick needs:

- **only the pages a sequence holds**: the work of a call is a flat list
  of (slot, group of up to ``G`` pages) built from ``kv_lens``, ``live``
  and the window by a dozen dense operations (:func:`work_list`); an idle
  slot, a page past a sequence's end and a page wholly before the sliding
  window are in no group, so nothing is fetched for them;
- **one contiguous DMA a page, every KV head in it**, double-buffered by
  group; the loop runs over the flat list, so the next group is in flight
  while this one is multiplied, across sequences as within one;
- **pages multiplied as they lie in the pool**.  A page is rows of
  (token, KV head), each a packed ``uint32`` of that head's K (low half:
  even combined head) and V (high half; ``inference/paged/kv_pages.py``).  Picking
  one head's rows out is a sublane-strided load, which the v5e issues one
  a cycle and in pieces (45 loads a 128 keys of a head at 8 KV heads, 78
  at 16: the bound of a body written a head at a time, PERF.md PR 31).
  So no row is picked out: all of a slot's query heads meet every row of
  a step of pages in ONE product, and a query head keeps the columns of
  its own KV head (the mask that already drops keys past the end).  The
  MXU's cost there is the 128-row tiles of K and V it loads, whatever
  their rows are, so this costs what a head at a time would; the scores
  are ``H / Hkv`` times wider than they need be, which the vector unit
  has room for;
- V rows past the sequence's end are zeroed on the float32 the unpack
  gives (a stale NaN there would survive a zero weight); K needs no mask
  of its own: a masked score is replaced, not added to.  A group's pages
  are multiplied whole, fetched or not: a branch a page would cut the
  body into blocks the scheduler cannot overlap (measured: a quarter
  slower).

Arithmetic as the fused tick's kernel: K, V and q as stored (bf16),
float32 scores, softmax statistics and accumulator; the weights of the
p·V product are rounded to the pool's dtype.  The pool layout
``[P, page, 2 * Hkv, D]`` is unchanged.

Mosaic needs ``D == 128``, whole sublane tiles of combined heads a token
(at FOUR KV heads: a PAIR of tokens, :func:`tokens_a_tile`; the pool is then
fetched and buffered as ``[P, page / 2, 16, D]``, a bitcast of the array
where it lies, and the body is the same to the letter) and whole lane tiles
of rows a page (:func:`tiling` answers None otherwise, and the caller keeps
the path it had).  Under
``interpret=True`` the same kernel runs through the Pallas interpreter at
any head count (``tests/unit/ops/test_paged_decode.py``);
``tests/unit/ops/test_tpu_compile.py`` hands it to the chip's compiler at
the serving configurations' widths.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes

# the route key of a decode program's attention: which kernel each call
# took (``paged_attention`` beside it is the fused tick's)
KERNEL = "paged_attention_decode"
# the operation's name in the compiled program and in a device trace
NAME = "paged_decode_attention"

_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_LANES = 128
# bytes of K and V one DMA group holds (two are in VMEM at a time), and
# (token, KV head) rows one product of the body takes.  Settled on the
# v5e at 32 slots of 256-3,072 tokens and at 15 of 32 at about 600, for 8
# and 16 KV heads (PERF.md, PR 31): 1 and 2 MiB groups are within 2 % of
# each other on long contexts and the smaller is a tenth faster on short
# ones (a last group is multiplied whole); products of 512 rows are a
# tenth slower than of 2,048 (a step's softmax waits for its scores)
_GROUP_BYTES = 1024 * 1024
_STEP_ROWS = 2048


def tokens_a_tile(kv_heads: int) -> Optional[int]:
    """Tokens whose ``2 * kv_heads`` combined heads fill whole bf16 sublane
    tiles of 16 rows: 1 at a multiple of eight KV heads; 2 at FOUR, where a
    tile holds the eight combined heads of a PAIR of tokens (both kernels
    then fetch and buffer the pool as ``[P, page / 2, 16, D]``, the same
    bytes, and row ``t * Hkv + h`` of the packed words is token t's head h
    as at any other count); None otherwise.  Two KV heads would be four
    tokens a tile: no configuration reads two through these kernels (the one
    with two attends chosen blocks of a pool of its own), so that layout has
    never been handed to the compiler and keeps the path it had."""
    if (2 * kv_heads) % 16 == 0:
        return 1
    return 2 if kv_heads == 4 else None


def tiling(kv_heads: int, page_size: int, pages_per_seq: int,
           head_dim: int, dtype) -> Optional[Tuple[int, int]]:
    """``(pages a DMA group, pages a step of the body)`` from the shapes
    alone, or None where the kernel's layout does not hold: head_dim 128,
    a bf16 pool (K and V of a head are the halves of one 32-bit word, and
    a bf16 is the upper half of its float32), whole sublane tiles of
    combined heads a token or, at four KV heads, a pair of tokens
    (:func:`tokens_a_tile`), and whole lane tiles of (token, KV head) pairs
    a page."""
    dtype = jnp.dtype(dtype)
    if head_dim != _LANES or dtype != jnp.bfloat16:
        return None
    pair = tokens_a_tile(kv_heads)
    if pair is None or page_size % pair or (page_size * kv_heads) % _LANES:
        return None
    page_bytes = page_size * 2 * kv_heads * head_dim * dtype.itemsize
    step = max(1, _STEP_ROWS // (page_size * kv_heads))
    group = max(step, _GROUP_BYTES // page_bytes // step * step)
    return min(group, -(-pages_per_seq // step) * step), step


def route_name(tiles: Optional[Tuple[int, int]], kv_heads: int) -> str:
    """What :data:`KERNEL` reads where the kernel runs at ``tiles``:
    ``pallas-decode-<pages a group>x<KV heads>``."""
    return f"pallas-decode-{tiles[0] if tiles else 0}x{kv_heads}"


def work_list(kv_lens: jax.Array, live: jax.Array, *, page_size: int,
              pages_per_seq: int, group: int,
              sliding_window: Optional[int]):
    """``(slot, page0, count, flags) [W], n_work [1]``: the groups of
    pages a call works through, in slot order.  A group is up to ``group``
    consecutive pages of one slot's page-table row from ``page0`` on;
    ``flags`` bit 0 marks a slot's first group, bit 1 its last.  A slot
    that is not ``live`` has none; a live one has the pages from the one
    that holds its first attended key (0, or ``kv_len - window``) to the
    one that holds its last.  ``W = slots * ceil(pages_per_seq / group)``
    bounds the list; entries past ``n_work`` are never read."""
    S = kv_lens.shape[0]
    first_key = (jnp.zeros_like(kv_lens) if sliding_window is None
                 else jnp.maximum(kv_lens - sliding_window, 0))
    first_page = first_key // page_size
    end_page = jnp.minimum(-(-kv_lens // page_size), pages_per_seq)
    pages = jnp.where(live, jnp.maximum(end_page - first_page, 0), 0)
    slot, g, flags, n_work = flat_list(-(-pages // group),
                                       S * -(-pages_per_seq // group))
    return ((slot, first_page[slot] + g * group,
             jnp.clip(pages[slot] - g * group, 0, group), flags), n_work)


def flat_list(counts: jax.Array, width: int):
    """``(owner, index, flags) [width], n [1]``: one entry for each of
    ``counts[s]`` items of every owner ``s``, in owner order: whose it is,
    which of its owner's it is, and ``flags`` bit 0 on an owner's first
    item, bit 1 on its last.  ``n`` is how many there are; entries past it
    are never to be read."""
    ends = jnp.cumsum(counts)
    starts = ends - counts
    w = jnp.arange(width, dtype=jnp.int32)
    owner = jnp.minimum(
        jnp.sum((ends[None, :] <= w[:, None]).astype(jnp.int32), axis=1),
        counts.shape[0] - 1)
    index = w - starts[owner]
    flags = (index == 0).astype(jnp.int32) + 2 * (index == counts[owner] - 1)
    return owner, index, flags, ends[-1:]


def _kernel(slot_ref, page0_ref, count_ref, flags_ref, nwork_ref,
            kv_lens_ref, table_ref,                     # scalar prefetch
            q_ref, pages_hbm, o_ref,
            buf, sems, m_sc, l_sc, acc_sc, *,
            sm_scale: float, sliding_window: Optional[int],
            pages_per_seq: int, page: int, Hkv: int):
    # q_ref, o_ref: [S, H, D]; pages_hbm: [P, page / pair, pair * 2Hkv, D]
    # (``pair`` tokens a sublane tile, :func:`tokens_a_tile`);
    # buf: [2, G // step, step * page / pair, pair * 2Hkv, D], ``step``
    # pages multiplied at a time; m_sc, l_sc: [H, 1]; acc_sc: [H, D]
    combined = 2 * Hkv
    _, steps, tiles_a_step, tile_rows, D = buf.shape
    pair = tile_rows // combined
    tokens = tiles_a_step * pair
    step = tokens // page
    group = steps * step
    H = q_ref.shape[1]
    rows = tokens * Hkv              # (token, KV head) pairs of a step
    n_work = nwork_ref[0]

    def copies(w, slot):
        s, page0 = slot_ref[w], page0_ref[w]
        for i in range(group):
            # a page of the table's padding (-1) is past every live
            # sequence's end and in no group; the clamp only keeps an
            # address legal
            page_id = jnp.maximum(
                table_ref[s * pages_per_seq
                          + jnp.minimum(page0 + i, pages_per_seq - 1)], 0)
            yield i, pltpu.make_async_copy(
                pages_hbm.at[page_id],
                buf.at[slot, i // step,
                       pl.ds(i % step * (page // pair), page // pair)],
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
        kv_len = kv_lens_ref[s]
        key0 = page0_ref[w] * page

        @pl.when(flags % 2 == 1)
        def _():
            m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc_sc[...] = jnp.zeros_like(acc_sc)

        # a step as it lies in the pool: row ``t * Hkv + h`` of ``words``
        # is token t's K (low half) and V (high half) of KV head h.  Every
        # query head meets every row; ``own`` keeps a query head the
        # columns of its KV head
        q = q_ref[s]                                          # [H, D]
        col = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
        own = (col % Hkv) == (jax.lax.broadcasted_iota(
            jnp.int32, (H, rows), 0) // (H // Hkv))
        token = col // Hkv
        row_token = jax.lax.broadcasted_iota(jnp.int32, (rows, D), 0) // Hkv

        def one_step(j):
            words = buf.at[slot, j].reshape(
                tokens * combined, D).bitcast(jnp.uint32)[...]  # [rows, D]
            pos = key0 + j * tokens
            attended = jnp.logical_and(own, token < kv_len - pos)
            if sliding_window is not None:
                attended = jnp.logical_and(
                    attended, token >= kv_len - sliding_window - pos)
            k = pltpu.bitcast(words << 16, jnp.float32)
            v = jnp.where(row_token < kv_len - pos, pltpu.bitcast(
                words & jnp.uint32(0xFFFF0000), jnp.float32), 0.0)
            scores = jax.lax.dot_general(
                q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            scores = jnp.where(attended, scores, _MASK_VALUE)
            m_prev = m_sc[...]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1,
                                                    keepdims=True)
            acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
                p.astype(q.dtype), v.astype(q.dtype),
                preferred_element_type=jnp.float32)
            m_sc[...] = m_new

        for j in range(steps):
            one_step(j)

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
    "sm_scale", "sliding_window", "tiles", "interpret"))
def _decode(q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
            page_indices: jax.Array, live: jax.Array, *, sm_scale: float,
            sliding_window: Optional[int], tiles: Tuple[int, int],
            interpret: bool) -> jax.Array:
    """The kernel at static ``(group, step)`` pages; jitted so that the
    calls of one program trace and lower once."""
    S, H, D = q.shape
    _, page, combined, _ = pages.shape
    pages_per_seq = page_indices.shape[1]
    group, step = tiles
    assert group % step == 0, tiles
    pair = tokens_a_tile(combined // 2) or 1   # the interpreter: any count
    if pair > 1:
        # a sublane tile of a token pair: the same bytes where they lie
        pages = pages.reshape(-1, page // pair, pair * combined, D)
    work, n_work = work_list(
        kv_lens, live, page_size=page, pages_per_seq=pages_per_seq,
        group=group, sliding_window=sliding_window)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale,
                          sliding_window=sliding_window,
                          pages_per_seq=pages_per_seq, page=page,
                          Hkv=combined // 2),
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            grid=(1,),
            scratch_shapes=[
                pltpu.VMEM((2, group // step, step * page // pair,
                            pair * combined, D), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=NAME,
    )(*work, n_work, kv_lens.astype(jnp.int32),
      page_indices.astype(jnp.int32).reshape(-1), q, pages)


def paged_decode_attention(
        q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
        page_indices: jax.Array, live: Optional[jax.Array], *,
        sm_scale: float, sliding_window: Optional[int] = None,
        fallback: Callable[[], jax.Array], fallback_route: str,
        interpret: bool = False) -> jax.Array:
    """Attention of a decode tick: ``q [S, H, D]``, row ``s`` the one
    query of slot ``s`` at position ``kv_lens[s] - 1``, over the pool
    ``pages [P, page, 2 * Hkv, D]`` through ``page_indices [S, pp]``
    (``-1`` past a sequence's end).  Slots where ``live`` is false come
    back zero and touch no page.  The Pallas kernel on a TPU where
    :func:`tiling` finds its layout, else ``fallback()``; which is
    recorded under :data:`KERNEL` for every distinct answer."""
    S, H, D = q.shape
    _, page, combined, _ = pages.shape
    tiles = None
    if q.dtype == pages.dtype and H % (combined // 2) == 0:
        tiles = tiling(combined // 2, page, page_indices.shape[1], D,
                       pages.dtype)
    route = routes.take(
        KERNEL, eligible=tiles is not None, interpret=interpret,
        every=True, xla=fallback_route,
        pallas=route_name(tiles, combined // 2))
    if route == fallback_route:
        return fallback()
    if live is None:
        live = jnp.ones((S,), bool)
    return _decode(q, pages, kv_lens, page_indices, live,
                   sm_scale=sm_scale, sliding_window=sliding_window,
                   tiles=tiles, interpret=interpret)
