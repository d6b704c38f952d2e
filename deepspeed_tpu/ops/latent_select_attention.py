"""Latent attention over the rows a token-by-token choice leaves
(``models/glm_moe_dsa.py``): the absorbed form of ``ops/latent_attention.py``
under the choice of ``ops/token_select_attention.py``.

The pool is ``ops/latent_attention.py``'s: one row ``[c | k_r]`` a token,
``W`` wide, which is every head's key and whose first ``rank`` columns are
the value.  What differs is WHICH rows a query meets: the ``topk`` an
indexer chose for it (``token_select_attention.index_select`` for the rows
of a prompt chunk, ``row_index_scores`` and ``row_choice`` for a row alone),
so no block of keys is met unmasked and the diagonal is part of the choice.

- :func:`chunk_attention` (kernel ``latent_select_chunk_attention``): the
  prompt chunks of a fused tick in ``ops/linear_attention.py:tile_layout``'s
  tiles of :data:`TILE` rows, queries HEADS FIRST as the module's absorbing
  product emits them.  A grid step is (a tile, :data:`HEADS` heads of it):
  those ``HEADS * TILE`` query rows meet a key step of eight pages in ONE
  product, step after step up to the tile's last row's own key, pages and
  the step's block of the bias double-buffered.  The bias is
  ``index_select``'s ``[keys, TILE]`` bfloat16 (0 at a chosen key); it is
  turned once a step and every head's slab of the scores takes the one bias.
  **Why a tile is not every head of its tokens**, as the dense kernel's is:
  at 64 heads that is 8,192 query rows, whose accumulator, statistics and
  scores alone are 60 MB of VMEM beside 38 MB of queries and outputs in
  flight (``ops/latent_attention.py:chunk_vmem_bytes(1032, 64, 640, 512,
  128, 512)`` is 165 MB: it keeps the whole tick's output besides).  A
  quarter of the heads a step keeps 2,048 x 640 against 512 x 640, the
  product the dense kernel's masked blocks make, and fetches a key step
  four times: 0.66 MB against 2.4 GFLOP.
  This is a MASKED DENSE pass: it multiplies every key up to the tile's
  last row, ``t + 1`` a query where the equations need ``min(t + 1,
  topk)``.  With weights from a seed each row's choice is a random subset,
  so the union over a tile's rows is every key and no step can be skipped;
  a form that gathers the chosen rows is ROADMAP's.
- :func:`row_attention`: one query a sequence (decode rows) GATHERS the
  chosen rows by token and reads no other: ``jax.numpy`` on every backend,
  as ``token_select_attention.row_attention`` is and for its reason (2,048
  descriptors of 1,280 bytes a row a layer as a Pallas kernel).  One row
  serves all the heads: 2.6 MB a query row a layer.

The chunk kernel's ``jax.numpy`` twin is :func:`chunk_twin`; which ran is
recorded under :data:`KERNEL_PREFILL`, and the one-row gather under
:data:`KERNEL_DECODE`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes
from deepspeed_tpu.ops.block_select_attention import (_STEP_PAGES, TILE,
                                                      step_copies)
from deepspeed_tpu.ops.token_select_attention import _MASK_VALUE, padded_keys

KERNEL = "latent_select"
KERNEL_PREFILL = KERNEL + "_prefill"
KERNEL_DECODE = KERNEL + "_decode"
CHUNK_NAME = "latent_select_chunk_attention"

_LANES = 128
# heads a grid step multiplies: 2,048 query rows a product at tiles of 128
HEADS = 16


def heads_a_step(heads: int) -> int:
    """:data:`HEADS`, or every head of a model that has fewer or no whole
    number of such groups."""
    return HEADS if heads % HEADS == 0 else heads


# -- one row a sequence (decode rows): jax.numpy on every backend -------------

def row_attention(q, pages, page_indices, tokens, taken, *, rank: int,
                  sm_scale: float):
    """``q [S, H, W]`` (absorbed) over the tokens ``tokens [S, k]``
    (positions in the row's sequence, ``taken`` of them real) of ``pages
    [P, page, W]`` through ``page_indices [S, pp]`` -> ``[S, H, rank]``.
    Gathers the chosen tokens' rows and reads no other row of the pool (an
    entry not taken reads the row's first chosen token and weighs 0)."""
    P, page, W = pages.shape
    tok = jnp.where(taken, tokens, tokens[:, :1])
    pg = jnp.maximum(jnp.take_along_axis(page_indices, tok // page, axis=1),
                     0)
    got = jnp.take(pages.reshape(P * page, W), pg * page + tok % page,
                   axis=0)                                   # [S, k, W]
    s = jnp.einsum("shw,skw->shk", q, got,
                   preferred_element_type=jnp.float32) * sm_scale
    ok = taken[:, None]
    s = jnp.where(ok, s, _MASK_VALUE)
    p = jnp.where(ok, jax.nn.softmax(s, axis=-1), 0.0)
    y = jnp.einsum("shk,skc->shc", p.astype(got.dtype), got[..., :rank],
                   preferred_element_type=jnp.float32)
    return y.astype(q.dtype)


def decode_route(topk: int) -> str:
    """Record and return the one-row path's route: the gather by token."""
    return routes.take(KERNEL_DECODE, eligible=False,
                       xla=f"xla-latent-token-gather-{int(topk)}")


# -- the prompt chunks of a fused tick ----------------------------------------

def chunk_twin(q, pages, bias, tile_table, *, rank: int, sm_scale: float):
    """``q [H, NT * TILE, W]``, ``bias [NT, K, TILE]`` (0 at the keys the row
    across attends, :data:`_MASK_VALUE` at the others), ``tile_table [NT,
    pp]`` (each tile's sequence's pages) -> ``[H, NT * TILE, rank]``; a row
    with no key comes back zero."""
    H, rows, W = q.shape
    NT = tile_table.shape[0]
    keys = jnp.take(pages, jnp.maximum(tile_table, 0), axis=0).reshape(
        NT, -1, W)                                           # [NT, K', W]
    K = keys.shape[1]
    ok = (bias[:, :K].transpose(0, 2, 1) > 0.5 * _MASK_VALUE)[None]
    s = jnp.einsum("hnrw,nkw->hnrk",
                   q.reshape(H, NT, TILE, W).astype(jnp.float32),
                   keys.astype(jnp.float32)) * sm_scale
    s = jnp.where(ok, s, _MASK_VALUE)
    p = jnp.where(ok, jax.nn.softmax(s, axis=-1), 0.0)
    y = jnp.einsum("hnrk,nkc->hnrc", p, keys[..., :rank].astype(jnp.float32))
    return y.reshape(H, rows, rank).astype(q.dtype)


def _chunk_kernel(seq_ref, pos0_ref, len_ref, table_ref,    # scalar prefetch
                  q_ref, bias_hbm, pages_hbm, o_ref,
                  buf, sems, bbuf, bsems, m_sc, l_sc, acc_sc, *,
                  sm_scale: float, pages_per_seq: int):
    # q_ref: [hg, TILE, W]; o_ref: [hg, TILE, rank] (some heads of one
    # tile); bias_hbm: [NT, K, TILE] bfloat16 (in HBM: a key step's block
    # comes with the step's pages, so what lies past a tile's last step,
    # and a tile of no row, is never fetched); pages_hbm: [P, page, W]; buf:
    # [2, _STEP_PAGES, page, W]; bbuf: [2, keys, TILE]; m_sc, l_sc: [R, 1];
    # acc_sc: [R, rank] with R = hg * TILE (head h's i-th row at h * TILE + i)
    hg, _, W = q_ref.shape
    rank = o_ref.shape[2]
    page = pages_hbm.shape[1]
    keys = _STEP_PAGES * page
    R = hg * TILE
    n = pl.program_id(0)
    seq, pos0, length = seq_ref[n], pos0_ref[n], len_ref[n]
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
        # then, and every live row attends a key
        rows = buf[slot].reshape(keys, W)
        bias = bbuf[slot].astype(jnp.float32).T               # [TILE, keys]
        s = jax.lax.dot_general(
            q_ref[...].reshape(R, W), rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        # every head's slab of the scores takes the one bias
        s = (s.reshape(hg, TILE, keys) + bias[None]).reshape(R, keys)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

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
        # zeros for a row past the tile's length and for one that met no
        # key it attends (it holds the first maximum)
        i = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) % TILE
        ok = jnp.logical_and(i < length, m_sc[...] > _MASK_VALUE)
        o_ref[...] = jnp.where(ok, acc_sc[...] / l_sc[...], 0.0).astype(
            o_ref.dtype).reshape(hg, TILE, rank)


def chunk_vmem_bytes(hg: int, W: int, rank: int, page: int) -> int:
    """What a call of the chunk kernel keeps in VMEM: a step's heads of a
    tile of q and of the output, two buffers each; two key steps as
    fetched, with their blocks of the bias; the accumulator and the two
    statistics (a column each, which lies in whole lane tiles); and four
    score tiles of working room (the scores, their weights, the weights
    rounded, the bias turned and spread)."""
    keys, R = _STEP_PAGES * page, hg * TILE
    return (2 * R * W * 2 + 2 * R * rank * 2 + 2 * keys * (W + TILE) * 2
            + R * rank * 4 + 2 * R * _LANES * 4 + 4 * R * keys * 4)


@functools.partial(jax.jit, static_argnames=("rank", "sm_scale", "hg",
                                             "interpret"))
def _chunk(q, pages, bias, tile_seq, tile_pos0, tile_len, page_indices, *,
           rank: int, sm_scale: float, hg: int, interpret: bool):
    H, rows, W = q.shape
    _, page, _ = pages.shape
    NT = rows // TILE
    pp = page_indices.shape[1]
    keys = _STEP_PAGES * page
    K = bias.shape[1]
    assert H % hg == 0 and pages.shape[2] == W, (q.shape, pages.shape, hg)
    assert bias.shape == (NT, K, TILE) and K == padded_keys(pp, page), (
        bias.shape, pp)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sm_scale=sm_scale,
                          pages_per_seq=pp),
        out_shape=jax.ShapeDtypeStruct((H, rows, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((hg, TILE, W),
                                   lambda n, g, *_: (g, n, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((hg, TILE, rank),
                                   lambda n, g, *_: (g, n, 0)),
            grid=(NT, H // hg),
            scratch_shapes=[
                pltpu.VMEM((2, _STEP_PAGES, page, W), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((2, keys, TILE), bias.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hg * TILE, 1), jnp.float32),
                pltpu.VMEM((hg * TILE, 1), jnp.float32),
                pltpu.VMEM((hg * TILE, rank), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=chunk_vmem_bytes(hg, W, rank, page)),
        interpret=interpret, name=CHUNK_NAME,
    )(tile_seq, tile_pos0, tile_len, page_indices.reshape(-1), q, bias,
      pages)


def eligible(W: int, rank: int, page: int, dtype) -> bool:
    """Mosaic's layout: a bf16 pool, ``rank`` and ``W`` whole lane tiles,
    pages of whole sublane tiles."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and W % _LANES == 0
            and rank % _LANES == 0 and rank <= W and page % 16 == 0)


def chunk_attention(q, pages, bias, tile_seq, tile_pos0, tile_len,
                    page_indices, *, rank: int, sm_scale: float,
                    interpret: bool = False) -> jax.Array:
    """Latent attention of the tiled prompt rows of a tick under their
    choice: ``q [H, NT * TILE, W]`` (absorbed, heads first) over ``pages
    [P, page, W]`` through ``page_indices [S, pp]``, tile ``n`` rows
    ``tile_len[n]`` (0: no row, and zeros come back) of sequence
    ``tile_seq[n]`` from position ``tile_pos0[n]`` on; ``bias [NT,
    padded_keys(pp, page), TILE]`` bfloat16 as ``token_select_attention.
    index_select`` writes it.  ``[H, NT * TILE, rank]``: the weighted sums
    of the chosen rows' first ``rank`` columns.  The Pallas kernel on a TPU,
    else the twin (which reads every step: give it a whole bias); recorded
    under :data:`KERNEL_PREFILL`."""
    H, _, W = q.shape
    page = pages.shape[1]
    hg = heads_a_step(H)
    route = routes.take(
        KERNEL_PREFILL, eligible=eligible(W, rank, page, pages.dtype)
        and q.dtype == pages.dtype, interpret=interpret,
        pallas=f"pallas-latent-select-chunk-{hg * TILE}x{_STEP_PAGES * page}")
    if route == routes.XLA:
        return chunk_twin(q, pages, bias,
                          jnp.take(page_indices, tile_seq, axis=0),
                          rank=rank, sm_scale=sm_scale)
    return _chunk(q, pages, bias.astype(jnp.bfloat16),
                  tile_seq.astype(jnp.int32), tile_pos0.astype(jnp.int32),
                  tile_len.astype(jnp.int32), page_indices.astype(jnp.int32),
                  rank=rank, sm_scale=sm_scale, hg=hg, interpret=interpret)
