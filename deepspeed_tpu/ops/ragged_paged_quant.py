"""Quantized-pages ragged paged attention (Pallas TPU kernel).

The serving KV pool persists int8 / fp8-e4m3 pages with per-(row, head)
fp32 scales (``inference/paged/kv_pages.py``).  The full-width Pallas kernel the
paged path was written against (``jax.experimental.pallas.ops.tpu.
ragged_paged_attention``) reads float pages, so a quantized pool used to
be dequantized into a transient ``[P, page, 2*Hkv, D]`` float operand
every attention call — the capacity win was real but the bandwidth win
was negative.  This kernel removes that: it streams the 1-byte pages and
their scale rows straight from the pool and dequantizes ONE page tile at
a time in registers (VMEM), so HBM traffic per attended token is the
quantized byte count, never the full-width pool.

Layout contract (shared with :func:`~deepspeed_tpu.inference.paged.
ref_paged_attention`): pages are ``[num_pages, page_size, 2*Hkv, D]``
with K at even combined-head indices and V at odd; ``scales`` is the
matching ``[num_pages, page_size, 2*Hkv]`` fp32 buffer; ``page_indices``
pads unused entries with -1; ``kv_lens`` includes the current tick's
tokens.

Grid: ``(query_row_blocks, num_seq_slots, pages_per_seq)`` with the page
dim minor, so the streaming-softmax accumulators (m, l, acc) carry across
one sequence's pages in VMEM scratch; a query-row block holds at most
``_Q_BLOCK`` tokens, which bounds VMEM at any tick width and skips the
sequences that own no row in it.  The ragged metadata rides scalar
prefetch (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`):
page ids feed the page/scale BlockSpec index maps, so the DMA engine
fetches only attended pages.  The output block changes only with the
row block and is revisited — each sequence's programs write only their
own query rows at their last page step.

Written for Mosaic's vector layouts: everything per-query is
``[Hkv, rows, lanes]`` (KV head leading, untiled), each KV head's work is
2-D ``[rows, D] x [page, D]`` tiles, K/V of one head are sublane-strided
loads of the widened page tile, a scale column becomes a ``[page, 1]``
vector by a masked lane reduction, and masks are built at the shape they
are used — no reshape splits the lane dim or moves an ``i1`` vector.

Head dim must be 128 (the MXU lane width, same constraint as the
full-width kernel), and ``2 * Hkv`` a multiple of 8 to compile for the
chip.  Two suites cover this file on the CPU: ``interpret=True`` runs the
identical kernel through the Pallas interpreter
(``tests/unit/inference/test_paged_quant.py``, parity), and
``tests/unit/ops/test_tpu_compile.py`` hands it to the TPU's compiler at
Mistral-7B widths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# same mask value family as ops/flash_attention.py: large enough to
# vanish under softmax, small enough that (mask - mask) stays exact 0
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
# the blocks below are f32 and lane-padded (a [rows, 1] stat costs a full
# 128-lane tile): ~14 MiB at 128 query rows x 32 heads before the page
# tiles, so the limit is raised past the 16 MiB default scope; well inside
# a v5e core's 128 MiB
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024


# query rows (tokens) one grid step holds in VMEM: bounds the q / output /
# accumulator blocks at any fused-tick width T
_Q_BLOCK = 128


def _quant_kernel(kv_lens_ref, pi_ref, cu_ref, ns_ref,   # scalar prefetch
                  q_ref, pages_ref, scales_ref, o_ref,
                  acc_sc, m_sc, l_sc, kv_sc, *, page: int, groups: int,
                  sliding_window: Optional[int]):
    # Everything per-query is laid out [Hkv, rows, lanes] with
    # rows = q_block * groups (row r is token r // groups, q head
    # h * groups + r % groups), so each KV head's work is plain 2-D
    # [rows, D] x [page, D] tiles — the only layouts Mosaic is asked for
    # keep D (or the page) on the 128-lane dim and never reshape a mask.
    b = pl.program_id(0)                   # query-row block
    i = pl.program_id(1)                   # sequence slot
    j = pl.program_id(2)                   # page ordinal within the slot
    pp = pl.num_programs(2)
    Hkv, N, D = q_ref.shape
    combined = 2 * Hkv
    t0 = b * (N // groups)                 # first token of this block

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _zero_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j == 0)
    def _reset_seq():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)

    q0 = cu_ref[i]
    q1 = cu_ref[i + 1]
    kvl = kv_lens_ref[i]
    # the slot is in use and owns query rows inside this block
    live = jnp.logical_and(
        jnp.logical_and(i < ns_ref[0], q1 > q0),
        jnp.logical_and(q0 < t0 + N // groups, q1 > t0))
    # page j holds attended rows AND is resident: a -1 entry is padding
    # or an interior hole — its tile (the index map clamped it to the
    # trash page) is skipped, and because kv positions derive from the
    # column ordinal j the surviving columns keep their true absolute
    # positions
    in_range = jnp.logical_and(j * page < kvl, pi_ref[i, j] >= 0)

    @pl.when(jnp.logical_and(live, in_range))
    def _tile():
        # dequantize THIS page tile only: 1-byte rows widen to f32 into a
        # [page * 2Hkv, D] scratch whose row p * 2Hkv + c is token p of
        # combined head c, so K / V of one head are sublane-strided loads
        kv_sc[...] = pages_ref[0].astype(jnp.float32).reshape(
            page * combined, D)
        sc = scales_ref[0]                               # [page, 2Hkv]
        head_of = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)

        t_idx = t0 + jax.lax.broadcasted_iota(
            jnp.int32, (N, page), 0) // groups
        kv_idx = jax.lax.broadcasted_iota(jnp.int32, (N, page), 1) + \
            j * page
        q_pos = kvl - (q1 - q0) + (t_idx - q0)           # abs position
        mask = ((t_idx >= q0) & (t_idx < q1) &
                (kv_idx <= q_pos) & (kv_idx < kvl))
        if sliding_window is not None:
            mask = mask & (kv_idx > q_pos - sliding_window)

        def row_scale(c):
            # one fp32 scale per (row, combined head): pick head c's
            # column as a [page, 1] sublane vector (a masked lane
            # reduction — exact, the other terms are 0)
            return jnp.sum(jnp.where(head_of == c, sc, 0.0), axis=1,
                           keepdims=True)

        for h in range(Hkv):
            k = kv_sc[pl.ds(2 * h, page, stride=combined), :] * \
                row_scale(2 * h)                         # [page, D]
            v = kv_sc[pl.ds(2 * h + 1, page, stride=combined), :] * \
                row_scale(2 * h + 1)
            att = jax.lax.dot_general(                   # [N, page]
                q_ref[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            att = jnp.where(mask, att, _MASK_VALUE)
            m_prev = m_sc[h]                             # [N, 1]
            m_new = jnp.maximum(
                m_prev, jnp.max(att, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(att - m_new)
            acc_sc[h] = acc_sc[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            l_sc[h] = l_sc[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_sc[h] = m_new

    @pl.when(jnp.logical_and(live, j == pp - 1))
    def _finalize():
        rows = t0 + jax.lax.broadcasted_iota(
            jnp.int32, (N, 1), 0) // groups
        mine = jnp.logical_and(rows >= q0, rows < q1)    # [N, 1]
        for h in range(Hkv):
            l = jnp.maximum(l_sc[h], 1e-30)
            # rows that never matched a key keep 0 (the engine's padding
            # rows and other sequences' rows are written by their own
            # programs or stay at the j==0 zero fill)
            valid = m_sc[h] > _MASK_VALUE * 0.5          # [N, 1]
            val = jnp.where(valid, acc_sc[h] / l, 0.0)
            o_ref[h] = jnp.where(mine, val, o_ref[h])


def ragged_paged_attention_quant(
        q: jax.Array, pages: jax.Array, scales: jax.Array,
        kv_lens: jax.Array, page_indices: jax.Array,
        cu_q_lens: jax.Array, num_seqs: jax.Array, *, sm_scale: float,
        sliding_window: Optional[int] = None, interpret: bool = False):
    """Ragged paged attention over a QUANTIZED page pool.

    q: ``[T, H, D]`` float; pages: ``[P, page, 2*Hkv, D]`` int8 or
    fp8_e4m3; scales: ``[P, page, 2*Hkv]`` fp32; metadata as the
    full-width kernel (``page_indices`` may pad with -1 — trailing
    padding OR interior holes; hole tiles are skipped and the surviving
    columns keep their true positions).  Returns ``[T, H, D]`` in
    ``q.dtype``.  D must be 128 — the kernel contract
    it shares with the full-width vLLM-TPU kernel; other head dims use
    :func:`~deepspeed_tpu.inference.paged.ref_paged_attention_quant`.
    """
    T, H, D = q.shape
    P, page, combined, _ = pages.shape
    Hkv = combined // 2
    S, pp = page_indices.shape
    assert D == 128, (
        f"ragged_paged_attention_quant requires head_dim 128, got {D} "
        "(use ref_paged_attention_quant for other dims)")
    assert H % Hkv == 0, (H, Hkv)
    groups = H // Hkv
    assert pages.dtype in (jnp.int8, jnp.float8_e4m3fn), pages.dtype

    # pad query rows to the f32 sublane multiple, then to whole blocks
    Tp = (T + 7) // 8 * 8
    tq = min(Tp, _Q_BLOCK)
    Tp = (Tp + tq - 1) // tq * tq
    N = tq * groups

    def to_rows(x):
        # [T, H, ...] -> [Hkv, Tp * groups, lanes]: the KV head leads so
        # the kernel indexes it on an untiled dim
        x = x.astype(jnp.float32)
        x = x.reshape(T, H, -1)
        if Tp != T:
            # padded rows belong to no sequence
            x = jnp.pad(x, ((0, Tp - T), (0, 0), (0, 0)),
                        constant_values=0.0)
        lanes = x.shape[-1]
        return x.reshape(Tp, Hkv, groups, lanes).transpose(
            1, 0, 2, 3).reshape(Hkv, Tp * groups, lanes)

    def from_rows(x):
        lanes = x.shape[-1]
        return x.reshape(Hkv, Tp, groups, lanes).transpose(
            1, 0, 2, 3).reshape(Tp, H, lanes)[:T]

    # fold sm_scale into q host-side (one mul per q element, exactly as
    # ops/flash_attention.py)
    qf = to_rows(q.astype(jnp.float32) * jnp.float32(sm_scale))

    # raw page ids ride the scalar prefetch so the kernel can SKIP -1
    # tiles; the BlockSpec index maps clamp to the trash page only to
    # keep the DMA address legal for skipped tiles
    pi = page_indices.astype(jnp.int32)

    def wide():
        return pl.BlockSpec((Hkv, N, D), lambda b, i, j, *refs: (0, b, 0))

    in_specs = [
        wide(),
        pl.BlockSpec((1, page, combined, D),
                     lambda b, i, j, kvl, pi, cu, ns: (
                         jnp.maximum(pi[i, j], 0), 0, 0, 0)),
        pl.BlockSpec((1, page, combined),
                     lambda b, i, j, kvl, pi, cu, ns: (
                         jnp.maximum(pi[i, j], 0), 0, 0)),
    ]
    out_shape = jax.ShapeDtypeStruct((Hkv, Tp * groups, D), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(Tp // tq, S, pp),
        in_specs=in_specs,
        out_specs=wide(),
        scratch_shapes=[
            pltpu.VMEM((Hkv, N, D), jnp.float32),
            pltpu.VMEM((Hkv, N, 1), jnp.float32),
            pltpu.VMEM((Hkv, N, 1), jnp.float32),
            pltpu.VMEM((page * combined, D), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        functools.partial(_quant_kernel, page=page, groups=groups,
                          sliding_window=sliding_window),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ragged_paged_attention_quant",
    )(kv_lens.astype(jnp.int32), pi, cu_q_lens.astype(jnp.int32),
      num_seqs.astype(jnp.int32), qf, pages, scales)
    return from_rows(res).astype(q.dtype)
