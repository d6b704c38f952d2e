"""Flash attention for TPU.

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/`` softmax/attention CUDA kernels powering
``DeepSpeedTransformerLayer``, and the inference flash kernels under
``csrc/transformer/inference/``).  Two implementations behind one API:

- :func:`flash_attention` — a Pallas TPU kernel (online-softmax, blockwise,
  O(S) memory, GQA via head-index mapping).  The grid is ``(B, H, tiles)``
  over the LIVE tiles of the ``num_q_blocks x num_k_blocks`` rectangle (a
  causal call has no step for a tile above the diagonal), the ``(i, j)`` of
  a step read from two scalar-prefetched lists; TPU grids execute
  sequentially per core, so the running max/denominator/accumulator live
  in VMEM scratch across the steps of one q block.  A tile that sits
  square on the causal diagonal is multiplied by row groups, each against
  the keys it reaches and no others (:func:`_diag_rows`).
- :func:`blockwise_attention` — a pure-XLA ``lax.scan`` formulation of the
  same math, used as the CPU fallback and as the memory-efficient custom
  backward (recompute-based, matching the flash-attention-2 backward).

Both return identical values; the custom VJP makes the Pallas forward
differentiable with blockwise-recompute gradients, so the full train step
stays O(S) in activation memory (the reference gets this from its fused
kernels + activation checkpointing).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import routes
from deepspeed_tpu.utils.compat import get_abstract_mesh, shard_map

DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_LANE = 128  # TPU lane width; scratch row-stat buffers are (bq, _LANE)


# ---------------------------------------------------------------------------
# Reference (naive) attention — used by tests and tiny shapes
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  bias: Optional[jax.Array] = None) -> jax.Array:
    """Naive O(S^2)-memory attention. q: [B,H,S,D]; k,v: [B,Hkv,S,D]."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    groups = q.shape[1] // k.shape[1]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        logits = logits + bias
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None, None], logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Blockwise XLA implementation (fallback fwd + custom bwd)
# ---------------------------------------------------------------------------

def _blockwise_fwd(q, k, v, *, sm_scale: float, causal: bool,
                   block_q: int, block_k: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Online-softmax attention via lax.scan. Returns (out, lse).

    q: [B,H,S,D] (f32 compute), k/v already head-expanded to H.
    """
    B, H, S, D = q.shape
    Sk = k.shape[2]
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(Sk, block_k)
    q_pad = nq * block_q - S
    k_pad = nk * block_k - Sk
    qf = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    kf = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    vf = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    qf = qf.reshape(B, H, nq, block_q, D)
    kf = kf.reshape(B, H, nk, block_k, D)
    vf = vf.reshape(B, H, nk, block_k, D)

    k_idx = jnp.arange(nk * block_k).reshape(nk, block_k)
    q_idx = jnp.arange(nq * block_q).reshape(nq, block_q)
    # bottom-right-aligned causal (matches mha_reference tril k=Sk-S): the
    # last query attends to the last key — the KV-cache decode convention
    causal_offset = Sk - S

    def q_block_step(_, qi):
        q_blk, qpos = qi  # [B,H,bq,D], [bq]

        def k_block_step(carry, ki):
            acc, m, l = carry
            k_blk, v_blk, kpos = ki
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk) * sm_scale
            mask = (kpos[None, :] < Sk)
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None] + causal_offset)
            s = jnp.where(mask[None, None], s, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_blk)
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((B, H, block_q, D), jnp.float32),
                jnp.full((B, H, block_q), -jnp.inf, jnp.float32),
                jnp.zeros((B, H, block_q), jnp.float32))
        (acc, m, l), _ = jax.lax.scan(
            k_block_step, init,
            (kf.transpose(2, 0, 1, 3, 4), vf.transpose(2, 0, 1, 3, 4), k_idx))
        # rows with no valid key (causal with Sk < S) never raise m above the
        # mask value: emit 0 output and +inf lse so the backward sees p = 0
        valid = m > DEFAULT_MASK_VALUE * 0.5
        out = jnp.where(valid[..., None],
                        acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
        lse = jnp.where(valid, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
        return None, (out, lse)

    _, (o_blocks, lse_blocks) = jax.lax.scan(
        q_block_step, None, (qf.transpose(2, 0, 1, 3, 4), q_idx))
    out = o_blocks.transpose(1, 2, 0, 3, 4).reshape(B, H, nq * block_q, D)
    lse = lse_blocks.transpose(1, 2, 0, 3).reshape(B, H, nq * block_q)
    return out[:, :, :S], lse[:, :, :S]


def _blockwise_bwd(q, k, v, o, lse, do, *, sm_scale: float, causal: bool,
                   block_q: int, block_k: int):
    """Flash-attention-2 style backward: recompute P blockwise from lse."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    nq = pl.cdiv(S, block_q)
    q_pad = nq * block_q - S

    def pad_q(x, fill=0.0):
        return jnp.pad(x.astype(jnp.float32),
                       ((0, 0), (0, 0), (0, q_pad)) + ((0, 0),) * (x.ndim - 3),
                       constant_values=fill)

    causal_offset = Sk - S
    qf = pad_q(q).reshape(B, H, nq, block_q, D)
    dof = pad_q(do).reshape(B, H, nq, block_q, D)
    # padded rows get lse=+inf → P = exp(-inf) = 0 → no gradient contribution
    lsef = pad_q(lse, fill=jnp.inf).reshape(B, H, nq, block_q)
    deltaf = pad_q(delta).reshape(B, H, nq, block_q)
    q_idx = jnp.arange(nq * block_q).reshape(nq, block_q)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = jnp.arange(Sk)

    def q_block_step(carry, qi):
        dk_acc, dv_acc = carry
        q_blk, do_blk, lse_blk, delta_blk, qpos = qi
        s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, kf) * sm_scale
        mask = jnp.ones((block_q, Sk), dtype=bool)
        if causal:
            mask = k_pos[None, :] <= qpos[:, None] + causal_offset
        s = jnp.where(mask[None, None], s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse_blk[..., None])
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p, do_blk)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_blk, vf)
        ds = p * (dp - delta_blk[..., None]) * sm_scale
        dq_blk = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, q_blk)
        return (dk_acc, dv_acc), dq_blk

    init = (jnp.zeros((B, H, Sk, D), jnp.float32),
            jnp.zeros((B, H, Sk, D), jnp.float32))
    (dk, dv), dq_blocks = jax.lax.scan(
        q_block_step, init,
        (qf.transpose(2, 0, 1, 3, 4), dof.transpose(2, 0, 1, 3, 4),
         lsef.transpose(2, 0, 1, 3), deltaf.transpose(2, 0, 1, 3), q_idx))
    dq = dq_blocks.transpose(1, 2, 0, 3, 4).reshape(B, H, nq * block_q, D)
    return dq[:, :, :S], dk, dv


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------

def _block_mask(i, j, *, causal: bool, block_q: int, block_k: int,
                seq_k: int, causal_offset: int):
    """[bq, bk] bool mask for block (i, j), or None when fully valid.

    ``i``/``j`` are traced program ids, so the mask *computation* is traced —
    but whether a mask is needed at all is decided per-block inside the
    kernel via ``pl.when`` on :func:`_block_is_edge`, keeping the interior
    (the vast majority of blocks) free of VPU mask work.
    """
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k
    if causal:
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(mask, kpos <= qpos + causal_offset)
    return mask


def _block_is_edge(i, j, *, causal: bool, block_q: int, block_k: int,
                   seq_k: int, causal_offset: int):
    """True when block (i, j) needs masking: it crosses the causal diagonal
    or contains padded key columns."""
    edge = (j + 1) * block_k > seq_k  # padded tail columns
    if causal:
        # crosses the shifted diagonal: some (qpos, kpos) in the block has
        # kpos > qpos + offset while the block is not skipped entirely
        edge = jnp.logical_or(
            edge, (j + 1) * block_k - 1 > i * block_q + causal_offset)
    return edge


def _diag_rows(causal: bool, block_q: int, block_k: int, seq_q: int,
               seq_k: int) -> int:
    """Rows of a row group of a diagonal tile, 0 where the tiles that cross
    the diagonal keep the whole-tile masked code.

    A tile crossing the causal diagonal is worked by row groups where it
    sits square on it: equal blocks, the diagonal's offset a block multiple
    and no padded tail, so that the diagonal tiles are the only edge tiles
    of the call.  Row group ``g`` of ``block / sb`` then reaches keys
    ``[0, (g + 1) * sb)`` of its tile: one rectangular product, the mask on
    its last ``sb`` columns alone, nothing multiplied above the diagonal.
    ``sb`` is half the block: three of a tile's four sub-tiles are
    multiplied and two masked.  Quarters multiply 10 of 16 and were slower
    in two kernels of three on v5e (PERF.md section 6, PR 60): the MXU
    streams a group's rows past each 128 x 128 block of keys, and pays
    the block's load for 256 rows as for 1,024.  The static slices of the
    scores and of the row statistics need ``sb`` in whole lane tiles; a
    block whose half is not keeps the whole-tile code too."""
    if not causal or block_q != block_k or seq_q % block_q or \
            seq_k % block_k or seq_k < seq_q or (seq_k - seq_q) % block_k:
        return 0
    sb = block_q // 2
    return 0 if sb % _LANE else sb


def _live_tiles(nq: int, nk: int, *, causal: bool, block_q: int,
                block_k: int, seq_q: int, seq_k: int, interpret: bool
                ) -> np.ndarray:
    """``[steps, 2]`` int32 ``(i, j)`` of the tiles a call visits, q block
    major: every tile of a non-causal call, of a causal one those that
    reach at or under the (shifted) diagonal.  A q block that reaches no
    key keeps its first tile as a step that multiplies nothing, so its
    output is still written.  A causal call records how far its work list
    is the tiles under the diagonal, ``<live tiles>/<grid steps>-diag<sb>``
    (``flash_attention_causal``, one entry a distinct shape)."""
    tiles, live = [], 0
    for i in range(nq):
        last = nk - 1
        if causal:
            last = min(last, ((i + 1) * block_q - 1 + seq_k - seq_q)
                       // block_k)
        live += max(last + 1, 0)
        tiles += [(i, j) for j in range(max(last, 0) + 1)]
    if causal:
        sb = _diag_rows(causal, block_q, block_k, seq_q, seq_k)
        routes.take("flash_attention_causal", every=True,
                    interpret=interpret,
                    pallas=f"{live}/{len(tiles)}-diag{sb}")
    return np.asarray(tiles, np.int32)


def _tile_lists(tiles: np.ndarray, major: int):
    """The scalar-prefetch operands of a sweep over ``tiles`` that
    accumulates along axis ``1 - major``: the q-block and k-block index of
    every step, sorted by ``major`` then the other."""
    order = np.lexsort((tiles[:, 1 - major], tiles[:, major]))
    return jnp.asarray(tiles[order, 0]), jnp.asarray(tiles[order, 1])


def _sweep_ends(major_ref, t, steps: int):
    """(first, last): whether grid step ``t`` opens / closes the run of
    steps that share ``major_ref[t]``, the block whose accumulator the
    kernel holds in scratch."""
    blk = major_ref[t]
    first = jnp.logical_or(t == 0, major_ref[jnp.maximum(t - 1, 0)] != blk)
    last = jnp.logical_or(t == steps - 1,
                          major_ref[jnp.minimum(t + 1, steps - 1)] != blk)
    return first, last


_ALL = slice(None)  # a whole tile's rows or keys


def _lanes(x, n: int):
    """``[rows, n]`` of a row statistic kept ``[rows, _LANE]`` with the
    row's value in every lane."""
    if n <= _LANE:
        return x[:, :n]
    if n % _LANE == 0:
        return jnp.tile(x, (1, n // _LANE))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _whole_tile_mask(i, j, geom):
    return lambda s: jnp.where(_block_mask(i, j, **geom), s,
                               DEFAULT_MASK_VALUE)


def _diag_mask(sb: int):
    """Masks the scores of a row group ``[sb, keys]`` or of a key group
    ``[rows, sb]``: of its sub-tiles only the ``[sb, sb]`` one that sits on
    the diagonal needs it, the group's last columns or its first rows."""
    tri = (jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 1) <=
           jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 0))

    def mask(s):
        rows, keys = s.shape
        on = lambda x: jnp.where(tri, x, DEFAULT_MASK_VALUE)
        if rows == keys:
            return on(s)
        if rows == sb:
            return jnp.concatenate([s[:, :keys - sb], on(s[:, keys - sb:])],
                                   axis=1)
        return jnp.concatenate([on(s[:sb]), s[sb:]], axis=0)
    return mask


def _diag_groups(block: int, sb: int, by_keys: bool):
    """(rows, keys) of a diagonal tile's groups.  Row group ``g`` reaches
    keys ``[0, (g + 1) * sb)`` of the tile and no others; ``by_keys`` is
    the transpose, for a kernel that accumulates a key at a time: key
    group ``c`` is reached by rows ``[c * sb, block)``."""
    if by_keys:
        return [(pl.ds(c * sb, block - c * sb), pl.ds(c * sb, sb))
                for c in range(block // sb)]
    return [(pl.ds(g * sb, sb), pl.ds(0, (g + 1) * sb))
            for g in range(block // sb)]


def _visit_tile(update, i, j, *, causal: bool, block_q: int, block_k: int,
                seq_q: int, seq_k: int, by_keys: bool = False):
    """Work tile ``(i, j)`` of a sweep with ``update(rows, keys, mask)``,
    which multiplies ``rows`` of the q block by ``keys`` of the k block and
    accumulates: an interior tile whole and unmasked, a tile on an edge
    whole under :func:`_block_mask` or, where :func:`_diag_rows` says so,
    by the groups under the diagonal; a tile that reaches no key (a
    placeholder step of :func:`_live_tiles`) not at all."""
    # bottom-right-aligned causal diagonal (KV-cache decode convention)
    causal_offset = seq_k - seq_q
    # causal: block row i only reaches key blocks starting at or below its
    # shifted diagonal
    run = jnp.logical_or(
        not causal, j * block_k <= (i + 1) * block_q - 1 + causal_offset)
    geom = dict(causal=causal, block_q=block_q, block_k=block_k,
                seq_k=seq_k, causal_offset=causal_offset)
    edge = _block_is_edge(i, j, **geom)
    sb = _diag_rows(causal, block_q, block_k, seq_q, seq_k)

    @pl.when(jnp.logical_and(run, edge))
    def _compute_masked():
        if sb:
            mask = _diag_mask(sb)
            for rows, keys in _diag_groups(block_q, sb, by_keys):
                update(rows, keys, mask)
        else:
            update(_ALL, _ALL, _whole_tile_mask(i, j, geom))

    @pl.when(jnp.logical_and(run, jnp.logical_not(edge)))
    def _compute_interior():
        update(_ALL, _ALL)


def _flash_fwd_kernel(ti_ref, tj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_sc, m_sc, l_sc, *,
                      sm_scale: float, causal: bool,
                      block_q: int, block_k: int, seq_q: int, seq_k: int):
    del sm_scale  # folded into q by the caller
    t = pl.program_id(2)
    i, j = ti_ref[t], tj_ref[t]
    first, last = _sweep_ends(ti_ref, t, pl.num_programs(2))

    @pl.when(first)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[:] = jnp.zeros_like(l_sc)

    def _update(rows, keys, mask=None):
        # dots stay in the input dtype (bf16 on TPU -> full MXU rate) with
        # fp32 accumulation; only the softmax statistics run in fp32
        q = q_ref[0, 0, rows, :]                       # [rows, d]
        k = k_ref[0, 0, keys, :]                       # [keys, d]
        v = v_ref[0, 0, keys, :]                       # [keys, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [rows, keys] f32
        if mask is not None:
            s = mask(s)
        # the statistics keep the scratch's form, a row's value in each of
        # its lanes: turning them into a [rows] vector and back cost the
        # forward 6 % at D = 128, S = 4096 on v5e (PERF.md section 6, PR 60)
        m_prev = m_sc[rows, :]                         # [rows, _LANE]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        alpha = jnp.exp(m_prev - m_new)
        l_sc[rows, :] = (l_sc[rows, :] * alpha +
                         jnp.sum(p, axis=-1, keepdims=True))
        acc_sc[rows, :] = acc_sc[rows, :] * _lanes(alpha, v.shape[1]) + \
            jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_sc[rows, :] = m_new

    _visit_tile(_update, i, j, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k)

    @pl.when(last)
    def _finalize():
        l = jnp.maximum(l_sc[:], 1e-30)                # [bq, _LANE]
        m = m_sc[:]
        # rows that never saw a valid key: 0 output, +inf lse (bwd p = 0)
        valid = m > DEFAULT_MASK_VALUE * 0.5
        d = acc_sc.shape[1]
        o_ref[0, 0] = jnp.where(_lanes(valid, d), acc_sc[:] / _lanes(l, d),
                                0.0).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(valid, m + jnp.log(l),
                                  jnp.inf)[:, 0][None, :]


def _flash_fwd_pallas(q, k, v, *, sm_scale: float, causal: bool,
                      block_q: int, block_k: int,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """q: [B,H,S,D]; k,v: [B,Hkv,Sk,D] (GQA: Hkv divides H)."""
    B, H, S, D = q.shape
    _, Hkv, Sk, _ = k.shape
    groups = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(Sk, block_k)
    q_pad = nq * block_q - S
    k_pad = nk * block_k - Sk
    # scale folded into q host-side: one mul per q element instead of one
    # per score element inside the kernel
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))

    common = dict(causal=causal, block_q=block_q, block_k=block_k,
                  seq_q=S, seq_k=Sk)
    tiles = _live_tiles(nq, nk, interpret=interpret, **common)
    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, t, ti, tj: (b, h, ti[t], 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, t, ti, tj: (b, h // groups, tj[t], 0))
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, sm_scale=sm_scale, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, len(tiles)),
            in_specs=[q_spec, k_spec, k_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, h, t, ti, tj: (b, h, 0, ti[t])),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq * block_q, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, nq * block_q), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="flash_fwd",
    )(*_tile_lists(tiles, major=0), q, k, v)
    return out[:, :, :S], lse[:, :, 0, :S]


# ---------------------------------------------------------------------------
# Pallas TPU backward kernels (flash-attention-2 dq / dk,dv)
# ---------------------------------------------------------------------------
#
# TPU-native equivalent of the reference's fused attention backward CUDA
# kernels (csrc/transformer/). Two kernels with opposite loop orders:
# - dq: for each q block, accumulate ds @ K over k blocks (same sweep as fwd)
# - dk/dv: for each k block, accumulate ds^T @ Q and P^T @ dO over q blocks
# P is recomputed blockwise from the saved logsumexp — O(S) memory, and every
# dot hits the MXU in fp32 accumulation.

def _flash_bwd_dq_kernel(ti_ref, tj_ref, q_ref, k_ref, v_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, dq_sc, *,
                         sm_scale: float, causal: bool,
                         block_q: int, block_k: int, seq_q: int, seq_k: int):
    # q arrives pre-scaled by sm_scale; the caller rescales dq afterwards
    del sm_scale
    t = pl.program_id(2)
    i, j = ti_ref[t], tj_ref[t]
    first, last = _sweep_ends(ti_ref, t, pl.num_programs(2))

    @pl.when(first)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _update(rows, keys, mask=None):
        q = q_ref[0, 0, rows, :]                       # [rows, d]
        k = k_ref[0, 0, keys, :]                       # [keys, d]
        v = v_ref[0, 0, keys, :]                       # [keys, d]
        do = do_ref[0, 0, rows, :]                     # [rows, d]
        lse = lse_ref[0, 0, 0, rows]                   # [rows] f32
        delta = delta_ref[0, 0, 0, rows]               # [rows] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask is not None:
            s = mask(s)
        p = jnp.exp(s - lse[:, None])                  # masked/invalid -> 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [rows, keys]
        ds = (p * (dp - delta[:, None])).astype(k.dtype)
        dq_sc[rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _visit_tile(_update, i, j, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k)

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(ti_ref, tj_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                          *, sm_scale: float, causal: bool, block_q: int,
                          block_k: int, seq_q: int, seq_k: int):
    # q arrives pre-scaled: dk = ds^T @ (q * scale) absorbs the rescale
    del sm_scale
    t = pl.program_id(2)
    qi, j = ti_ref[t], tj_ref[t]
    first, last = _sweep_ends(tj_ref, t, pl.num_programs(2))

    @pl.when(first)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _update(rows, keys, mask=None):
        q = q_ref[0, 0, rows, :]                       # [rows, d]
        k = k_ref[0, 0, keys, :]                       # [keys, d]
        v = v_ref[0, 0, keys, :]                       # [keys, d]
        do = do_ref[0, 0, rows, :]                     # [rows, d]
        lse = lse_ref[0, 0, 0, rows]                   # [rows] f32
        delta = delta_ref[0, 0, 0, rows]               # [rows] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask is not None:
            s = mask(s)
        p = jnp.exp(s - lse[:, None])                  # [rows, keys] f32
        dv_sc[keys, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [keys, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [rows, keys]
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk_sc[keys, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [keys, d]

    _visit_tile(_update, qi, j, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k, by_keys=True)

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, *, sm_scale: float, causal: bool,
                      block_q: int, block_k: int, interpret: bool = False):
    """Returns (dq, dk, dv) with dk/dv per *q*-head ([B, H, Sk, D]); the
    caller sums GQA groups back onto the shared kv head."""
    B, H, S, D = q.shape
    _, Hkv, Sk, _ = k.shape
    groups = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(Sk, block_k)
    q_pad = nq * block_q - S
    k_pad = nk * block_k - Sk

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # q pre-scaled to match the forward's logits; dq is rescaled at the end
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
        # padded rows: lse=+inf -> p=0 -> no contribution to dk/dv
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, q_pad)),
                      constant_values=jnp.inf)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, q_pad)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    lse4 = lse[:, :, None, :]                          # [B,H,1,Sq_pad]
    delta4 = delta[:, :, None, :]

    common = dict(causal=causal, block_q=block_q, block_k=block_k,
                  seq_q=S, seq_k=Sk)
    tiles = _live_tiles(nq, nk, interpret=interpret, **common)
    common.update(sm_scale=sm_scale)
    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, t, ti, tj: (b, h, ti[t], 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, t, ti, tj: (b, h // groups, tj[t], 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda b, h, t, ti, tj: (b, h, 0, ti[t]))
    in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    # dq sweep: q block outer, k block inner (accumulate over k)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, len(tiles)),
            in_specs=in_specs, out_specs=[q_spec],
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, nq * block_q, D), jnp.float32)],
        compiler_params=params, interpret=interpret, name="flash_bwd_dq",
    )(*_tile_lists(tiles, major=0), q, k, v, do, lse4, delta4)[0]

    # dkv sweep: k block outer, q block inner (accumulate over q)
    kv_out_spec = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, t, ti, tj: (b, h, tj[t], 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, len(tiles)),
            in_specs=in_specs, out_specs=[kv_out_spec, kv_out_spec],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nk * block_k, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nk * block_k, D), jnp.float32),
        ],
        compiler_params=params, interpret=interpret, name="flash_bwd_dkv",
    )(*_tile_lists(tiles, major=1), q, k, v, do, lse4, delta4)
    # dq was computed against the pre-scaled q; undo the fold
    dq = dq * sm_scale
    return dq[:, :, :S], dk[:, :, :Sk], dv[:, :, :Sk]


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

def _expand_kv(q, k, v):
    groups = q.shape[1] // k.shape[1]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    return k, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd_dispatch(q, k, v, sm_scale, causal, block_q, block_k,
                                 interpret)
    return out


def _flash_fwd_dispatch(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret):
    if routes.take("flash_attention_fwd", interpret=interpret) != routes.XLA:
        return _flash_fwd_pallas(q, k, v, sm_scale=sm_scale, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    ke, ve = _expand_kv(q, k, v)
    out, lse = _blockwise_fwd(q, ke, ve, sm_scale=sm_scale, causal=causal,
                              block_q=block_q, block_k=block_k)
    return out.astype(q.dtype), lse


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_dispatch(q, k, v, sm_scale, causal, block_q,
                                   block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    n_kv = k.shape[1]
    groups = q.shape[1] // n_kv
    if routes.take("flash_attention_bwd", interpret=interpret) != routes.XLA:
        dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, do,
                                       sm_scale=sm_scale, causal=causal,
                                       block_q=block_q, block_k=block_k,
                                       interpret=interpret)
    else:
        ke, ve = _expand_kv(q, k, v)
        dq, dk, dv = _blockwise_bwd(q, ke, ve, out, lse, do,
                                    sm_scale=sm_scale, causal=causal,
                                    block_q=block_q, block_k=block_k)
    if groups > 1:  # sum GQA group gradients back to the shared kv head
        B, H, Sk, D = dk.shape
        dk = dk.reshape(B, n_kv, groups, Sk, D).sum(axis=2)
        dv = dv.reshape(B, n_kv, groups, Sk, D).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: bool = False) -> jax.Array:
    """Flash attention.  q: [B, H, S, D]; k, v: [B, Hkv, Sk, D] where Hkv
    divides H (grouped-query attention).  Returns [B, H, S, D] in q.dtype.

    Pallas kernel on TPU; blockwise-XLA everywhere else; O(S)-memory custom
    backward in both cases.  ``interpret=True`` forces the Pallas kernel in
    interpreter mode (CPU testing).  Blocks clamp to the sequence length;
    the 1024 default measured fastest at the 2k-seq bench shape on v5e
    (fwd+bwd 1.57 ms vs 1.72 at 512, D=64 GQA) and again at D=128 GQA,
    S=4096 causal, 2 x 32 heads (a chip's share of ``pretrain-zero3``;
    PR 60: fwd 2.47 + bwd 6.18 ms vs 2.66 + 7.54 at 512) — VMEM
    comfortably holds [1024, D] tiles for the head dims in use.
    """
    assert q.shape[1] % k.shape[1] == 0, (
        f"q heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}")
    assert k.shape == v.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    # clamp here so EVERY backend sees it: the blockwise-XLA fallback pads
    # S up to a block multiple, so an unclamped default would compute (and
    # mask away) up to block_q/S times the work on short sequences
    block_q = min(int(block_q), q.shape[2])
    block_k = min(int(block_k), k.shape[2])
    attend = functools.partial(
        _flash, sm_scale=float(sm_scale), causal=bool(causal),
        block_q=block_q, block_k=block_k, interpret=bool(interpret))
    if interpret or routes.on_tpu():
        sharded = _mesh_partition(q, k)
        if sharded is not None:
            mesh, spec = sharded
            attend = shard_map(attend, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec, check_vma=False)
    return attend(q, k, v)


def _mesh_partition(q, k):
    """``(mesh, spec)`` to run the Pallas kernel manually partitioned
    under the installed multi-device mesh, ``None`` on one device or
    inside a region that is already manual (Ulysses, ring).

    GSPMD cannot split a Mosaic kernel (the TPU compiler: "Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"), and attention is independent per sample and per head:
    batch goes over the data axes, heads over ``tensor``, each only where
    it divides.  A dim that does not divide stays whole, every shard then
    computing it."""
    import deepspeed_tpu.comm as dist

    topo = dist.peek_topology()
    if topo is None or topo.mesh.size == 1 or \
            get_abstract_mesh().manual_axes:
        return None
    sizes = topo.mesh.shape
    batch = tuple(a for a in ("data", "data_sub", "expert") if sizes[a] > 1)
    if q.shape[0] % int(np.prod([sizes[a] for a in batch])):
        batch = ()
    tp = sizes["tensor"]
    heads = ("tensor" if tp > 1 and q.shape[1] % tp == 0
             and k.shape[1] % tp == 0 else None)
    return topo.mesh, P(batch or None, heads, None, None)
