"""The three flash kernels as they stood before the causal work list
(``(B, H, nq, nk)`` grid, a dead step for every tile above the diagonal,
every tile on it multiplied whole and masked whole).

Kept beside the tests as the reference of
``test_flash_attention.py::test_row_groups_engage_by_shape``: a call the
row-group rule does not cover must give what these give, to the bit.
Nothing outside the tests imports this file.
"""
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.flash_attention import DEFAULT_MASK_VALUE, _LANE


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


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_sc, m_sc, l_sc, *,
                      sm_scale: float, causal: bool,
                      block_q: int, block_k: int, seq_q: int, seq_k: int):
    del sm_scale  # folded into q by the caller
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    i = pl.program_id(2)
    # bottom-right-aligned causal diagonal (KV-cache decode convention)
    causal_offset = seq_k - seq_q

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[:] = jnp.zeros_like(l_sc)

    # causal: block row i only reaches key blocks starting at or below its
    # shifted diagonal
    run = jnp.logical_or(
        not causal, j * block_k <= (i + 1) * block_q - 1 + causal_offset)
    geom = dict(causal=causal, block_q=block_q, block_k=block_k,
                seq_k=seq_k, causal_offset=causal_offset)

    def _tile(masked: bool):
        # dots stay in the input dtype (bf16 on TPU -> full MXU rate) with
        # fp32 accumulation; only the softmax statistics run in fp32
        q = q_ref[0, 0]                                # [bq, d]
        k = k_ref[0, 0]                                # [bk, d]
        v = v_ref[0, 0]                                # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk] f32
        if masked:
            s = jnp.where(_block_mask(i, j, **geom), s, DEFAULT_MASK_VALUE)
        m_prev = m_sc[:, 0]                            # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:] = (l_sc[:] * alpha[:, None] +
                   jnp.broadcast_to(jnp.sum(p, axis=-1)[:, None],
                                    l_sc.shape))
        acc_sc[:] = acc_sc[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new[:, None], m_sc.shape)

    edge = _block_is_edge(i, j, **geom)

    @pl.when(jnp.logical_and(run, edge))
    def _compute_masked():
        _tile(masked=True)

    @pl.when(jnp.logical_and(run, jnp.logical_not(edge)))
    def _compute_interior():
        _tile(masked=False)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:, 0], 1e-30)             # [bq]
        m = m_sc[:, 0]
        # rows that never saw a valid key: 0 output, +inf lse (bwd p = 0)
        valid = m > DEFAULT_MASK_VALUE * 0.5
        o_ref[0, 0] = jnp.where(valid[:, None], acc_sc[:] / l[:, None],
                                0.0).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(valid, m + jnp.log(l), jnp.inf)[None, :]


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

    grid = (B, H, nq, nk)
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          seq_q=S, seq_k=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // groups, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // groups, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq * block_q, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, nq * block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret, name="flash_fwd",
    )(q, k, v)
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

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_sc, *, sm_scale: float, causal: bool,
                         block_q: int, block_k: int, seq_q: int, seq_k: int):
    # q arrives pre-scaled by sm_scale; the caller rescales dq afterwards
    del sm_scale
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    i = pl.program_id(2)
    causal_offset = seq_k - seq_q

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    run = jnp.logical_or(
        not causal, j * block_k <= (i + 1) * block_q - 1 + causal_offset)
    geom = dict(causal=causal, block_q=block_q, block_k=block_k,
                seq_k=seq_k, causal_offset=causal_offset)

    def _tile(masked: bool):
        q = q_ref[0, 0]                                # [bq, d]
        k = k_ref[0, 0]                                # [bk, d]
        v = v_ref[0, 0]                                # [bk, d]
        do = do_ref[0, 0]                              # [bq, d]
        lse = lse_ref[0, 0, 0]                         # [bq] f32
        delta = delta_ref[0, 0, 0]                     # [bq] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_block_mask(i, j, **geom), s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[:, None])                  # masked/invalid -> 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = (p * (dp - delta[:, None])).astype(k.dtype)
        dq_sc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    edge = _block_is_edge(i, j, **geom)

    @pl.when(jnp.logical_and(run, edge))
    def _compute_masked():
        _tile(masked=True)

    @pl.when(jnp.logical_and(run, jnp.logical_not(edge)))
    def _compute_interior():
        _tile(masked=False)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_sc, dv_sc, *, sm_scale: float,
                          causal: bool, block_q: int, block_k: int,
                          seq_q: int, seq_k: int):
    # q arrives pre-scaled: dk = ds^T @ (q * scale) absorbs the rescale
    del sm_scale
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    j = pl.program_id(2)
    causal_offset = seq_k - seq_q

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    run = jnp.logical_or(
        not causal, j * block_k <= (qi + 1) * block_q - 1 + causal_offset)
    geom = dict(causal=causal, block_q=block_q, block_k=block_k,
                seq_k=seq_k, causal_offset=causal_offset)

    def _tile(masked: bool):
        q = q_ref[0, 0]                                # [bq, d]
        k = k_ref[0, 0]                                # [bk, d]
        v = v_ref[0, 0]                                # [bk, d]
        do = do_ref[0, 0]                              # [bq, d]
        lse = lse_ref[0, 0, 0]                         # [bq] f32
        delta = delta_ref[0, 0, 0]                     # [bq] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_block_mask(qi, j, **geom), s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[:, None])                  # [bq, bk] f32
        dv_sc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk_sc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]

    edge = _block_is_edge(qi, j, **geom)

    @pl.when(jnp.logical_and(run, edge))
    def _compute_masked():
        _tile(masked=True)

    @pl.when(jnp.logical_and(run, jnp.logical_not(edge)))
    def _compute_interior():
        _tile(masked=False)

    @pl.when(qi == nq - 1)
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

    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_q=S, seq_k=Sk)
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, i, j: (b, h // groups, j, 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda b, h, i, j: (b, h, 0, i))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, nq * block_q, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret, name="flash_bwd_dq",
    )(q, k, v, do, lse4, delta4)[0]

    # dkv sweep: k block outer, q block inner (accumulate over q)
    kq_q_spec = pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, j, i: (b, h, i, 0))
    kq_k_spec = pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, j, i: (b, h // groups, j, 0))
    kq_row_spec = pl.BlockSpec((1, 1, 1, block_q),
                               lambda b, h, j, i: (b, h, 0, i))
    kq_out_spec = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, j, i: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(B, H, nk, nq),
        in_specs=[kq_q_spec, kq_k_spec, kq_k_spec, kq_q_spec, kq_row_spec,
                  kq_row_spec],
        out_specs=[kq_out_spec, kq_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nk * block_k, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nk * block_k, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret, name="flash_bwd_dkv",
    )(q, k, v, do, lse4, delta4)
    # dq was computed against the pre-scaled q; undo the fold
    dq = dq * sm_scale
    return dq[:, :, :S], dk[:, :, :Sk], dv[:, :, :Sk]
