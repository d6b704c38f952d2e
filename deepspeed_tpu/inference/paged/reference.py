"""XLA-compilable attention over a per-head K/V pool, mask-based: the route
every backend without the Pallas kernels takes (CPU tests) and the twin the
kernels' tests compare against."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# same mask-value family as ops/flash_attention.py and the Pallas
# quantized kernel: vanishes under softmax, (mask - mask) stays exact 0
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def ref_paged_attention(q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
                        page_indices: jax.Array, cu_q_lens: jax.Array,
                        num_seqs: jax.Array, *, sm_scale: float,
                        sliding_window=None) -> jax.Array:
    """Same math as the kernel's ``ref_ragged_paged_attention`` but with
    static control flow (where-masks over the flat page buffer), so it
    jits on any backend.  ``page_indices`` may pad unused entries with -1
    (never matches a real page — including interior holes: the columns
    a window pool has given back drop out while the surviving columns
    keep their true positions).  O(T * P * page_size) — test scale.
    """
    T, H, D = q.shape
    P, page, combined, _ = pages.shape
    Hkv = combined // 2
    S, pp = page_indices.shape
    k_flat = pages[:, :, 0::2, :].reshape(P * page, Hkv, D)
    v_flat = pages[:, :, 1::2, :].reshape(P * page, Hkv, D)

    page_of_r = jnp.arange(P * page, dtype=jnp.int32) // page     # [R]
    pos_in_page = jnp.arange(P * page, dtype=jnp.int32) % page

    # token -> sequence (padding tokens map past num_seqs and mask out)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    seq_of_t = jnp.sum((t_idx[:, None] >= cu_q_lens[None, 1:]).astype(
        jnp.int32), axis=1)                                       # [T]
    token_valid = t_idx < cu_q_lens[num_seqs[0]]
    seq_of_t = jnp.minimum(seq_of_t, S - 1)

    # per (seq, flat row): does the row belong to the seq, at which pos
    match = page_indices[:, :, None] == page_of_r[None, None, :]  # [S,pp,R]
    owned = jnp.any(match, axis=1)                                # [S, R]
    kvpos = (jnp.sum(jnp.where(
        match, jnp.arange(pp, dtype=jnp.int32)[None, :, None], 0),
        axis=1) * page + pos_in_page[None, :])                    # [S, R]

    q_len = cu_q_lens[1:] - cu_q_lens[:-1]                        # [S]
    # absolute position of token t within its sequence
    q_pos = (jnp.take(kv_lens - q_len, seq_of_t) +
             (t_idx - jnp.take(cu_q_lens[:-1], seq_of_t)))        # [T]

    mask = (jnp.take(owned, seq_of_t, axis=0) &
            (jnp.take(kvpos, seq_of_t, axis=0) <= q_pos[:, None]) &
            token_valid[:, None])                                 # [T, R]
    if sliding_window is not None:
        mask = mask & (jnp.take(kvpos, seq_of_t, axis=0) >
                       q_pos[:, None] - sliding_window)

    groups = H // Hkv
    k_r = jnp.repeat(k_flat, groups, axis=1)
    v_r = jnp.repeat(v_flat, groups, axis=1)
    att = jnp.einsum("thd,rhd->htr", q.astype(jnp.float32),
                     k_r.astype(jnp.float32)) * sm_scale
    att = jnp.where(mask[None], att, jnp.float32(_MASK_VALUE))
    p = jax.nn.softmax(att, axis=-1)
    y = jnp.einsum("htr,rhd->thd", p, v_r.astype(jnp.float32))
    return jnp.where(token_valid[:, None, None], y, 0.0).astype(q.dtype)


def ref_paged_attention_quant(q: jax.Array, pages: jax.Array,
                              scales: jax.Array, kv_lens: jax.Array,
                              page_indices: jax.Array, cu_q_lens: jax.Array,
                              num_seqs: jax.Array, *, sm_scale: float,
                              sliding_window=None) -> jax.Array:
    """Dequant-free XLA read path for a QUANTIZED page pool: gather each
    sequence's attended pages (still 1-byte) through ``page_indices``,
    dequantize ONLY the gathered operand, then masked attention.  The
    dequantized intermediate is ``[S, pp*page, ...]`` — bounded by the
    pages sequences actually attend, never the ``[P, ...]`` pool
    (``test_paged_quant.py`` pins that on the traced jaxpr).  Rows
    gathered in page-table order sit at their kv position directly, so
    masking is ``row < kv_len`` + causal bound + per-column validity
    (a ``-1`` page-table entry — padding or an interior hole — gathers
    the trash page, so its rows must mask out even when they sit below
    ``kv_len``).

    q: ``[T, H, D]``; pages: ``[P, page, 2*Hkv, D]`` int8/fp8_e4m3;
    scales: ``[P, page, 2*Hkv]`` fp32.  O(T * pp * page_size) — the
    same test-scale contract as :func:`ref_paged_attention`, but over
    per-sequence attended rows instead of the whole pool.
    """
    T, H, D = q.shape
    P, page, combined, _ = pages.shape
    Hkv = combined // 2
    S, pp = page_indices.shape
    R = pp * page                          # attended rows per sequence

    safe = jnp.maximum(page_indices, 0).reshape(-1)       # [S*pp]
    g_pages = jnp.take(pages, safe, axis=0)               # quantized
    g_scales = jnp.take(scales, safe, axis=0)
    kv = (g_pages.astype(jnp.float32) *
          g_scales[..., None]).reshape(S, R, combined, D)
    k_g = kv[:, :, 0::2, :]                               # [S, R, Hkv, D]
    v_g = kv[:, :, 1::2, :]

    t_idx = jnp.arange(T, dtype=jnp.int32)
    seq_of_t = jnp.sum((t_idx[:, None] >= cu_q_lens[None, 1:]).astype(
        jnp.int32), axis=1)                               # [T]
    token_valid = t_idx < cu_q_lens[num_seqs[0]]
    seq_of_t = jnp.minimum(seq_of_t, S - 1)

    q_len = cu_q_lens[1:] - cu_q_lens[:-1]                # [S]
    q_pos = (jnp.take(kv_lens - q_len, seq_of_t) +
             (t_idx - jnp.take(cu_q_lens[:-1], seq_of_t)))  # [T]
    r_idx = jnp.arange(R, dtype=jnp.int32)
    kv_len_t = jnp.take(kv_lens, seq_of_t)                # [T]
    # column validity: -1 entries (padding OR interior holes)
    # gathered the trash page above — their rows never attend
    col_valid = jnp.repeat(page_indices >= 0, page, axis=1)  # [S, R]
    mask = ((r_idx[None, :] <= q_pos[:, None]) &
            (r_idx[None, :] < kv_len_t[:, None]) &
            jnp.take(col_valid, seq_of_t, axis=0) &
            token_valid[:, None])                         # [T, R]
    if sliding_window is not None:
        mask = mask & (r_idx[None, :] > q_pos[:, None] - sliding_window)

    groups = H // Hkv
    k_t = jnp.repeat(jnp.take(k_g, seq_of_t, axis=0), groups, axis=2)
    v_t = jnp.repeat(jnp.take(v_g, seq_of_t, axis=0), groups, axis=2)
    att = jnp.einsum("thd,trhd->htr", q.astype(jnp.float32),
                     k_t) * sm_scale
    att = jnp.where(mask[None], att, jnp.float32(_MASK_VALUE))
    p = jax.nn.softmax(att, axis=-1)
    y = jnp.einsum("htr,trhd->thd", p, v_t)
    return jnp.where(token_valid[:, None, None], y, 0.0).astype(q.dtype)
