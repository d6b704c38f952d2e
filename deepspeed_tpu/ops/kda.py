"""Kimi Delta Attention: the delta rule over a matrix state whose decay is a
VECTOR a key channel (Pallas TPU kernels).

A KDA layer of ``models/solar_open2.py`` (Kimi Linear, arXiv:2510.26692)
keeps, for each head, a ``dk x dv`` float32 state as a gated delta rule does
(``ops/gated_delta_rule.py``), but every ROW of it (a key channel) decays by
a factor of its own: with ``a_t = exp(g_t)`` in ``(0, 1)^dk`` a head and
token and ``b_t`` in (0, 2)::

    S' = diag(a_t) S_(t-1)      d_t = b_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T        o_t = S_t^T q_t

i.e. ``S_t = (I - b_t k_t k_t^T) diag(a_t) S_(t-1) + b_t k_t v_t^T``.  Two
forms serve it (``paged/kda_state.py``):

- :func:`chunk_scan` (kernel ``kda_chunk_scan``): the prompt chunks of a
  fused tick, ``C`` = :data:`CHUNK` tokens at a time.  With ``G_i =
  sum_(j<=i) g_j`` in ``R^dk`` (falling, at most 0) and ``S_0`` the state
  before the chunk::

      P_ij = sum_c k_ic k_jc exp(G_ic - G_jc)     R_ij likewise with q_i
      A   = tril(diag(b) P, -1)        T = (I + A)^-1
      U   = T diag(b) (V - (K * exp G) S_0)
      O   = (Q * exp G) S_0 + tril(R) U
      S_C = diag(exp G_C) S_0 + (K * exp(G_C - G_i))^T U

  ``T`` by doubling, by the one function that builds it for both rules
  (``ops/gated_delta_rule.py:solve``) and for its reason.  **``P`` and ``R``
  are no product masked by a scalar**: the
  exponent sits inside the contraction.  They are matrix products again once
  a reference row ``r`` with ``j <= r <= i`` splits it, ``exp(G_i - G_r)
  exp(G_r - G_j)``, both factors at most 1.  The kernel takes one ``r`` a
  LEVEL: at level ``s`` (1, 2, 4 .. C/2) the pairs whose highest differing
  bit is ``s`` (``i`` has it, ``j`` has not, the bits above agree) take
  ``r = (i // s) s``, the first row of ``i``'s block of ``s``, so ``P = sum_s
  mask_s ((K * E_row_s) (K * E_col_s)^T)`` with ``E_row_s = exp(G_i - G_r)``
  on the rows that have the bit (else 0) and ``E_col_s = exp(G_r - G_j)`` on
  those that have not: ``log2 C`` products (K and Q stacked) on the MXU,
  every pair ``i > j`` in exactly one of them, and ``G_r`` by one sublane
  roll a level from the level before (``X_s[i] = G[(i // s) s]`` is
  ``X_(s/2)`` with bit ``s/2`` cleared; the column's reference is ``X_s``
  rolled up by ``s``, which is the reference of the row ``s`` below).  Below
  ``s = 8`` a level is a whole ``[2C, dk] x [dk, C]`` product whose operands
  are half zeros.  From there (``gated_delta_rule.levels``: 4 of the 7
  levels at ``C`` = 128) the rows that have the bit are whole groups of
  sublanes, and the level takes ``E_row``, ``K * E_row`` and ``Q * E_row``
  on those ``C / 2`` rows alone, stacked ``[C, dk]``, ``E_col`` and ``K *
  E_col`` on the ``C / 2`` that have it not (zeros between them: the
  product's other side stays ``C`` wide, the MXU's time follows the rows
  streamed and not the columns, PERF.md PR 57), and adds the product to
  the same rows of ``P`` and ``R``: half the rows streamed, half the
  ``exp`` and the multiplies, the same numbers to the bit.  Every exponent
  the kernel takes is of a
  non-positive number (none is ever ``exp(-G)``), so a channel whose decay
  underflows inside a chunk gives zeros, not ``inf * 0``.  ``R``'s diagonal
  is ``q_i . k_i``.  The running sum ``G`` is a product with a triangle of
  ones inside the kernel: ``g`` arrives as its projection leaves it.
- :func:`decode_step` (kernel ``kda_decode_step``): one token a row, the
  state updated where it lies, on the vector unit alone, as
  ``gated_delta_rule.decode_step``; ``a`` comes as a column beside ``k`` and
  ``q`` (``[dk, heads]``) and scales the state's rows.  ``2 x dk x dv x 4``
  bytes a head a row: bandwidth-bound.

**Layouts** are ``ops/gated_delta_rule.py``'s: the state ``[slots, dk, heads
* dv]`` (heads along the lanes; at the published 128 x 128 both dimensions
are whole lane tiles), a tile's rows as the projections leave them, ``q, k,
g [NT * C, H * dk]``, ``v, o [NT * C, H * dv]``, a head a lane block, ``b [NT
* C, H]``; tiles by ``linear_attention.tile_layout`` (``LOAD`` / ``ZERO`` /
carry); a tile row at or past the tile's ``len`` holds no token and the
kernel zeroes its q, k, v, g and b whatever the tile holds there.

Products that touch the state, the level products, the running sum and the
solve are float32 at ``Precision.HIGHEST``.  Each kernel has a ``jax.numpy``
twin taken on the CPU; which ran is recorded under :data:`KERNEL_SCAN` /
:data:`KERNEL_STEP` (``ops/routes.py``).  :func:`recurrence` is the
definition, token by token, for the tests.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes
from deepspeed_tpu.ops.gated_delta_rule import (_dot, _heads_along_lanes,
                                                _heads_first, levels,
                                                rows_plus, rows_spread,
                                                rows_with, solve)
from deepspeed_tpu.ops.linear_attention import LOAD, ZERO, zeros_past

# the route keys, and the operations' names in a program and a device trace
KERNEL = "kda"
KERNEL_SCAN = KERNEL + "_scan"
KERNEL_STEP = KERNEL + "_decode"
SCAN_NAME = "kda_chunk_scan"
STEP_NAME = "kda_decode_step"

_LANES = 128
# tokens a tile of the scan holds (a power of two: the levels halve it)
CHUNK = 128
# heads a grid step holds: of the scan (four chains of products to overlap;
# a tick of 1,024 + 8 rows of 64 heads took 3.21 ms at two, 3.08 at four and
# 3.04 at eight on the v5e: PERF.md, PR 56), of the decode step (512 KiB of
# state in, the same out, double-buffered; sixteen ran 8 % slower)
_SCAN_HEADS = 4
_STEP_HEADS = 8


# -- the definition and the twins --------------------------------------------

def recurrence(q, k, v, g, b, state):
    """Token by token, one sequence: ``q, k, g [n, H, dk]`` (``g`` float32:
    the log of a token's decay a key channel), ``v [n, H, dv]``, ``b [n,
    H]``, ``state [H, dk, dv]`` float32 -> ``(o [n, H, dv] float32,
    state)``."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = (a.astype(jnp.float32) for a in x)
        s = jnp.exp(g_t)[:, :, None] * s
        d = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", s, k_t, precision=jax.lax.Precision.HIGHEST))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t,
                             precision=jax.lax.Precision.HIGHEST)

    state, o = jax.lax.scan(step, state.astype(jnp.float32), (q, k, v, g, b))
    return o, state


_NT = (((1,), (1,)), ((), ()))          # a b^T
_TN = (((0,), (0,)), ((), ()))          # a^T b


def _chunk_math(q, k, v, g, b_col, s_in, n, roll=jnp.roll):
    """One tile of one head: ``q, k [C, dk]``, ``v [C, dv]``, ``g [C, dk]``
    float32 (a token's log decay), ``b_col [C, 1]`` float32, ``s_in [dk,
    dv]`` float32, ``n`` live rows -> ``(o [C, dv], s_out)``.  Shared by the
    kernel body (``roll``: the sublane rotate) and the twin."""
    C, dk = q.shape
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    # row and column of the [C / 2, C] block a halved level forms
    half_i = jax.lax.broadcasted_iota(jnp.int32, (C // 2, C), 0)
    half_j = jax.lax.broadcasted_iota(jnp.int32, (C // 2, C), 1)
    # a row at or past ``n`` holds no token, whatever the tile holds there
    live = row < n
    qf, kf, vf = (jnp.where(live, x, jnp.zeros_like(x)).astype(jnp.float32)
                  for x in (q, k, v))
    g = jnp.where(live, g, 0.0)
    b_col = jnp.where(live, b_col, 0.0)
    big = _dot(jnp.where(i >= j, 1.0, 0.0), g)                # G [C, dk]
    # P and R level by level: the pairs (i, j) whose highest differing bit
    # is s, by the reference row (i // s) s of i's block of s rows
    p = jnp.zeros((C, C), jnp.float32)
    r = jnp.where(i == j, jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
    ref = big                           # ref[i] = G[(i // s) s]
    for s, half in levels(C, 1):
        bit = s.bit_length() - 1
        if s > 1:
            ref = jnp.where((row & (s // 2)) != 0, roll(ref, s // 2, 0), ref)
        # a column's reference is the first row of the NEXT block of s
        if half:
            # the rows that have the bit against those that have not, where
            # that reference is the reference of the row s below
            ref_up = rows_with(ref, s)
            e_row = jnp.exp(jnp.minimum(rows_with(big, s) - ref_up, 0.0))
            e_col = jnp.exp(jnp.minimum(
                ref_up - rows_with(big, s, False), 0.0))
            lower = rows_with(kf, s, False) * e_col           # [C/2, dk]
            m = _dot(jnp.concatenate([rows_with(kf, s) * e_row,
                                      rows_with(qf, s) * e_row]),
                     rows_spread(lower, s), _NT)              # [C, C]
            # the u-th row that has the bit lies in block u // s of 2 s rows
            same = (half_i >> bit) == (half_j >> (bit + 1))
            p = rows_plus(p, jnp.where(same, m[:C // 2], 0.0), s)
            r = rows_plus(r, jnp.where(same, m[C // 2:], 0.0), s)
        else:
            upper = (row & s) != 0
            e_row = jnp.where(upper, jnp.exp(jnp.minimum(big - ref, 0.0)),
                              0.0)
            e_col = jnp.where(upper, 0.0, jnp.exp(jnp.minimum(
                roll(ref, C - s, 0) - big, 0.0)))
            m = _dot(jnp.concatenate([kf * e_row, qf * e_row]),
                     kf * e_col, _NT)                         # [2C, C]
            same = ((i ^ j) >> (bit + 1)) == 0
            p = p + jnp.where(same, m[:C], 0.0)
            r = r + jnp.where(same, m[C:], 0.0)
    t = solve(b_col * p)                # of a strictly lower triangle
    e = jnp.exp(big)
    u = _dot(t, b_col * (vf - _dot(kf * e, s_in)))            # [C, dv]
    o = _dot(qf * e, s_in) + _dot(r, u)
    g_last = big[C - 1:C, :]            # G_C [1, dk]: g is 0 past n
    # a row of lanes as a column of sublanes, without a transpose
    ii = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    e_last = jnp.sum(jnp.where(ii == jj, jnp.exp(g_last), 0.0), axis=1,
                     keepdims=True)                           # [dk, 1]
    s_out = e_last * s_in + _dot(kf * jnp.exp(g_last - big), u, _TN)
    return o, s_out


def scan_twin(q, k, v, g, b, state, slot, init, length, *,
              chunk: int = CHUNK):
    """The XLA twin of :func:`chunk_scan`, tile by tile."""
    rows, H = b.shape
    NT = rows // chunk
    # [NT * C, H * d] -> [NT, H, C, d]
    tiles = lambda a: a.reshape(NT, chunk, H, -1).transpose(0, 2, 1, 3)
    every_head = jax.vmap(_chunk_math, in_axes=(0, 0, 0, 0, 0, 0, None))
    state = _heads_first(state, H)

    def step(carry, x):
        state, run = carry
        q_n, k_n, v_n, g_n, b_n, slot_n, init_n, len_n = x
        s_in = jnp.where(init_n == LOAD, state[slot_n],
                         jnp.where(init_n == ZERO, 0.0, run))
        o, s_out = every_head(q_n, k_n, v_n, g_n, b_n, s_in, len_n)
        live = jnp.logical_or(len_n > 0, init_n == LOAD)
        s_out = jnp.where(len_n > 0, s_out, s_in)
        state = state.at[jnp.where(live, slot_n, state.shape[0])].set(
            s_out, mode="drop")
        return (state, s_out), o

    f32 = lambda a: a.astype(jnp.float32)
    (state, _), o = jax.lax.scan(
        step, (state, jnp.zeros_like(state[0])),
        (tiles(q), tiles(k), tiles(v), tiles(f32(g)), tiles(f32(b)),
         slot, init, length))
    o = o.transpose(0, 2, 1, 3).reshape(rows, -1).astype(v.dtype)
    return o, _heads_along_lanes(state)


def decode_step_twin(q, k, v, g, b, state, slot, fresh, n):
    """The XLA twin of :func:`decode_step`."""
    S = state.shape[0]
    H = q.shape[1]
    live = jnp.arange(q.shape[0]) < n[0]
    qf, kf, vf, gf, bf = (a.astype(jnp.float32) for a in (q, k, v, g, b))
    st = _heads_first(state, H)
    s_in = jnp.where(fresh[:, None, None, None], 0.0,
                     jnp.take(st, slot, axis=0))
    s = jnp.exp(gf)[..., None] * s_in
    d = bf[..., None] * (vf - jnp.sum(kf[..., :, None] * s, axis=-2))
    new = s + kf[..., :, None] * d[..., None, :]
    o = jnp.sum(qf[..., :, None] * new, axis=-2)
    st = st.at[jnp.where(live, slot, S)].set(new, mode="drop")
    return (jnp.where(live[:, None, None], o, 0.0).astype(v.dtype),
            _heads_along_lanes(st))


# -- the kernels --------------------------------------------------------------

def _scan_kernel(slot_ref, init_ref, len_ref,                # scalar prefetch
                 q_ref, k_ref, v_ref, g_ref, b_ref, s_in_ref, o_ref,
                 s_out_ref):
    # q, k, g: [C, hb * dk]; v, o: [C, hb * dv]; b: [1, C, hb]; s_in, s_out:
    # [1, dk, hb * dv].  A head is a lane slice of each.  The output state
    # block is the running state: its index (slot, head block) is the same
    # for every tile of a sequence
    n = pl.program_id(1)
    init, length = init_ref[n], len_ref[n]
    hb, dk = b_ref.shape[2], s_out_ref.shape[1]
    dv = v_ref.shape[1] // hb

    @pl.when(init == LOAD)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(init == ZERO)
    def _():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)

    @pl.when(length > 0)
    def _():
        for h in range(hb):
            of_qk = slice(h * dk, (h + 1) * dk)
            lanes = slice(h * dv, (h + 1) * dv)
            o, s_out = _chunk_math(
                q_ref[:, of_qk], k_ref[:, of_qk], v_ref[:, lanes],
                g_ref[:, of_qk], b_ref[0, :, h:h + 1],
                s_out_ref[0, :, lanes], length, roll=pltpu.roll)
            o_ref[:, lanes] = o.astype(o_ref.dtype)
            s_out_ref[0, :, lanes] = s_out

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def scan_heads(heads: int, dk: int, dv: int) -> int:
    """Heads a grid step of the scan holds: :data:`_SCAN_HEADS` where they
    divide the heads and fill whole lane tiles, all the heads where those
    are fewer, else 0."""
    hb = min(_SCAN_HEADS, heads)
    whole = (hb * dk) % _LANES == 0 and (hb * dv) % _LANES == 0
    return hb if heads % hb == 0 and (whole or hb == heads) else 0


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   donate_argnums=(5,))
def _scan(q, k, v, g, b, state, slot, init, length, *, chunk: int,
          interpret: bool):
    rows, H = b.shape
    C = chunk
    dk, dv = q.shape[1] // H, v.shape[1] // H
    hb = scan_heads(H, dk, dv)
    qk = pl.BlockSpec((C, hb * dk), lambda h, n, *_: (n, h))
    vo = pl.BlockSpec((C, hb * dv), lambda h, n, *_: (n, h))
    block = pl.BlockSpec((1, dk, hb * dv),
                         lambda h, n, slot, *_: (slot[n], 0, h))
    # [rows, H] -> [H / hb, rows, hb]: a step's heads a lane each, so that
    # a head's b is a column of its block
    b = b.astype(jnp.float32).T.reshape(H // hb, hb, rows).transpose(0, 2, 1)
    return pl.pallas_call(
        _scan_kernel,
        out_shape=(jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[qk, qk, vo, qk,
                      pl.BlockSpec((1, C, hb), lambda h, n, *_: (h, n, 0)),
                      block],
            out_specs=(vo, block),
            grid=(H // hb, slot.shape[0])),
        # operands are counted with the scalar-prefetch arrays: the state
        # is the ninth, and becomes the second result
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=SCAN_NAME,
    )(slot, init, length, q, k, v, g.astype(jnp.float32), b, state)


def scan_eligible(heads: int, dk: int, dv: int, dtype) -> bool:
    """Mosaic's layout: bf16 operands, whole sublane tiles of keys, and a
    grid step's heads a whole number of lane tiles side by side (or all the
    heads there are)."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and dk % 8 == 0
            and scan_heads(heads, dk, dv) > 0)


def chunk_scan(q, k, v, g, b, state, slot, init, length, *,
               chunk: int = CHUNK, interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """The multi-row sequences of a tick in ``linear_attention.tile_layout``'s
    tiles of ``chunk`` rows, each row as the projections leave it (a head a
    lane block): ``q, k [R, H * dk]``, ``v [R, H * dv]``, ``g [R, H * dk]``
    (the log of a token's decay a key channel, at most 0) with ``R >= NT *
    C`` (the rows past the last tile's are read by no one and come back
    zeros: ``linear_attention.scan_tick``), ``b [NT * C or more, H]``,
    ``state [slots, dk, H * dv]`` float32, a tile's ``slot``, ``init`` and
    live rows ``length`` (``[NT]``) -> ``(o [R, H * dv], state)``.  The
    Pallas kernel on a TPU, else the twin; recorded under
    :data:`KERNEL_SCAN`."""
    H = b.shape[1]
    dk, dv = q.shape[1] // H, v.shape[1] // H
    R, whole = q.shape[0], slot.shape[0] * chunk
    route = routes.take(
        KERNEL_SCAN, eligible=scan_eligible(H, dk, dv, q.dtype),
        interpret=interpret, pallas=f"pallas-kda-scan-{chunk}")
    if route == routes.XLA:
        o, state = scan_twin(q[:whole], k[:whole], v[:whole], g[:whole],
                             b[:whole], state, slot, init, length,
                             chunk=chunk)
    else:
        o, state = _scan(q, k, v, g, b, state, slot, init, length,
                         chunk=chunk, interpret=interpret)
    return zeros_past(o, whole, R), state


def _step_kernel(slot_ref, fresh_ref, n_ref,                 # scalar prefetch
                 q_ref, k_ref, a_ref, v_ref, b_ref, s_in_ref, o_ref,
                 s_out_ref):
    # q, k, a: [1, 1, dk, hb] (a head a lane: columns; a = exp(g)); v, o:
    # [1, 1, hb, dv]; b: [1, 1, 1, hb * dv] (spread over its head's lanes);
    # s_in, s_out: [1, dk, hb * dv], the block (slot[i], head block) of the
    # state array itself.  Rows past ``n`` keep the LAST live row's last
    # block (``_step``'s index map), so nothing moves for them
    i, n = pl.program_id(0), n_ref[0]
    hb, dv = v_ref.shape[2], v_ref.shape[3]

    @pl.when(i < n)
    def _():
        keep = jnp.where(fresh_ref[i] > 0, 0.0, 1.0)
        q, k, a = q_ref[0, 0], k_ref[0, 0], a_ref[0, 0]
        v, b = v_ref[0, 0], b_ref[0, 0]
        for h in range(hb):
            lanes = slice(h * dv, (h + 1) * dv)
            k_col, q_col = k[:, h:h + 1], q[:, h:h + 1]       # [dk, 1]
            s = (a[:, h:h + 1] * keep) * s_in_ref[0, :, lanes]
            d = b[0:1, lanes] * (v[h:h + 1, :] - jnp.sum(
                k_col * s, axis=0, keepdims=True))            # [1, dv]
            new = s + k_col * d
            s_out_ref[0, :, lanes] = new
            o_ref[0, 0, h:h + 1, :] = jnp.sum(q_col * new, axis=0,
                                              keepdims=True)

    @pl.when(i >= n)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # no live row at all: the one block every step names is copied onto
    # itself once, so that what is written back at the end is what was read
    @pl.when(jnp.logical_and(n == 0, jnp.logical_and(
        i == 0, pl.program_id(1) == 0)))
    def _():
        s_out_ref[...] = s_in_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def _step(q, k, v, g, b, state, slot, fresh, n, *, interpret: bool):
    R, H, dk = q.shape
    dv = v.shape[2]
    hb = step_heads(H, dv)
    HB = H // hb
    f32 = lambda a: a.astype(jnp.float32)
    columns = lambda a: f32(a).reshape(R, HB, hb, dk).transpose(0, 1, 3, 2)
    b = jnp.repeat(f32(b), dv, axis=1).reshape(R, HB, 1, hb * dv)
    row = lambda r, c: pl.BlockSpec((1, 1, r, c),
                                    lambda i, h, *_: (i, h, 0, 0))

    def block_of(i, h, slot, fresh, n):
        live = i < n[0]
        last = jnp.maximum(n[0] - 1, 0)
        return (slot[jnp.minimum(i, last)], 0, jnp.where(live, h, HB - 1))

    block = pl.BlockSpec((1, dk, hb * dv), block_of)
    o, state = pl.pallas_call(
        _step_kernel,
        out_shape=(jax.ShapeDtypeStruct((R, HB, hb, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[row(dk, hb), row(dk, hb), row(dk, hb), row(hb, dv),
                      row(1, hb * dv), block],
            out_specs=(row(hb, dv), block),
            grid=(R, HB)),
        # operands are counted with the scalar-prefetch arrays
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=STEP_NAME,
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32), n.astype(jnp.int32),
      columns(q), columns(k), columns(jnp.exp(f32(g))),
      f32(v).reshape(R, HB, hb, dv), b, state)
    return o.reshape(R, H, dv).astype(v.dtype), state


def step_heads(heads: int, dv: int) -> int:
    """Heads a grid step of the decode kernel holds: :data:`_STEP_HEADS`
    where they divide the heads and fill whole lane tiles, else 0."""
    for hb in (_STEP_HEADS, 2):
        if heads % hb == 0 and (hb * dv) % _LANES == 0:
            return hb
    return 0


def decode_step(q, k, v, g, b, state, slot, fresh, n, *,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One token a row, the state updated WHERE IT LIES: the first ``n[0]``
    rows of ``q, k [R, H, dk]``, ``v [R, H, dv]``, ``g [R, H * dk]`` (or
    ``[R, H, dk]``), ``b [R, H]`` are live (``linear_attention.live_first``),
    row ``i``'s state is ``state[slot[i]]`` (``[slots, dk, H * dv]``
    float32; the live rows' slots are distinct), read as zeros where
    ``fresh[i]`` -> ``(o [R, H, dv], state)``; a row past ``n`` comes back
    zero, and no other slot's state is read or written.  The Pallas kernel
    on a TPU, else the twin; recorded under :data:`KERNEL_STEP`."""
    H, dk = q.shape[1:]
    g = g.reshape(q.shape)
    route = routes.take(
        KERNEL_STEP, eligible=dk % 8 == 0 and step_heads(H, v.shape[2]) > 0,
        interpret=interpret, pallas="pallas-kda-step")
    if route == routes.XLA:
        return decode_step_twin(q, k, v, g, b, state, slot, fresh, n)
    return _step(q, k, v, g, b, state, slot, fresh, n, interpret=interpret)
