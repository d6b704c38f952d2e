"""The gated delta rule over a matrix state (Pallas TPU kernels).

A ``linear_attention`` layer of ``models/olmo_hybrid.py`` (a Gated DeltaNet,
arXiv:2412.06464) keeps, for each head, a ``dk x dv`` float32 state in place
of keys and values.  With ``a_t = exp(g_t)`` in (0, 1) a decay A TOKEN and
``b_t`` in (0, 2) the strength of the correction::

    S' = a_t S_(t-1)        d_t = b_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T    o_t = S_t^T q_t

i.e. ``S_t = a_t (I - b_t k_t k_t^T) S_(t-1) + b_t k_t v_t^T``: what the
state already answers for ``k_t`` is corrected, not added to.  Two forms
serve it (``paged/delta_state.py:ragged_gated_delta``):

- :func:`chunk_scan` (kernel ``delta_chunk_scan``): the prompt chunks of a
  fused tick, ``C`` = :data:`CHUNK` tokens at a time.  The correction
  couples the rows of a chunk, so where ``ops/linear_attention.py`` has a
  masked product this has a triangular solve.  With ``G_i = sum_(j<=i)
  g_j`` and ``S_0`` the state before the chunk::

      A   = tril(diag(b) ((K K^T) * exp(G_i - G_j)), -1)
      T   = (I + A)^-1
      U   = T diag(b) (V - diag(exp G) K S_0)
      O   = diag(exp G) Q S_0 + tril((Q K^T) * exp(G_i - G_j)) U
      S_C = exp(G_C) S_0 + (K * exp(G_C - G_i))^T U

  ``A`` is strictly lower triangular and ``T`` is built by doubling
  (:func:`solve`, which ``ops/kda.py`` calls too): the inverse of every
  diagonal block of ``2 s`` rows from those of its halves, ``[[T1, 0], [-T2
  A21 T1, T2]]``, ``log2 C - 1`` levels of two products on the MXU.  A level
  couples the ``s`` rows of a block whose index has bit ``s`` with the ``s``
  that have it not, and changes the former alone: from ``s = 8``, where
  those rows are whole groups of sublanes, its two products stream those ``C
  / 2`` rows and no others (``[C/2, C] x [C, C]``; 4 of the 6 levels at ``C``
  = 128, :func:`levels`), the levels below multiply the whole tile.  What is
  dropped are products with exact zeros: the results are the whole
  products' to the bit.  The doubling is forward substitution by blocks,
  and as stable: the finite product ``(I - A)(I + A^2)(I + A^4) ..`` costs
  no fewer products and passes through powers of ``A`` whose entries, for
  keys that resemble one another, are thousands of times the inverse's (at
  keys of 8 it lost three digits of float32 in a tile).  Every exponent is
  of a non-positive number.  The rows of a packed ragged batch are laid out
  in tiles of ``C`` by ``linear_attention.tile_layout`` (a tile is of one
  sequence; a head's state rides a sequence's tiles in the output block;
  ``LOAD`` / ``ZERO`` / carry as there), each row AS THE PROJECTIONS LEAVE
  IT: ``q, k [NT * C, H * dk]``, ``v, o [NT * C, H * dv]``, rows down, a
  head a lane block.  Nothing is transposed to heads first and nothing
  comes back: a grid step's block is ``C`` rows of its heads' lanes, and a
  head is a static lane slice of it, as it is of the state's block.  A
  tile row at or past the tile's ``len`` holds no token: its ``g`` and ``b``
  come as zeros and the kernel zeroes its q, k, v whatever the tile holds
  there, which leaves state and solve untouched.
- :func:`decode_step` (kernel ``delta_decode_step``): one token a row, the
  state updated where it lies (``linear_attention.live_first``: live rows
  only, an idle slot's state does not move), on the vector unit alone:
  ``S'^T k`` and ``S^T q`` are reductions down the sublanes of a state held
  keys down, ``k`` and ``q`` come as columns (``[dk, heads]``) so nothing is
  transposed.  ``2 x dk x dv x 4`` bytes a head a row: bandwidth-bound.

**The state's layout** is ``[slots, dk, heads * dv]``: at the published 96 x
192 neither dimension is a whole number of the chip's 128 lanes, and
``[.., 96, 192]`` is stored as ``[.., 96, 256]`` (a third more to keep, read
and write).  Heads side by side along the lanes are ``30 x 192 = 45`` whole
tiles; a grid step takes an even number of heads (``2 x 192 = 3`` tiles) and
a head's columns are a lane slice of the block.  **A tile's rows** are blocked
the same way: v and o ``(C, 2 x 192)``, whole lane tiles; q and k ``(C, 4 x
96)``, the lanes of FOUR heads (``2 x 96 = 192`` is no whole number of lane
tiles), of which the step's parity takes its two; the fifteenth pair's block
lies half past the row's end, and that half is never read.

Products that touch the state, and the solve, are float32 at
``Precision.HIGHEST``; ``K K^T`` and ``Q K^T`` multiply the operands as
stored with a float32 result.  Each kernel has a ``jax.numpy`` twin
(:func:`scan_twin`, :func:`decode_step_twin`) taken on the CPU; which ran is
recorded under :data:`KERNEL_SCAN` / :data:`KERNEL_STEP` (``ops/routes.py``).
:func:`recurrence` is the definition, token by token, for the tests.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes
from deepspeed_tpu.ops.linear_attention import LOAD, ZERO, zeros_past

# the route keys, and the operations' names in a program and a device trace
KERNEL = "gated_delta"
KERNEL_SCAN = KERNEL + "_scan"
KERNEL_STEP = KERNEL + "_decode"
SCAN_NAME = "delta_chunk_scan"
STEP_NAME = "delta_decode_step"

_LANES = 128
# rows a float32 register holds: a level of the doubling whose blocks have at
# least as many is taken on half the tile (``levels``)
_SUBLANES = 8
# tokens a tile of the scan holds.  The MXU's work a token is about the same
# at 64 and 128 (the solve is two products a level over log2 C - 1 levels,
# of C x C below s = 8 and of C/2 x C from there: ``levels``; the other
# products grow with C); on the v5e a tick of 1,024 rows took 1.71 ms a
# layer at 64 and 1.52 at 128, half the grid steps (PERF.md, PR 50)
CHUNK = 128
# heads a grid step holds: of the scan (two chains of products to overlap,
# and 2 x 192 lanes of v, o and the state are three whole lane tiles; q's and
# k's 2 x 96 are not, so their block holds two steps' heads: ``_scan_blocks``.
# Four heads a step, whose 4 x 96 are whole tiles, ran 3 % slower on the
# v5e: PERF.md, PR 54), of the decode step (432 KiB of state in, the same
# out, double-buffered)
_SCAN_HEADS = 2
_STEP_HEADS = 6
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at highest precision."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _as_stored(a, b):
    """``a b^T`` of two operands as stored (bf16: one exact pass; float32:
    at highest precision), float32 result."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_HIGHEST if a.dtype == jnp.float32 else None)


# -- a level of the doubling, and the half of a tile it couples ---------------

def levels(C: int, first: int = 2):
    """``(s, half)`` for every level of a doubling over a tile of ``C`` rows
    (a power of two), from blocks of ``first`` rows up: the level couples,
    in every block of ``2 s`` rows, the ``s`` rows whose index has bit ``s``
    with the ``s`` that have it not.  ``half``: the level multiplies those
    ``C / 2`` rows alone, which it does where they are whole groups of the
    eight sublanes of a float32 array, so that taking them is a static
    choice of registers and no shuffle.  Unrolled by :func:`solve` (and by
    ``ops/kda.py`` for its level products, from 1), counted by
    :func:`levels_halved`."""
    assert C & (C - 1) == 0, f"a tile of {C} rows is no power of two"
    out, s = [], first
    while s < C:
        out.append((s, s >= _SUBLANES))
        s *= 2
    return tuple(out)


def levels_halved(C: int, first: int = 2):
    """``(levels that multiply half the tile, levels)`` of :func:`levels`:
    4 of 6 at a tile of 128 (4 of 7 from 1)."""
    halves = [half for _, half in levels(C, first)]
    return sum(halves), len(halves)


def rows_with(x, s: int, bit: bool = True):
    """The rows of ``x [C, ..]`` whose index has bit ``s`` (or has it not),
    ``C / 2`` of them in their order: blocks of ``s`` rows side by side."""
    return jnp.concatenate([x[r:r + s] for r in range(s if bit else 0,
                                                      x.shape[0], 2 * s)])


def rows_spread(lower, s: int):
    """``rows_with(.., s, False)`` undone: ``lower [C / 2, ..]`` at the rows
    of ``[C, ..]`` whose index has not bit ``s``, zeros at those that have."""
    zero = jnp.zeros_like(lower[:s])
    return jnp.concatenate([part for r in range(0, lower.shape[0], s)
                            for part in (lower[r:r + s], zero)])


def rows_plus(x, up, s: int):
    """``x [C, ..]`` with ``up [C / 2, ..]`` added to the rows whose index
    has bit ``s``, the others as they are."""
    parts = []
    for n, r in enumerate(range(0, x.shape[0], 2 * s)):
        parts += [x[r:r + s], x[r + s:r + 2 * s] + up[n * s:(n + 1) * s]]
    return jnp.concatenate(parts)


def solve(a):
    """``(I + A)^-1`` of a strictly lower triangular ``a [C, C]`` float32,
    by doubling: the inverse of every diagonal block of ``2 s`` rows from
    those of its two halves, ``[[T1, 0], [-T2 A21 T1, T2]]``, all blocks of
    a size at once as ``T - T A_off T`` (``A_off``: each block's lower
    left).  ``A_off`` is zero outside the rows that have bit ``s``, ``T`` is
    block diagonal in blocks of ``s``, so ``T A_off T`` is zero outside
    those rows too: a level that :func:`levels` halves streams only them
    through the two products (``[C/2, C] x [C, C]``; the products it drops
    are of exact zeros).  Both rules' ``_chunk_math`` call this, kernel and
    twin alike."""
    C = a.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    differ = i ^ j                      # a pair's level: its highest set bit
    t = jnp.where(i == j, 1.0, 0.0) - jnp.where((differ >> 1) == 0, a, 0.0)
    for s, half in levels(C):
        off = jnp.where((differ >> (s.bit_length() - 1)) == 1, a, 0.0)
        if half:
            t = rows_plus(t, -_dot(_dot(rows_with(t, s), off), t), s)
        else:
            t = t - _dot(_dot(t, off), t)
    return t


# -- the definition and the twins --------------------------------------------

def recurrence(q, k, v, g, b, state):
    """Token by token, one sequence: ``q, k [n, H, dk]``, ``v [n, H, dv]``,
    ``g, b [n, H]`` float32, ``state [H, dk, dv]`` float32 -> ``(o [n, H,
    dv] float32, state)``."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = (a.astype(jnp.float32) for a in x)
        s = jnp.exp(g_t)[:, None, None] * s
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision=_HIGHEST))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=_HIGHEST)

    state, o = jax.lax.scan(step, state.astype(jnp.float32), (q, k, v, g, b))
    return o, state


def _chunk_math(q, k, v, aux, s_in, n):
    """One tile of one head: ``q, k [C, dk]``, ``v [C, dv]``, ``aux [2, C]``
    float32 (row 0: ``G``, the running sum of ``g`` over the tile; row 1:
    ``b``), ``s_in [dk, dv]`` float32, ``n`` live rows -> ``(o [C, dv],
    s_out)``.  Shared by the kernel body and the twin."""
    C = q.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # a row at or past ``n`` holds no token, whatever the tile holds there
    # (its g and b come as zeros)
    live = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < n
    q, k, v = (jnp.where(live, x, jnp.zeros_like(x)) for x in (q, k, v))
    g_row, b_row = aux[0:1, :], aux[1:2, :]                   # [1, C]
    # a row of lanes as a column of sublanes, without a transpose
    column = lambda r: jnp.sum(jnp.where(i == j, r, 0.0), axis=1,
                               keepdims=True)
    g_col, b_col = column(g_row), column(b_row)               # [C, 1]
    decay = jnp.exp(jnp.where(i >= j, g_col - g_row, 0.0))    # exp(G_i - G_j)
    a = jnp.where(i > j, b_col * _as_stored(k, k) * decay, 0.0)
    t = solve(a)
    kf, qf, vf = (x.astype(jnp.float32) for x in (k, q, v))
    e_col = jnp.exp(g_col)
    u = _dot(t, b_col * (vf - _dot(kf * e_col, s_in)))        # [C, dv]
    o = _dot(qf * e_col, s_in) + _dot(
        jnp.where(i >= j, _as_stored(q, k) * decay, 0.0), u)
    g_last = jnp.min(g_row, axis=1, keepdims=True)            # G_C: G falls
    s_out = jnp.exp(g_last) * s_in + _dot(
        kf * jnp.exp(g_last - g_col), u, (((0,), (0,)), ((), ())))
    return o, s_out


def _aux(g, b, chunk: int):
    """``g, b [NT * C, H]`` -> ``[H, NT, 2, C]`` float32: the running sum of
    ``g`` over each tile, and ``b``."""
    rows, H = g.shape
    tiles = lambda a: a.astype(jnp.float32).T.reshape(H, rows // chunk,
                                                      chunk)
    return jnp.stack([jnp.cumsum(tiles(g), axis=-1), tiles(b)], axis=2)


def _heads_first(state, H: int):
    """``[slots, dk, H * dv]`` -> ``[slots, H, dk, dv]``."""
    S, dk, width = state.shape
    return state.reshape(S, dk, H, width // H).transpose(0, 2, 1, 3)


def _heads_along_lanes(state):
    """``[slots, H, dk, dv]`` -> ``[slots, dk, H * dv]``."""
    S, H, dk, dv = state.shape
    return state.transpose(0, 2, 1, 3).reshape(S, dk, H * dv)


def scan_twin(q, k, v, g, b, state, slot, init, length, *,
              chunk: int = CHUNK):
    """The XLA twin of :func:`chunk_scan`, tile by tile."""
    rows, H = g.shape
    NT = rows // chunk
    # [NT * C, H * d] -> [NT, H, C, d]
    tiles = lambda a: a.reshape(NT, chunk, H, -1).transpose(0, 2, 1, 3)
    every_head = jax.vmap(_chunk_math, in_axes=(0, 0, 0, 0, 0, None))
    state = _heads_first(state, H)

    def step(carry, x):
        state, run = carry
        q_n, k_n, v_n, aux_n, slot_n, init_n, len_n = x
        s_in = jnp.where(init_n == LOAD, state[slot_n],
                         jnp.where(init_n == ZERO, 0.0, run))
        o, s_out = every_head(q_n, k_n, v_n, aux_n, s_in, len_n)
        live = jnp.logical_or(len_n > 0, init_n == LOAD)
        s_out = jnp.where(len_n > 0, s_out, s_in)
        state = state.at[jnp.where(live, slot_n, state.shape[0])].set(
            s_out, mode="drop")
        return (state, s_out), o

    (state, _), o = jax.lax.scan(
        step, (state, jnp.zeros_like(state[0])),
        (tiles(q), tiles(k), tiles(v),
         _aux(g, b, chunk).transpose(1, 0, 2, 3), slot, init, length))
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
    s = jnp.exp(gf)[..., None, None] * s_in
    d = bf[..., None] * (vf - jnp.sum(kf[..., :, None] * s, axis=-2))
    new = s + kf[..., :, None] * d[..., None, :]
    o = jnp.sum(qf[..., :, None] * new, axis=-2)
    st = st.at[jnp.where(live, slot, S)].set(new, mode="drop")
    return (jnp.where(live[:, None, None], o, 0.0).astype(v.dtype),
            _heads_along_lanes(st))


# -- the kernels --------------------------------------------------------------

def _scan_kernel(slot_ref, init_ref, len_ref,                # scalar prefetch
                 q_ref, k_ref, v_ref, aux_ref, s_in_ref, o_ref, s_out_ref,
                 *, parts: int):
    # q, k: [C, parts * hb * dk] (or the whole row); v, o: [C, hb * dv];
    # aux: [hb, 1, 2, C]; s_in, s_out: [1, dk, hb * dv].  A head is a lane
    # slice of each.  The output state block is the running state: its
    # index (slot, head block) is the same for every tile of a sequence
    block, n = pl.program_id(0), pl.program_id(1)
    init, length = init_ref[n], len_ref[n]
    hb, dk = aux_ref.shape[0], s_out_ref.shape[1]
    dv = v_ref.shape[1] // hb

    @pl.when(init == LOAD)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(init == ZERO)
    def _():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)

    def of_step(ref):
        """The lanes of a q/k block that are this step's heads'."""
        if parts == 1:
            return ref[...]
        wide, lanes = ref[...], hb * dk
        mine = wide[:, :lanes]
        for part in range(1, parts):
            mine = jnp.where(block % parts == part,
                             wide[:, part * lanes:(part + 1) * lanes], mine)
        return mine

    @pl.when(length > 0)
    def _():
        q, k = of_step(q_ref), of_step(k_ref)
        for h in range(hb):
            of_qk = slice(h * dk, (h + 1) * dk)
            lanes = slice(h * dv, (h + 1) * dv)
            o, s_out = _chunk_math(q[:, of_qk], k[:, of_qk], v_ref[:, lanes],
                                   aux_ref[h, 0], s_out_ref[0, :, lanes],
                                   length)
            o_ref[:, lanes] = o.astype(o_ref.dtype)
            s_out_ref[0, :, lanes] = s_out

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _scan_blocks(heads: int, dk: int):
    """``(hb, parts)``: the heads a grid step holds, and the steps that
    share one q/k block: ``hb * dk`` lanes are a block of their own where
    they are whole lane tiles, else ``parts`` steps' heads side by side are
    (two of 192 lanes at the published 96), or the whole row."""
    hb = min(_SCAN_HEADS, heads)
    parts = _LANES // math.gcd(hb * dk, _LANES)
    return hb, min(parts, -(-heads // hb))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   donate_argnums=(4,))
def _scan(q, k, v, aux, state, slot, init, length, *, chunk: int,
          interpret: bool):
    H, C = aux.shape[0], chunk
    dk, dv = q.shape[1] // H, v.shape[1] // H
    NT = slot.shape[0]
    hb, parts = _scan_blocks(H, dk)
    qk = pl.BlockSpec((C, parts * hb * dk), lambda h, n, *_: (n, h // parts))
    vo = pl.BlockSpec((C, hb * dv), lambda h, n, *_: (n, h))
    block = pl.BlockSpec((1, dk, hb * dv),
                         lambda h, n, slot, *_: (slot[n], 0, h))
    return pl.pallas_call(
        functools.partial(_scan_kernel, parts=parts),
        out_shape=(jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[qk, qk, vo,
                      pl.BlockSpec((hb, 1, 2, C),
                                   lambda h, n, *_: (h, n, 0, 0)), block],
            out_specs=(vo, block),
            grid=(H // hb, NT)),
        # operands are counted with the scalar-prefetch arrays: the state
        # is the eighth, and becomes the second result
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=SCAN_NAME,
    )(slot, init, length, q, k, v, aux, state)


def scan_eligible(heads: int, dk: int, dv: int, dtype) -> bool:
    """Mosaic's layout: bf16 operands, whole sublane tiles of keys, and a
    grid step's heads a whole number of lane tiles side by side (or all the
    heads there are)."""
    hb, _ = _scan_blocks(heads, dk)
    return (jnp.dtype(dtype) == jnp.bfloat16 and dk % 8 == 0
            and heads % hb == 0
            and ((hb * dv) % _LANES == 0 or hb == heads))


def chunk_scan(q, k, v, g, b, state, slot, init, length, *,
               chunk: int = CHUNK, interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """The multi-row sequences of a tick in ``linear_attention.tile_layout``'s
    tiles of ``chunk`` rows, each row as the projections leave it (a head a
    lane block): ``q, k [R, H * dk]``, ``v [R, H * dv]`` with ``R >= NT * C``
    (the rows past the last tile's are read by no one and come back zeros:
    ``linear_attention.scan_tick``), ``g, b [NT * C or more, H]`` (the log of
    a token's decay, at most 0, and the correction's strength; both 0 where
    a tile row holds no token), ``state [slots, dk, H * dv]`` float32, a
    tile's ``slot``, ``init`` and live rows ``length`` (``[NT]``) -> ``(o [R,
    H * dv], state)``.  The Pallas kernel on a TPU, else the twin; recorded
    under :data:`KERNEL_SCAN`."""
    H = g.shape[1]
    dk, dv = q.shape[1] // H, v.shape[1] // H
    R, whole = q.shape[0], slot.shape[0] * chunk
    g, b = g[:whole], b[:whole]
    route = routes.take(
        KERNEL_SCAN, eligible=scan_eligible(H, dk, dv, q.dtype),
        interpret=interpret, pallas=f"pallas-delta-scan-{chunk}")
    if route == routes.XLA:
        o, state = scan_twin(q[:whole], k[:whole], v[:whole], g, b, state,
                             slot, init, length, chunk=chunk)
    else:
        o, state = _scan(q, k, v, _aux(g, b, chunk), state, slot, init,
                         length, chunk=chunk, interpret=interpret)
    return zeros_past(o, whole, R), state


def _step_kernel(slot_ref, fresh_ref, n_ref,                 # scalar prefetch
                 q_ref, k_ref, v_ref, ab_ref, s_in_ref, o_ref, s_out_ref):
    # q, k: [1, 1, dk, hb] (a head a lane: columns); v, o: [1, 1, hb, dv];
    # ab: [1, 1, 2, hb * dv] (row 0: a = exp(g), row 1: b, each spread over
    # its head's lanes: Mosaic spreads a [1, 1] one way, not both); s_in,
    # s_out: [1, dk, hb * dv], the block (slot[i], head block) of the state
    # array itself.
    # Rows past ``n`` keep the LAST live row's last block (``_step``'s index
    # map), so nothing moves for them
    i, n = pl.program_id(0), n_ref[0]
    hb, dv = v_ref.shape[2], v_ref.shape[3]

    @pl.when(i < n)
    def _():
        keep = jnp.where(fresh_ref[i] > 0, 0.0, 1.0)
        q, k, v, ab = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], ab_ref[0, 0]
        for h in range(hb):
            lanes = slice(h * dv, (h + 1) * dv)
            k_col, q_col = k[:, h:h + 1], q[:, h:h + 1]       # [dk, 1]
            s = (ab[0:1, lanes] * keep) * s_in_ref[0, :, lanes]
            d = ab[1:2, lanes] * (v[h:h + 1, :] - jnp.sum(
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
    ab = jnp.stack([jnp.exp(f32(g)), f32(b)], axis=1)         # [R, 2, H]
    ab = jnp.repeat(ab, dv, axis=2).reshape(R, 2, HB, hb * dv).transpose(
        0, 2, 1, 3)
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
            in_specs=[row(dk, hb), row(dk, hb), row(hb, dv), row(2, hb * dv),
                      block],
            out_specs=(row(hb, dv), block),
            grid=(R, HB)),
        # operands are counted with the scalar-prefetch arrays
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=STEP_NAME,
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32), n.astype(jnp.int32),
      columns(q), columns(k), f32(v).reshape(R, HB, hb, dv), ab, state)
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
    rows of ``q, k [R, H, dk]``, ``v [R, H, dv]``, ``g, b [R, H]`` are live
    (``linear_attention.live_first``), row ``i``'s state is ``state[slot[i]]``
    (``[slots, dk, H * dv]`` float32; the live rows' slots are distinct),
    read as zeros where ``fresh[i]`` -> ``(o [R, H, dv], state)``; a row
    past ``n`` comes back zero, and no other slot's state is read or
    written.  The Pallas kernel on a TPU, else the twin; recorded under
    :data:`KERNEL_STEP`."""
    H, dk = q.shape[1:]
    route = routes.take(
        KERNEL_STEP, eligible=dk % 8 == 0 and step_heads(H, v.shape[2]) > 0,
        interpret=interpret, pallas="pallas-delta-step")
    if route == routes.XLA:
        return decode_step_twin(q, k, v, g, b, state, slot, fresh, n)
    return _step(q, k, v, g, b, state, slot, fresh, n, interpret=interpret)
