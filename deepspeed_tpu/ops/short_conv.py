"""The short convolution of a fused tick as ONE pass over its rows (a Pallas
TPU kernel).

A delta-rule mixer (``models/olmo_hybrid.py:ShortConv``) convolves q~, k~ and
v~ along each sequence with ``K`` taps a channel, rounds, applies SiLU and
norms q and k a head.  As XLA fusions that is three passes over the tick's
rows, one of them in float32, because a scatter and a reshape to heads each
want their operand in memory (PERF.md §6, PR 67).  :func:`rows` is the one
pass, a part at a time: a block of rows of the part's lanes comes in with the
16 rows before it, both are laid down as float32 once, and a loop over the
block's tiles takes tap ``d`` of a tile as the same tile fetched ``d`` rows up
(a static slice of a view of the tile's rows and the 16 before it: no roll,
no select; the loop's body is lowered once, which is what a served program's
set-up pays for every call site).  The sum is float32 in the
order ``conv_state.py`` adds it and every rounding stands where the XLA lines
have it: to bf16 after the taps, SiLU in float32, to bf16, the unit norm a
head (the sum of squares is a reduction along the lanes: one a row where a
head is whole lane tiles, Solar's 128; under a mask in each lane tile a head
touches where it is not, Olmo-Hybrid's 96, whose reshape to heads cost XLA
two relayout copies of the float32 rows), the scale, to bf16.

The kernel knows no sequence: a chunk's first ``K - 1`` rows would come out
as if the rows above them were their own.  ``conv_state.py`` computes those
``seqs * (K - 1)`` rows whole beside it (from their chunk and the slot's
carried rows) and the kernel SETS them into a block before it leaves (XLA's
scatter of the same rows writes a row at a time, 22 us a part at Solar's
width), so nothing of another sequence is left in a row.

The kernel has no twin of its own: where it is not eligible (the CPU, a
decode program, operands that are not bf16) the callers keep their XLA lines,
and which ran is recorded under :data:`KERNEL` (``ops/routes.py``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes

# the route key, and the operation's name in a program and a device trace
KERNEL = "short_conv"
NAME = "short_conv_rows"

_LANES = 128
# rows and (at most) lanes of a grid step (Step 0 of PR 67: 512 x 1,024 read
# 228 us a Solar layer, 256 x 512 260); the tile of it the taps, SiLU and the
# norms run over at a time (64 x 512: the working set stays in registers, and
# the last tile of a tick of 1,032 rows wastes 56 rows where 128 waste 120)
ROWS = 512
LANE_BLOCK = 1024
_TILE_ROWS = 64
_TILE_LANES = 512
# the rows before a block that come with it: one packed bf16 tile, of which
# the last ``K - 1`` are read
_HALO = 16


def head_lanes(head_dim: Optional[int]) -> int:
    """The fewest whole lane tiles that are whole heads of ``head_dim``
    (one lane tile where the part is not normed)."""
    return math.lcm(head_dim or _LANES, _LANES)


def lane_block(width: int, head_dim: Optional[int] = None) -> int:
    """The lanes of a grid step over a part ``width`` lanes wide: the most
    whole lane tiles up to :data:`LANE_BLOCK` that divide it (whole heads of
    ``head_dim`` where the pass norms them), or all of it where none
    does."""
    unit = head_lanes(head_dim)
    if width % unit:
        return width
    units = width // unit
    return unit * max(n for n in range(1, max(LANE_BLOCK // unit, 1) + 1)
                      if units % n == 0)


def block_rows(lanes: int) -> int:
    """The rows of a grid step whose block is ``lanes`` wide: half of them
    where a part that is no whole lane tiles comes whole (``x``, the block
    and its output stay under the scoped VMEM)."""
    return ROWS if lanes <= LANE_BLOCK else ROWS // 2


def vmem_bytes(width: int, taps: int, head_dim: Optional[int] = None) -> int:
    """What a call over a part ``width`` lanes wide keeps in VMEM: the block
    and its output twice (bf16), the halo, the taps and the set rows (32
    at eight slots of four taps) twice, ``x``."""
    cb = lane_block(width, head_dim)
    cb, r = -(-cb // _LANES) * _LANES, block_rows(cb)
    return cb * (2 * 2 * r * 2 + 2 * _HALO * 2 + 2 * max(taps, 8) * 4
                 + 2 * 32 * 4 + (_HALO + r) * 4)


def eligible(T: int, taps: int, dtype, head_dim: Optional[int] = None
             ) -> bool:
    """A tick of bf16 rows, whole sublane tiles of them, whose taps reach no
    further back than the halo, and whose heads (where the pass norms them)
    lie whole side by side in a tile of the pass: 128 (a head a lane tile),
    96 and 192 (four, two heads of three lane tiles), 64, 256."""
    return (T % 8 == 0 and 2 <= taps <= _HALO
            and jnp.dtype(dtype) == jnp.bfloat16
            and head_lanes(head_dim) <= _TILE_LANES)


def route(T: int, seqs: int, taps: int, dtype,
          head_dim: Optional[int] = None) -> str:
    """Choose the route of a call's convolution, and record a fused tick's
    (more rows than sequences).  A decode program has no kernel to take: its
    rows are the XLA lines', and it leaves no record."""
    if T <= seqs:
        return routes.XLA
    return routes.take(
        KERNEL, eligible=eligible(T, taps, dtype, head_dim), every=True,
        pallas=f"pallas-rows-{ROWS}x{LANE_BLOCK}")


def _silu(f: jax.Array) -> jax.Array:
    """``f * sigmoid(f)`` in float32 with the quotient as the reciprocal
    unit's estimate and one Newton step (float32's last bit or two, which
    the rounding to bf16 that follows does not see; the exact quotient cost
    10 us a part more, Step 0 of PR 67)."""
    den = 1.0 + jnp.exp(-f)
    r = pl.reciprocal(den, approx=True)
    return f * (r * (2.0 - den * r))


def _head_masks(W: int, head_dim: int):
    """Of a tile ``W`` lanes wide that holds whole heads of ``head_dim``
    from its first lane: by (head, lane tile) that the head touches, the
    lanes of the lane tile that are the head's (``None``: all of them)."""
    masks = {}
    for c0 in range(0, W, _LANES):
        c1 = min(c0 + _LANES, W)
        lane = c0 + jax.lax.broadcasted_iota(jnp.int32, (1, c1 - c0), 1)
        for h0 in range(c0 // head_dim * head_dim, c1, head_dim):
            whole = h0 <= c0 and c1 <= h0 + head_dim
            masks[h0 // head_dim, c0 // _LANES] = None if whole else (
                jnp.logical_and(lane >= h0, lane < h0 + head_dim))
    return masks


def _unit(y: jax.Array, masks, scale, eps: float):
    """The heads of a tile ``y [rows, W]`` at unit length times ``scale``,
    a lane tile at a time (a list).  A head that is no whole lane tiles is
    summed under its mask (:func:`_head_masks`) in each lane tile it touches,
    and a lane tile takes each of its heads' roots under the same masks."""
    a = [y[:, c0:c0 + _LANES].astype(jnp.float32)
         for c0 in range(0, y.shape[1], _LANES)]
    ss = {}
    for (h, c), mask in masks.items():
        sq = a[c] * a[c]
        if mask is not None:
            sq = jnp.where(mask, sq, 0.0)
        ss[h] = ss.get(h, 0.0) + jnp.sum(sq, axis=-1, keepdims=True)
    root = {h: jax.lax.rsqrt(s + eps) for h, s in ss.items()}
    out = []
    for c in range(len(a)):
        r = None
        for (h, c_), mask in masks.items():
            if c_ == c:
                r = root[h] if r is None or mask is None else jnp.where(
                    mask, root[h], r)
        out.append((a[c] * r * scale).astype(y.dtype))
    return out


def _kernel(dest_ref, scale_ref, u_ref, halo_ref, w_ref, fix_ref, o_ref,
            x_ref, *, T: int, silu: bool, head_dim: Optional[int],
            eps: float):
    # u, o: [R, Cb], rows i * R .. of the part's lanes j * Cb ..; halo: the
    # 16 rows before them (block 0: its own first rows, which only rows that
    # are set below read); w: [K, Cb] float32, tap K - 1 on the row itself;
    # x: [16 + R, Cb] float32, the halo and the block; fix: [N, Cb] float32,
    # the finished rows that start a chunk, row n the tick's row dest[n];
    # scale: [1] float32 in SMEM (q's and k's calls are one program)
    R, Cb = u_ref.shape
    K = w_ref.shape[0]
    x_ref[:_HALO, :Cb] = halo_ref[...].astype(jnp.float32)
    x_ref[_HALO:, :Cb] = u_ref[...].astype(jnp.float32)
    rows = min(_TILE_ROWS, R)
    lanes = min(_TILE_LANES // head_lanes(head_dim) * head_lanes(head_dim),
                Cb)
    first = pl.program_id(1) * R
    # by tile width (the last tile of a block may be narrower)
    masks = {} if head_dim is None else {
        W: _head_masks(W, head_dim) for W in {lanes, Cb % lanes or lanes}}

    def tile(t, _):
        r0 = pl.multiple_of(t * rows, rows)
        here = pl.ds(r0, rows)
        for l0 in range(0, Cb, lanes):
            at = slice(l0, min(l0 + lanes, Cb))
            near = x_ref.at[pl.ds(r0, _HALO + rows)]
            up = lambda d: near[_HALO - d:_HALO - d + rows, at]
            acc = up(0) * w_ref[K - 1:K, at]
            for d in range(1, K):
                acc = acc + up(d) * w_ref[K - 1 - d:K - d, at]
            y = acc.astype(o_ref.dtype)
            if silu:
                y = _silu(y.astype(jnp.float32)).astype(o_ref.dtype)
            if head_dim is None:
                o_ref[here, at] = y
                continue
            for c, values in enumerate(_unit(y, masks[y.shape[1]],
                                             scale_ref[0], eps)):
                c0 = l0 + c * _LANES
                o_ref[here, c0:c0 + values.shape[1]] = values

    # the tiles that hold a row of the tick: one past its last row holds
    # nothing
    jax.lax.fori_loop(
        0, jnp.clip(pl.cdiv(T - first, rows), 0, R // rows), tile, None)

    # the rows that start a chunk, set where they lie: the packed tile of 16
    # rows that holds one is read, the row replaced, the tile written
    sub = jax.lax.broadcasted_iota(jnp.int32, (_HALO, 1), 0)

    def set_row(n, _):
        r = dest_ref[n] - first

        @pl.when(jnp.logical_and(r >= 0, r < R))
        def _():
            g = pl.multiple_of(r // _HALO * _HALO, _HALO)
            o_ref[pl.ds(g, _HALO), :] = jnp.where(
                sub == r - g, fix_ref[pl.ds(n, 1), :],
                o_ref[pl.ds(g, _HALO), :].astype(jnp.float32)
            ).astype(o_ref.dtype)

    jax.lax.fori_loop(0, dest_ref.shape[0], set_row, None)


@functools.partial(jax.jit, static_argnames=(
    "silu", "head_dim", "eps", "interpret"))
def _rows(u, w, fix, dest, scale, *, silu: bool, head_dim: Optional[int],
          eps: float, interpret: bool):
    T, C = u.shape
    K, N = w.shape[0], fix.shape[0]
    cb = lane_block(C, head_dim)
    rows = block_rows(cb)
    per = rows // _HALO
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # lane tiles outside: the taps' and the set rows' blocks move once
        # a lane tile
        grid=(C // cb, pl.cdiv(T, rows)),
        in_specs=[
            pl.BlockSpec((rows, cb), lambda j, i, dest, scale: (i, j)),
            pl.BlockSpec((_HALO, cb), lambda j, i, dest, scale: (
                jnp.maximum(i * per - 1, 0), j)),
            pl.BlockSpec((K, cb), lambda j, i, dest, scale: (0, j)),
            pl.BlockSpec((N, cb), lambda j, i, dest, scale: (0, j)),
        ],
        out_specs=pl.BlockSpec((rows, cb), lambda j, i, dest, scale: (i, j)),
        # (whole lane tiles: a view of some of its rows is taken)
        scratch_shapes=[pltpu.VMEM(
            (_HALO + rows, -(-cb // _LANES) * _LANES), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, T=T, silu=silu, head_dim=head_dim,
                          eps=eps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, C), u.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name=NAME,
    )(dest, scale, u, u, w, fix)


def rows(u: jax.Array, taps: jax.Array, fix: jax.Array, dest: jax.Array, *,
         silu: bool = False, head_dim: Optional[int] = None,
         scale: Optional[float] = None, eps: float = 0.0,
         interpret: bool = False) -> jax.Array:
    """One part of a fused tick's convolution, by the kernel: ``u [T, C]``
    bf16 (rows as the projection leaves them), ``taps [C, K]`` (tap ``K - 1``
    on the row itself) -> ``[T, C]``: row ``t`` the taps of rows ``t - K + 1
    .. t`` summed in float32, rounded, through SiLU where ``silu``, and where
    ``head_dim`` is a number (:func:`eligible`) each head of so many lanes
    brought to unit length (``eps`` under the root) times ``scale``; but row ``dest[n]`` (``[N]``; a
    ``dest`` of ``T`` or more names no row) is ``fix[n]`` (``[N, C]``,
    finished): the rows that start a chunk, which the caller computes whole
    (``conv_state.py``)."""
    pad = -fix.shape[0] % 8
    return _rows(u, taps.astype(jnp.float32).T,
                 jnp.pad(fix.astype(jnp.float32), ((0, pad), (0, 0))),
                 jnp.pad(dest.astype(jnp.int32), (0, pad),
                         constant_values=u.shape[0]),
                 jnp.full((1,), 1.0 if scale is None else scale, jnp.float32),
                 silu=silu, head_dim=head_dim, eps=eps, interpret=interpret)
