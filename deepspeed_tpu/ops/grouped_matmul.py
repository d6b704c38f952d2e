"""Grouped matmul for expert-sorted rows (Pallas TPU kernel).

``grouped_matmul(rows [R, K], banks [E, K, N], group_sizes int32[E])``
multiplies each expert's contiguous run of rows by that expert's bank:
the contract of ``jax.lax.ragged_dot``, which is its XLA twin.  Rows are
sorted by group; rows past ``sum(group_sizes)`` come back as whatever the
kernel left there (``moe/sharded_moe.py:grouped_moe`` masks them).

The kernel is the forward of megablox ``gmm``
(``jax.experimental.pallas.ops.tpu.megablox``), carried here because the
upstream entry point cannot be given a name for the trace (the device
operation would be ``gmm``).  Its group metadata is computed here too,
by a dozen dense operations over ``[visits, groups]``: upstream's
``make_group_metadata`` gives the same arrays (the tests hold them
equal) through ``repeat``, ``histogram`` and ``roll``, whose tracing alone
added 1.5 s to a serving process's set-up (PERF.md, PR 29).  Grid
``(n tiles, row-tile visits, k tiles)``:
scalar-prefetched metadata says which row tile and which group a visit
works on, so the bank BlockSpec fetches one ``[tk, tn]`` tile of that
group's bank a step and an empty group visits nothing.  A group that
straddles a row tile visits both tiles (and reads its bank twice); a row
tile that several groups share is revisited consecutively, each visit
storing only its own rows.  Products accumulate in a float32 VMEM
scratch; with ``tk == K`` the result equals ``ragged_dot``'s to the bit
on the v5e, with ``tk < K`` the partial sums meet in another order (a
bf16 rounding apart).

Forward only: serving is the one caller (``moe/layer.py:_grouped_ffn``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes

# the second route key of the grouped matmul: WHICH kernel ran, at which
# tiles, for each distinct shape (``moe_grouped_matmul`` beside it says
# Mosaic or XLA alone: ``moe/sharded_moe.py:grouped_matmul_route``)
KERNEL = "moe_grouped_matmul_kernel"
TWIN = "ragged-dot"
# the operation's name in the compiled program and in a device trace
NAME = "ragged-dot-gmm"

_LANES = 128
# of the 16 MiB a v5e kernel may scope by default: the compiler takes tiles
# of 14.1 MiB by this file's count and refuses 16.9
# (tests/unit/ops/test_tpu_compile.py)
_VMEM_BUDGET = 14 * 1024 * 1024


def _vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """Double-buffered row, bank and result tiles, and the accumulator."""
    return (2 * (tm * tk + tk * tn + tm * tn) * itemsize
            + tm * tn * 4)


def _largest_tile(dim: int, fits) -> Optional[int]:
    """The largest multiple of the lane width that divides ``dim`` and
    ``fits``."""
    for tile in range(dim, 0, -_LANES):
        if dim % tile == 0 and fits(tile):
            return tile
    return None


def tiles(k: int, n: int, itemsize: int) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` for a ``[R, k] x [E, k, n]`` product, or None where
    the lane width divides ``k`` or ``n`` not.  Reads shapes alone.

    Settled on the v5e at 256 and 4,352 rows against 64 banks of 2048 x
    1024 and 1024 x 2048 (PERF.md, PR 29): row tiles of 128 at either row
    count (32 and 64 re-read more banks, 256 is compute-bound at a few rows
    a group), the whole of K in a step wherever a 128-wide bank tile lets
    it fit, then the widest bank tile that fits."""
    if k % _LANES or n % _LANES:
        return None
    tm = 128
    tk = _largest_tile(
        k, lambda t: _vmem_bytes(tm, t, _LANES, itemsize) <= _VMEM_BUDGET)
    tn = _largest_tile(
        n, lambda t: _vmem_bytes(tm, tk, t, itemsize) <= _VMEM_BUDGET)
    return tm, tk, tn


def group_metadata(group_sizes: jax.Array, rows: int, tm: int):
    """``(group_offsets [E + 1], group_ids [S], m_tile_ids [S]), visits``:
    the rows where each group starts, and for each of the first ``visits``
    grid steps the group it works on and the row tile of ``tm`` rows.  A
    group visits every row tile it has a row in, in order; an empty group
    none; so a tile that several groups share is visited consecutively.
    ``S = rows // tm + E - 1`` bounds the visits; entries past ``visits``
    are never read."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    first_tile = (ends - group_sizes) // tm
    group_tiles = jnp.where(group_sizes > 0,
                            (ends - 1) // tm - first_tile + 1, 0)
    visit_ends = jnp.cumsum(group_tiles)
    visit_starts = visit_ends - group_tiles
    visit = jnp.arange(rows // tm + E - 1, dtype=jnp.int32)[:, None]
    # [S, E]: the one group, if any, whose run of visits holds this one
    mine = jnp.logical_and(visit_starts <= visit, visit < visit_ends)
    pick = lambda per_group: jnp.sum(jnp.where(mine, per_group, 0), axis=1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return ((offsets, pick(jnp.arange(E, dtype=jnp.int32)),
             pick(first_tile + visit - visit_starts)), visit_ends[-1])


def _kernel(group_offsets, group_ids, m_tile_ids, lhs, rhs, out, acc, *,
            tm: int, tn: int, tiles_k: int):
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        lhs[...], rhs[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _():
        # only this group's rows of the tile; the others are another
        # visit's, or past the last group
        group = group_ids[visit]
        row = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
               + m_tile_ids[visit] * tm)
        mine = jnp.logical_and(row >= group_offsets[group],
                               row < group_offsets[group + 1])
        out[...] = jnp.where(mine, acc[...],
                             out[...].astype(jnp.float32)).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def _gmm(rows: jax.Array, banks: jax.Array, group_sizes: jax.Array, *,
         tiling: Tuple[int, int, int], interpret: bool) -> jax.Array:
    """The kernel at static tiles; jitted so that the calls of one program
    trace and lower once a shape."""
    tm, tk, tn = tiling
    (R, K), N = rows.shape, banks.shape[2]
    padded = -(-R // tm) * tm
    if padded != R:
        rows = jnp.pad(rows, ((0, padded - R), (0, 0)))
    tiles_k, tiles_n = K // tk, N // tn
    metadata, visits = group_metadata(group_sizes, padded, tm)
    itemsize = rows.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((padded, N), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, v, k, off, gid, mid: (mid[v], k)),
                pl.BlockSpec((None, tk, tn),
                             lambda n, v, k, off, gid, mid: (gid[v], k, n)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, v, k, off, gid, mid: (mid[v], n)),
            grid=(tiles_n, visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * padded * K * N, transcendentals=0,
            bytes_accessed=(padded * K * tiles_n
                            + K * N * metadata[1].size
                            + padded * N) * itemsize),
        interpret=interpret, name=NAME,
    )(*metadata, rows, banks)
    return out[:R]


def _one_device() -> bool:
    """GSPMD cannot split a Mosaic kernel: under an installed topology of
    several devices the twin runs."""
    import deepspeed_tpu.comm as dist

    topo = dist.peek_topology()
    return topo is None or topo.mesh.size == 1


def grouped_matmul(rows: jax.Array, banks: jax.Array,
                   group_sizes: jax.Array, *, interpret: bool = False
                   ) -> jax.Array:
    """``[R, K]`` expert-sorted rows against ``[E, K, N]`` banks ->
    ``[R, N]``.  The Pallas kernel on one TPU device where the tile rule
    divides the banks, else ``jax.lax.ragged_dot``; which, and at which
    tiles, is recorded under :data:`KERNEL` for every distinct answer."""
    tiling = None
    if rows.dtype == banks.dtype and rows.dtype in (jnp.bfloat16,
                                                    jnp.float32):
        tiling = tiles(rows.shape[1], banks.shape[2], rows.dtype.itemsize)
    route = routes.take(
        KERNEL, eligible=tiling is not None and _one_device(),
        interpret=interpret, every=True, xla=TWIN,
        pallas="gmm-" + "x".join(map(str, tiling or ())))
    if route == TWIN:
        return jax.lax.ragged_dot(rows, banks, group_sizes)
    return _gmm(rows, banks, group_sizes, tiling=tiling, interpret=interpret)
