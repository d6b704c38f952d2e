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
works on, and an empty group visits nothing.  A group that straddles a row
tile visits both tiles; a row tile that several groups share is revisited
consecutively, each visit storing only its own rows.  Products accumulate
in a float32 VMEM scratch; with ``tk == K`` the result equals
``ragged_dot``'s to the bit on the v5e, with ``tk < K`` the partial sums
meet in another order (a bf16 rounding apart).

The banks stay in HBM and the kernel copies their ``[tk, tn]`` tiles
itself, into :data:`SLOTS` slots of VMEM, in the order the grid wants them:
with ``tk == K`` one tile a non-empty group a pass over n.  The step that
first multiplies a tile waits for it and starts the copy of the tile
``SLOTS - 1`` further on, so a group's second and later visits, which need
no new bank, multiply while the next groups' banks come.  As a BlockSpec
the bank was fetched one grid step ahead and no further: a straddling
group's second visit ran with the bank stream idle (the bank was NOT read
twice) and the next group's first visit then waited a whole tile, 2.0 us a
straddle at banks of 2048 x 768, which was a quarter of the kernel's time
in a prefill tick of 64.5 rows a group (PERF.md §6, PR 48).

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
# rows a visit multiplies (PERF.md, PR 29: 32 and 64 re-read more banks, 256
# is compute-bound at a few rows a group)
TM = 128
# bank tiles in VMEM at a time: the one a visit multiplies and the next
# ones of the sequence, on their way.  Two leave no copy in flight while the
# next is issued (decode ticks 7 % slower than the BlockSpec's pipeline);
# three read the banks at the chip's bandwidth (PERF.md §6, PR 48)
SLOTS = 3
# what the tile rule lets the row and result tiles, TWO bank tiles and the
# accumulator take: PR 29 settled the tiles against it when the pipeline
# held two, under the 16 MiB a v5e kernel scopes by default.  The third
# slot is asked for (:func:`vmem_limit_bytes`) and narrows no tile
_TILE_BUDGET = 14 * 1024 * 1024


def _vmem_bytes(tm: int, tk: int, tn: int, itemsize: int,
                slots: int = SLOTS) -> int:
    """Double-buffered row and result tiles, ``slots`` bank tiles, and the
    accumulator."""
    return ((2 * (tm * tk + tm * tn) + slots * tk * tn) * itemsize
            + tm * tn * 4)


def vmem_limit_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What the kernel asks the compiler for: its tiles by this file's
    count and 2 MiB for what Mosaic keeps of its own."""
    return _vmem_bytes(tm, tk, tn, itemsize) + 2 * 1024 * 1024


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
    tm = TM
    fits = lambda tk, tn: _vmem_bytes(                  # noqa: E731
        tm, tk, tn, itemsize, slots=2) <= _TILE_BUDGET
    tk = _largest_tile(k, lambda t: fits(t, _LANES))
    tn = _largest_tile(n, lambda t: fits(tk, t))
    return tm, tk, tn


def _group_tiles(group_sizes: jax.Array, tm: int):
    """``(ends, first_tile, tiles)`` of each group: the row past its last,
    the row tile of ``tm`` rows its first row lies in, and how many row
    tiles it has a row in (an empty group none)."""
    ends = jnp.cumsum(group_sizes)
    first_tile = (ends - group_sizes) // tm
    return ends, first_tile, jnp.where(
        group_sizes > 0, (ends - 1) // tm - first_tile + 1, 0)


def visits(group_sizes: jax.Array) -> jax.Array:
    """The row-tile visits the kernel's grid makes for these groups, a pass
    over n.  Less the groups that are not empty it is the visits whose
    bank is resident already: the second and later ones of a group that
    spans row tiles."""
    return jnp.sum(_group_tiles(group_sizes, TM)[2])


def group_metadata(group_sizes: jax.Array, rows: int, tm: int):
    """``(group_offsets [E + 1], group_ids [S], m_tile_ids [S], hit_groups
    [E], hit [1]), visits``: the rows where each group starts; for each of
    the first ``visits`` grid steps the group it works on and the row tile
    of ``tm`` rows; the non-empty groups in order (the first ``hit``
    entries), which is the order their banks are wanted in.  A group visits
    every row tile it has a row in, in order; an empty group none; so a
    tile that several groups share is visited consecutively.  ``S = rows
    // tm + E - 1`` bounds the visits; entries past ``visits`` (and past
    ``hit``) are never read."""
    E = group_sizes.shape[0]
    groups = jnp.arange(E, dtype=jnp.int32)
    ends, first_tile, group_tiles = _group_tiles(group_sizes, tm)
    visit_ends = jnp.cumsum(group_tiles)
    visit_starts = visit_ends - group_tiles
    visit = jnp.arange(rows // tm + E - 1, dtype=jnp.int32)[:, None]
    # [S, E]: the one group, if any, whose run of visits holds this one
    mine = jnp.logical_and(visit_starts <= visit, visit < visit_ends)
    pick = lambda per_group: jnp.sum(jnp.where(mine, per_group, 0), axis=1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    # [E, E]: the one non-empty group, if any, with this many before it
    ordinal = jnp.cumsum(group_sizes > 0) - 1
    nth = jnp.logical_and(group_sizes > 0, ordinal == groups[:, None])
    hit_groups = jnp.sum(jnp.where(nth, groups, 0), axis=1)
    return ((offsets, pick(groups), pick(first_tile + visit - visit_starts),
             hit_groups, (ordinal[-1:] + 1).astype(jnp.int32)),
            visit_ends[-1])


def _kernel(group_offsets, group_ids, m_tile_ids, hit_groups, hit, lhs,
            banks, out, acc, slots, sems, taken, *, tm: int, tk: int,
            tn: int, tiles_k: int):
    n_i, visit, k_i = (pl.program_id(i) for i in range(3))
    visits = pl.num_programs(1)
    # the bank tiles the grid wants, in order, are a sequence of its own:
    # with K whole one a non-empty group a pass over n (every later visit
    # of the group multiplies the tile its first one waited for), with K
    # split one a step
    a_pass = hit[0] if tiles_k == 1 else visits * tiles_k
    wanted = pl.num_programs(0) * a_pass

    def start(f):
        """The ``f``-th tile of the sequence on its way to slot ``f %
        SLOTS``, if there is one."""
        @pl.when(f < wanted)
        def _():
            n, at = f // a_pass, f % a_pass
            if tiles_k == 1:
                group, k = hit_groups[at], 0
            else:
                group, k = group_ids[at // tiles_k], at % tiles_k
            pltpu.make_async_copy(
                banks.at[group, pl.ds(pl.multiple_of(k * tk, _LANES), tk),
                         pl.ds(pl.multiple_of(n * tn, _LANES), tn)],
                slots.at[f % SLOTS], sems.at[f % SLOTS]).start()

    @pl.when(jnp.logical_and(n_i == 0,
                             jnp.logical_and(visit == 0, k_i == 0)))
    def _():
        taken[0] = 0
        for f in range(SLOTS - 1):
            start(jnp.int32(f))

    def take():
        # this step's tile has been on its way since the step that took the
        # tile ``SLOTS - 1`` before it; the slot that one multiplied from is
        # free for the tile as far ahead
        f = taken[0]
        pltpu.make_async_copy(
            banks.at[0, pl.ds(0, tk), pl.ds(0, tn)], slots.at[f % SLOTS],
            sems.at[f % SLOTS]).wait()
        start(f + SLOTS - 1)
        taken[0] = f + 1

    if tiles_k == 1:
        pl.when(jnp.logical_or(
            visit == 0,
            group_ids[visit] != group_ids[jnp.maximum(visit - 1, 0)]))(take)
    else:
        take()

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        lhs[...], slots[(taken[0] - 1) % SLOTS], (((1,), (0,)), ((), ())),
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
        functools.partial(_kernel, tm=tm, tk=tk, tn=tn, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((padded, N), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, v, k, off, gid, mid, *_: (mid[v], k)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, v, k, off, gid, mid, *_: (mid[v], n)),
            grid=(tiles_n, visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32),
                            pltpu.VMEM((SLOTS, tk, tn), banks.dtype),
                            pltpu.SemaphoreType.DMA((SLOTS,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        # the bank copies run on from one pass over n into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(tm, tk, tn, itemsize)),
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
