"""Decaying linear attention over a matrix state (Pallas TPU kernels).

A ``lightning-attn`` layer (``models/minicpm_sala.py``) keeps, for each
head, a ``d x d`` float32 state in place of keys and values::

    S_t = l S_(t-1) + k_t^T v_t        o_t = q_t S_t        l = exp(log_decay)

Two forms serve it (``paged/linear_state.py:ragged_linear_attention``):

- :func:`chunk_scan` (kernel ``linear_chunk_scan``): the prompt chunks of a
  fused tick, ``C`` = :data:`CHUNK` tokens at a time.  With ``D_ij =
  l^(i-j)`` for ``j <= i`` (0 above the diagonal)::

      O     = ((Q K^T) * D) V + diag(l^(i+1)) Q S_in
      S_out = l^n S_in + sum_(i<n) l^(n-1-i) k_i^T v_i      (n rows live)

  ``D`` is the exponential of a difference of indices, never a quotient
  of two powers: the fastest head's ``l^128`` is 1e-47 and its inverse
  overflows.  The rows of a packed ragged batch are laid out in **tiles**
  first (:func:`tile_layout`): each multi-row sequence's rows in whole
  tiles of ``C``, each row AS THE PROJECTIONS LEAVE IT (``[.., H * d]``:
  rows down, a head a lane block of 128; nothing is transposed to heads
  first and nothing comes back), so a tile belongs to one sequence, the
  grid is ``(head blocks, tiles)``, a step's block is ``C`` rows of its
  heads' lanes, and a head's state rides the tiles of a sequence in the
  output block itself; a sequence's first tile reads its slot's state (or
  zeros at position 0), the last leaves it there.  A tile row at or past
  the tile's ``len`` holds no token and is zeroed in the kernel, whatever
  the tile holds there.  Where every chunk of a tick starts on a multiple
  of ``C`` rows (``ragged_engine.py`` plans and packs it so: whole-tile
  chunks from row 0) the tiles ARE the tick's rows, ``tile_layout`` says
  so (``aligned``), and the scan reads the ``T // C`` tiles where they lie;
  else each operand is gathered to ``ceil(T / C) + seqs`` tiles
  (:func:`rows_to_tiles`) and ``o`` back (:func:`tiles_to_rows`).
  :func:`scan_tick` chooses, on the device.  The state array is donated
  (``input_output_aliases``): slots with no tile keep theirs.
- :func:`decode_step` (kernel ``linear_decode_step``): one token a row,
  ``2 x d x d x 4`` bytes read and written a head a row and a few hundred
  flops: bandwidth-bound.  The state is updated where it lies (the block
  of the state array a live row's slot names, aliased in place), live rows
  only: an idle slot's state does not move.  On the vector unit alone (the
  outer product ``k^T v`` by a broadcast and a 128 x 128 transpose, ``q S``
  by a sublane reduction): float32 exact, no MXU pass.

Products that touch the state are float32 at ``Precision.HIGHEST`` (the
state is the sum of thousands of terms; a bf16 pass would round each
tick's carry); ``Q K^T`` multiplies the bf16 operands as stored with a
float32 result.  Each kernel has a ``jax.numpy`` twin (:func:`scan_twin`,
:func:`decode_step_twin`) taken on the CPU; which ran is recorded under
:data:`KERNEL_SCAN` / :data:`KERNEL_STEP` (``ops/routes.py``).
:func:`recurrence` is the definition, token by token, for the tests.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes
from deepspeed_tpu.ops.paged_decode import flat_list

# the route keys, and the operations' names in a program and a device trace
KERNEL = "linear_attention"
KERNEL_SCAN = KERNEL + "_scan"
KERNEL_STEP = KERNEL + "_decode"
SCAN_NAME = "linear_chunk_scan"
STEP_NAME = "linear_decode_step"

_LANES = 128
# tokens a tile of the scan holds: one MXU tile of rows, and the width the
# decay table ``D`` is built at
CHUNK = 128
# heads a grid step holds: of the scan (a head's tile is half a microsecond
# of products, so a step of one head was mostly the step itself; the four
# heads' lanes of a row are one block of 512), of the decode kernel (512 KiB
# of state in, the same out, double-buffered)
_SCAN_HEADS = 4
_STEP_HEADS = 8
_HIGHEST = jax.lax.Precision.HIGHEST

# what a tile's first step does with the running state
CONTINUE, LOAD, ZERO = 0, 1, 2


def head_log_decay(heads: int) -> jax.Array:
    """``log l_h = -2^(-8 (h + 1) / heads)``: Lightning Attention's
    head-wise slopes (``[heads]`` float32)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / heads)


def tile_layout(cu_q_lens: jax.Array, num_seqs: jax.Array, first_pos:
                jax.Array, T: int, tile: int) -> Dict[str, jax.Array]:
    """The multi-row sequences of a packed ragged batch of ``T`` rows as
    whole tiles of ``tile`` rows, each of one sequence.

    ``NT = ceil(T / tile) + seqs`` bounds the tiles.  Returns ``rows [NT *
    tile]`` (the packed row a tile row holds, ``T`` where it holds none),
    ``seq [NT]`` (a tile's packed sequence; a tile past the last holds the
    last's, so that a block indexed by it does not move), ``init [NT]``
    (:data:`LOAD` on a sequence's first tile, :data:`ZERO` where that
    sequence starts at position 0 (``first_pos [seqs]``), else
    :data:`CONTINUE`; with no tile at all, tile 0 is ``LOAD``: it copies a
    state onto itself), ``len [NT]`` (live rows, 0 past the last tile),
    ``pos0 [NT]`` (the position of a tile's first row), ``n [1]`` and
    ``aligned`` (a scalar: the tiles ARE the tick's own rows, tile ``m`` rows
    ``m * tile .. m * tile + tile - 1``, for every tile that holds a token,
    and those are among the ``T // tile`` whole tiles the rows hold.  It
    holds where every multi-row sequence starts on a multiple of ``tile``
    with nothing but the tail of the one before it in between, which is how
    ``ragged_engine.py:_pack_tick`` lays a tick whose chunks are whole
    tiles; :func:`scan_tick` then scans the first ``T // tile`` tiles where
    the rows lie)."""
    S = cu_q_lens.shape[0] - 1
    NT = -(-T // tile) + S
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    multi = jnp.logical_and(jnp.arange(S) < num_seqs[0], q_lens >= 2)
    seq, idx, flags, n = flat_list(
        jnp.where(multi, -(-q_lens // tile), 0), NT)
    valid = jnp.arange(NT) < n[0]
    last_seq = seq[jnp.maximum(n[0] - 1, 0)]
    seq = jnp.where(valid, seq, jnp.where(n[0] > 0, last_seq, 0))
    length = jnp.where(valid, jnp.clip(q_lens[seq] - idx * tile, 0, tile), 0)
    first = jnp.logical_and(valid, flags % 2 == 1)
    init = jnp.where(first, jnp.where(first_pos[seq] == 0, ZERO, LOAD),
                     CONTINUE)
    init = init.at[0].set(jnp.where(n[0] > 0, init[0], LOAD))
    i = jnp.arange(tile, dtype=jnp.int32)
    rows = cu_q_lens[seq][:, None] + idx[:, None] * tile + i[None]
    rows = jnp.where(i[None] < length[:, None], rows, T).reshape(-1)
    lay = {"rows": rows.astype(jnp.int32), "seq": seq.astype(jnp.int32),
           "init": init.astype(jnp.int32), "len": length.astype(jnp.int32),
           "pos0": (first_pos[seq] + idx * tile).astype(jnp.int32), "n": n}
    # read by the matrix-state kinds alone (``scan_tick``): a program that
    # does not ask for it does not hold it
    at_home = cu_q_lens[seq] + idx * tile == jnp.arange(NT) * tile
    lay["aligned"] = jnp.logical_and(
        jnp.all(jnp.logical_or(jnp.logical_not(valid), at_home)),
        n[0] <= T // tile)
    return lay


def to_tiles(x: jax.Array, rows: jax.Array) -> jax.Array:
    """``x [T, ...]`` -> ``[NT * tile, ...]`` by :func:`tile_layout`'s
    ``rows`` (zeros where a tile row holds none)."""
    return jnp.take(x, rows, axis=0, mode="fill", fill_value=0)


def from_tiles(y: jax.Array, rows: jax.Array, T: int) -> jax.Array:
    """The inverse scatter: ``y [NT * tile, ...]`` -> ``[T, ...]``, zeros
    where no tile row came from."""
    return jnp.zeros((T,) + y.shape[1:], y.dtype).at[rows].set(
        y, mode="drop")


def rows_to_tiles(x: jax.Array, rows: jax.Array) -> jax.Array:
    """:func:`to_tiles` of ``x [T, W]`` for a consumer that zeroes what lies
    at or past a tile's ``len`` itself (the chunked scans do): a tile row
    that holds no token holds the batch's last row, and the gather is one
    pass where the fill is a second.  Taken by a tick whose chunks do not
    lie on its own tiles, and by no other (:func:`scan_tick`)."""
    return jnp.take(x, rows, axis=0, mode="clip")


def tiles_to_rows(y: jax.Array, rows: jax.Array, T: int) -> jax.Array:
    """:func:`from_tiles` of ``y [NT * tile, W]`` as a GATHER (a scatter
    writes a row at a time): packed row ``t`` reads the tile row that holds
    it, and a row that no tile holds reads ``y``'s last row, which is zeros
    where the producer leaves a tile without a token zero: :func:`tile_layout`
    bounds the tiles by ``ceil(T / tile) + seqs``, more than can hold a
    token, so the last is never live.  The way back of a tick that went to
    tiles by :func:`rows_to_tiles` (:func:`scan_tick`)."""
    n = rows.shape[0]
    held = jnp.full((T,), n - 1, jnp.int32).at[rows].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return jnp.take(y, held, axis=0, mode="clip")


def zeros_past(o: jax.Array, whole: int, rows: int) -> jax.Array:
    """A scan's ``o`` over ``rows`` rows of which the tiles hold the first
    ``whole``: a kernel's ``[rows, W]``, whose rows past them no grid step
    wrote, or a twin's ``[whole, W]``, with zeros from there."""
    if o.shape[0] == rows:
        return o if whole == rows else o.at[whole:].set(0)
    return jnp.pad(o, ((0, rows - whole), (0, 0)))


def scan_tick(scan, lay: Dict[str, jax.Array], seq_slot: jax.Array, T: int,
              tile: int, rows, gates, state):
    """A chunked scan over the multi-row sequences of a tick of ``T`` rows,
    by :func:`tile_layout`'s ``lay``: ``scan(rows, gates, state, slot, init,
    len) -> (o, state)`` with ``rows`` the operands ``[.., W]`` whose rows
    past a tile's ``len`` the scan zeroes itself, ``gates`` those ``[.., h]``
    that must come as zeros there -> ``(o [T, W], state)``; a row no tile
    holds reads zeros.

    ONE algorithm, two layouts of its operands, chosen on the device by
    ``lay["aligned"]`` (``lax.cond``: one program, nothing a caller sets).
    Where the tiles ARE the tick's rows nothing moves: the scan reads the
    operands as the projections left them through its own blocks, ``T //
    tile`` tiles and no more, the gates are masked where they lie (a head
    wide: an elementwise pass), and its ``o`` is the answer.  Else (a chunk
    that starts off a tile: the tail of one prompt beside the head of the
    next) each operand is gathered to the tiles (:func:`rows_to_tiles`,
    :func:`to_tiles`) and ``o`` back (:func:`tiles_to_rows`).  The same
    rows multiplied by the same kernels either way: the results are equal
    to the bit."""
    slot = jnp.take(seq_slot, lay["seq"])

    def gathered(rows, gates, state):
        o_t, state = scan(
            tuple(rows_to_tiles(a, lay["rows"]) for a in rows),
            tuple(to_tiles(a, lay["rows"]) for a in gates),
            state, slot, lay["init"], lay["len"])
        return tiles_to_rows(o_t, lay["rows"], T), state

    own = T // tile                 # the whole tiles a tick's rows hold
    if own == 0:
        return gathered(rows, gates, state)

    def as_they_lie(rows, gates, state):
        length = lay["len"][:own]
        live = (jnp.arange(own * tile, dtype=jnp.int32) % tile
                < jnp.repeat(length, tile))[:, None]
        return scan(rows, tuple(jnp.where(live, a[:own * tile], 0)
                                for a in gates),
                    state, slot[:own], lay["init"][:own], length)

    return jax.lax.cond(lay["aligned"], as_they_lie, gathered, rows, gates,
                        state)


# -- the definition and the twins --------------------------------------------

def recurrence(q, k, v, log_decay, state):
    """Token by token, one sequence: ``q, k, v [n, H, d]``, ``state [H, d,
    d]`` float32 -> ``(o [n, H, d] float32, state)``."""
    decay = jnp.exp(log_decay)[:, None, None]

    def step(s, qkv):
        q_t, k_t, v_t = (a.astype(jnp.float32) for a in qkv)
        s = decay * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hd,hde->he", q_t, s, precision=_HIGHEST)

    state, o = jax.lax.scan(step, state.astype(jnp.float32), (q, k, v))
    return o, state


def _chunk_math(q, k, v, lam, s_in, n):
    """One tile of one head: ``q, k, v [C, d]``, ``lam`` a scalar or ``[1,
    1]``, ``s_in [d, d]`` float32, ``n`` live rows -> ``(o, s_out)``.
    Shared by the kernel body and the twin."""
    C = q.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    # a row at or past ``n`` holds no token, whatever the tile holds there
    q, k, v = (jnp.where(row < n, x, jnp.zeros_like(x)) for x in (q, k, v))
    delta = (i - j).astype(jnp.float32)
    table = jnp.where(i >= j, jnp.exp(lam * jnp.maximum(delta, 0.0)), 0.0)
    a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    vf = v.astype(jnp.float32)
    o = jnp.dot(a * table, vf, precision=_HIGHEST,
                preferred_element_type=jnp.float32)
    rowf = row.astype(jnp.float32)
    o = o + jnp.dot(q.astype(jnp.float32) * jnp.exp(lam * (rowf + 1.0)),
                    s_in, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)
    nf = n.astype(jnp.float32)
    weight = jnp.where(row < n, jnp.exp(lam * jnp.maximum(nf - 1.0 - rowf,
                                                           0.0)), 0.0)
    kw = (k.astype(jnp.float32) * weight).T
    s_out = jnp.exp(lam * nf) * s_in + jnp.dot(
        kw, vf, precision=_HIGHEST, preferred_element_type=jnp.float32)
    return o, s_out


def scan_twin(q, k, v, log_decay, state, slot, init, length):
    """The XLA twin of :func:`chunk_scan`, tile by tile."""
    rows, C = q.shape[0], CHUNK
    H, NT = log_decay.shape[0], rows // C
    # [NT * C, H * d] -> [NT, H, C, d]
    tiles = lambda a: a.reshape(NT, C, H, -1).transpose(0, 2, 1, 3)
    lam = log_decay.astype(jnp.float32)
    math = jax.vmap(_chunk_math, in_axes=(0, 0, 0, 0, 0, None))

    def step(carry, x):
        state, run = carry
        q_n, k_n, v_n, slot_n, init_n, len_n = x
        s_in = jnp.where(init_n == LOAD, state[slot_n],
                         jnp.where(init_n == ZERO, 0.0, run))
        o, s_out = math(q_n, k_n, v_n, lam, s_in, len_n)
        live = jnp.logical_or(len_n > 0, init_n == LOAD)
        s_out = jnp.where(len_n > 0, s_out, s_in)
        state = state.at[jnp.where(live, slot_n, state.shape[0])].set(
            s_out, mode="drop")
        return (state, s_out), o

    (state, _), o = jax.lax.scan(
        step, (state, jnp.zeros_like(state[0])),
        (tiles(q), tiles(k), tiles(v), slot, init, length))
    return o.transpose(0, 2, 1, 3).reshape(rows, -1).astype(q.dtype), state


def decode_step_twin(q, k, v, log_decay, state, slot, fresh, n):
    """The XLA twin of :func:`decode_step`."""
    S = state.shape[0]
    live = jnp.arange(q.shape[0]) < n[0]
    decay = jnp.exp(log_decay.astype(jnp.float32))[None, :, None, None]
    kf, vf, qf = (a.astype(jnp.float32) for a in (k, v, q))
    s_in = jnp.where(fresh[:, None, None, None], 0.0,
                     jnp.take(state, slot, axis=0))
    new = decay * s_in + kf[..., :, None] * vf[..., None, :]
    o = jnp.sum(qf[..., :, None] * new, axis=-2)
    state = state.at[jnp.where(live, slot, S)].set(new, mode="drop")
    return jnp.where(live[:, None, None], o, 0.0).astype(q.dtype), state


# -- the kernels --------------------------------------------------------------

def _scan_kernel(slot_ref, init_ref, len_ref,                # scalar prefetch
                 q_ref, k_ref, v_ref, lam_ref, s_in_ref, o_ref, s_out_ref):
    # q, k, v, o: [C, hb * d], the lanes of the step's heads of a tile's
    # rows; lam: [hb, 1, 128]; s_in, s_out: [1, hb, d, d].  The output state
    # block is the running state: its index (slot, head block) is the same
    # for every tile of a sequence
    n = pl.program_id(1)
    init, length = init_ref[n], len_ref[n]
    hb, d = s_out_ref.shape[1], s_out_ref.shape[2]

    @pl.when(init == LOAD)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(init == ZERO)
    def _():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)

    @pl.when(length > 0)
    def _():
        for h in range(hb):
            lanes = slice(h * d, (h + 1) * d)
            lam = lam_ref[h][:, :1]                           # [1, 1]
            o, s_out = _chunk_math(q_ref[:, lanes], k_ref[:, lanes],
                                   v_ref[:, lanes], lam, s_out_ref[0, h],
                                   length)
            o_ref[:, lanes] = o.astype(o_ref.dtype)
            s_out_ref[0, h] = s_out

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def scan_heads(heads: int) -> int:
    """Heads a grid step of the scan holds: as many of :data:`_SCAN_HEADS`
    as divide the heads."""
    return next(hb for hb in (_SCAN_HEADS, 2, 1) if heads % hb == 0)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(4,))
def _scan(q, k, v, lam, state, slot, init, length, *, interpret: bool):
    H, d, C = lam.shape[0], state.shape[-1], CHUNK
    NT = slot.shape[0]
    hb = scan_heads(H)
    tile = pl.BlockSpec((C, hb * d), lambda h, n, *_: (n, h))
    block = pl.BlockSpec((1, hb, d, d),
                         lambda h, n, slot, *_: (slot[n], h, 0, 0))
    return pl.pallas_call(
        _scan_kernel,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[tile, tile, tile,
                      pl.BlockSpec((hb, 1, _LANES),
                                   lambda h, n, *_: (h, 0, 0)), block],
            out_specs=(tile, block),
            grid=(H // hb, NT)),
        # operands are counted with the scalar-prefetch arrays: the state
        # is the eighth, and becomes the second result
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=SCAN_NAME,
    )(slot, init, length, q, k, v, lam, state)


def _lam_lanes(log_decay: jax.Array) -> jax.Array:
    return jnp.broadcast_to(log_decay.astype(jnp.float32)[:, None, None],
                            (log_decay.shape[0], 1, _LANES))


def scan_eligible(d: int, dtype) -> bool:
    """Mosaic's layout: heads of 128 in bf16."""
    return d == _LANES and jnp.dtype(dtype) == jnp.bfloat16


def chunk_scan(q, k, v, log_decay, state, slot, init, length, *,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The multi-row sequences of a tick in :func:`tile_layout`'s tiles,
    each row as the projections leave it (a head a lane block): ``q, k, v
    [R, H * d]`` with ``R >= NT * C`` (tile ``n`` is rows ``n C .. n C + C -
    1``; the rows past the last tile's are read by no one and come back
    zeros: :func:`scan_tick`), ``state [slots, H, d, d]`` float32, a tile's
    ``slot``, ``init`` and live rows ``length`` (``[NT]``) -> ``(o [R, H *
    d], state)``.  The Pallas kernel on a TPU, else the twin; recorded under
    :data:`KERNEL_SCAN`."""
    d = state.shape[-1]
    R, whole = q.shape[0], slot.shape[0] * CHUNK
    route = routes.take(KERNEL_SCAN, eligible=scan_eligible(d, q.dtype),
                        interpret=interpret,
                        pallas=f"pallas-linear-scan-{CHUNK}")
    if route == routes.XLA:
        o, state = scan_twin(q[:whole], k[:whole], v[:whole], log_decay,
                             state, slot, init, length)
    else:
        o, state = _scan(q, k, v, _lam_lanes(log_decay), state, slot, init,
                         length, interpret=interpret)
    return zeros_past(o, whole, R), state


def _step_kernel(slot_ref, fresh_ref, n_ref,                 # scalar prefetch
                 q_ref, k_ref, v_ref, lam_ref, s_in_ref, o_ref, s_out_ref):
    # q, k, v, o: [1, hb, 1, d] float32; lam: [hb, 1, 128];
    # s_in, s_out: [1, hb, d, d], the block (slot[i], head block) of the
    # state array itself.  Rows past ``n`` keep the LAST live row's last
    # block (``_step``'s index map), so nothing moves for them
    i, n = pl.program_id(0), n_ref[0]
    hb, d = s_in_ref.shape[1], s_in_ref.shape[2]

    @pl.when(i < n)
    def _():
        keep = jnp.where(fresh_ref[i] > 0, 0.0, 1.0)
        for h in range(hb):
            col = lambda ref: jnp.broadcast_to(ref[0, h], (d, d)).T
            new = (jnp.exp(lam_ref[h]) * keep * s_in_ref[0, h]
                   + col(k_ref) * v_ref[0, h])
            s_out_ref[0, h] = new
            o_ref[0, h] = jnp.sum(col(q_ref) * new, axis=0, keepdims=True)

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
                   donate_argnums=(4,))
def _step(q, k, v, lam, state, slot, fresh, n, *, interpret: bool):
    R, H, d = q.shape
    hb = min(_STEP_HEADS, H)
    assert H % hb == 0, (H, hb)
    HB = H // hb
    row = pl.BlockSpec((1, hb, 1, d), lambda i, h, *_: (i, h, 0, 0))

    def block_of(i, h, slot, fresh, n):
        live = i < n[0]
        last = jnp.maximum(n[0] - 1, 0)
        return (slot[jnp.minimum(i, last)], jnp.where(live, h, HB - 1), 0, 0)

    block = pl.BlockSpec((1, hb, d, d), block_of)
    wide = lambda a: a.astype(jnp.float32)[:, :, None, :]
    o, state = pl.pallas_call(
        _step_kernel,
        out_shape=(jax.ShapeDtypeStruct((R, H, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[row, row, row,
                      pl.BlockSpec((hb, 1, _LANES),
                                   lambda i, h, *_: (h, 0, 0)), block],
            out_specs=(row, block),
            grid=(R, HB)),
        # operands are counted with the scalar-prefetch arrays
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=STEP_NAME,
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32), n.astype(jnp.int32),
      wide(q), wide(k), wide(v), lam, state)
    return o[:, :, 0].astype(q.dtype), state


def live_first(live: jax.Array):
    """``(order [R], n [1])``: the rows where ``live`` holds first, in
    their own order, then the others; and how many hold."""
    return (jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32),
            jnp.sum(live, dtype=jnp.int32)[None])


def decode_step(q, k, v, log_decay, state, slot, fresh, n, *,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One token a row, the state updated WHERE IT LIES: the first ``n[0]``
    rows of ``q, k, v [R, H, d]`` are live (:func:`live_first`), row ``i``'s
    state is ``state[slot[i]]`` (``[slots, H, d, d]`` float32; the live
    rows' slots are distinct), read as zeros where ``fresh[i]`` -> ``(o [R,
    H, d], state)``; a row past ``n`` comes back zero, and no other slot's
    state is read or written.  The Pallas kernel on a TPU, else the twin;
    recorded under :data:`KERNEL_STEP`."""
    d = q.shape[-1]
    route = routes.take(KERNEL_STEP, eligible=d == _LANES,
                        interpret=interpret, pallas="pallas-linear-step")
    if route == routes.XLA:
        return decode_step_twin(q, k, v, log_decay, state, slot, fresh, n)
    return _step(q, k, v, _lam_lanes(log_decay), state, slot, fresh, n,
                 interpret=interpret)
