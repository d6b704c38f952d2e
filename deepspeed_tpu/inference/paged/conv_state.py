"""Short-convolution state: the cache kind of a gated short-convolution
layer (``models/lfm2_moe.py``), which keeps the last ``conv_L_cache - 1``
inputs of its convolution and no K/V: a ``[max_seqs, rows, width]`` leaf under
``tree.py:SLOT_STATE``, indexed by slot, that ``KVState`` owns as it owns
pages."""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.paged.kv_pages import kv_lane_dim
from deepspeed_tpu.inference.paged.tree import (SLOT_STATE, SlotState,
                                                state_bytes_per_seq)
from deepspeed_tpu.ops import short_conv


# what follows the taps a head: (head_dim, eps, a scale a part or None)
Unit = Tuple[int, float, Sequence[Optional[float]]]


def conv_layers(cfg) -> int:
    """The ``"conv"`` entries of a config's ``layer_types`` (it may name
    none)."""
    return sum(t == "conv" for t in getattr(cfg, "layer_types", ()))


def _chunks(ragged_meta, T: int):
    """By packed sequence: its rows in this call, its last row, and whether
    it is live (a row here, under ``num_seqs``, its last row ``live``)."""
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    q_len = cu[1:] - cu[:-1]
    last = cu[1:] - 1                                 # its last row
    live = ragged_meta.get("live")
    seq_live = (q_len > 0) & (jnp.arange(S) < ragged_meta["num_seqs"][0])
    if live is not None:
        seq_live = seq_live & jnp.take(live, jnp.clip(last, 0, T - 1))
    return q_len, last, seq_live


def _leave_state(state, rows_of, old_j, seq_slot, chunks, T: int) -> None:
    """Every live sequence leaves the last ``K - 1`` rows of (its slot's
    state ``old_j [S, K - 1, C]``, its chunk) in its slot; ``rows_of(idx)``:
    the input's rows ``idx``, ``[n, C]``; ``chunks``: :func:`_chunks`'."""
    q_len, last, seq_live = chunks
    S, K = old_j.shape[0], old_j.shape[1] + 1
    rows = []
    for i in range(K - 1):                            # new row i, oldest 0
        d = K - 2 - i                                 # steps back from last
        from_chunk = rows_of(jnp.clip(last - d, 0, T - 1))
        from_state = jnp.take_along_axis(
            old_j, jnp.clip(K - 1 - d + q_len - 1, 0, K - 2)[:, None, None],
            axis=1)[:, 0]
        rows.append(jnp.where((q_len > d)[:, None], from_chunk, from_state))
    new = jnp.stack(rows, axis=1)
    state.value = state.value.at[
        jnp.where(seq_live, seq_slot, S)].set(new, mode="drop")


def _lacks(old_j, w, pos, cu, q_len, T: int):
    """What row ``r < K - 1`` of sequence ``j``'s chunk lacks, ``[S, K - 1,
    C]`` float32: its taps ``d > r``, which lie in row ``K - 1 - (d - r)`` of
    the slot's state ``old_j`` (row ``i`` of it zero where it lies before
    position 0); and the row it belongs in, ``[S, K - 1]`` (``T``: the chunk
    has no row ``r``)."""
    K = w.shape[1]
    first = cu[:-1]
    i = jnp.arange(K - 1, dtype=jnp.int32)
    start = jnp.take(pos, jnp.clip(first, 0, T - 1))
    carried = jnp.where((start[:, None] + i >= K - 1)[:, :, None],
                        old_j, 0).astype(jnp.float32)
    lacks = jnp.stack([sum(
        carried[:, K - 1 - d + r] * w[:, K - 1 - d]
        for d in range(r + 1, K)) for r in range(K - 1)], axis=1)
    return lacks, jnp.where(i < q_len[:, None], first[:, None] + i, T)


def after_taps(c: jax.Array, silu: bool, unit: Optional[Unit], n: int = 0
               ) -> jax.Array:
    """What follows the taps row by row, in XLA: SiLU in float32, and part
    ``n``'s heads brought to unit length and scaled."""
    if silu:
        c = jax.nn.silu(c.astype(jnp.float32)).astype(c.dtype)
    if unit is None or unit[2][n] is None:
        return c
    dim, eps, scales = unit
    a = c.reshape(c.shape[0], -1, dim).astype(jnp.float32)
    a = a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + eps)
    return (a * scales[n]).astype(c.dtype).reshape(c.shape)


def _one_pass(parts, taps, pos, cu, q_len, old_j, *, silu: bool,
              unit: Optional[Unit], interpret: bool) -> Tuple[jax.Array, ...]:
    """A fused tick's rows in ONE pass a part (``ops/short_conv.py:rows``),
    and the ``S (K - 1)`` rows that reach behind their chunk computed whole
    first (their taps inside the chunk in the kernel's order, then what
    the slot carries, through the same roundings), which the kernel sets
    into its bf16 output: no float32 ``[T, C]`` is written for a scatter to
    add to."""
    T, K = parts[0].shape[0], taps.shape[1]
    w = taps.astype(jnp.float32)
    lacks, dest = _lacks(old_j, w, pos, cu, q_len, T)
    S = dest.shape[0]
    # the rows past the last sequence's count as its own (as in the twin):
    # where it has fewer than K - 1, they lack nothing and read no further
    # back than its first
    i = jnp.arange(K - 1, dtype=jnp.int32)
    lacks = jnp.where((i < q_len[:, None])[:, :, None], lacks, 0)
    dest = dest.at[S - 1].set(cu[S - 1] + i)
    lacks, dest = lacks.reshape(S * (K - 1), -1), dest.reshape(-1)
    # tap d of such a row: row dest - d of the tick where the chunk reaches
    # that far (its row r within the chunk is at least d)
    d = jnp.arange(K, dtype=jnp.int32)
    rows = jnp.clip(dest[:, None] - d, 0, T - 1).reshape(-1)
    near = jnp.concatenate([jnp.take(p, rows, axis=0) for p in parts],
                           axis=-1).reshape(dest.shape[0], K, -1)
    near = jnp.where((jnp.tile(i, S)[:, None] >= d)[:, :, None], near, 0)
    c = near[:, 0].astype(jnp.float32) * w[:, K - 1]
    for t in range(1, K):
        c = c + near[:, t].astype(jnp.float32) * w[:, K - 1 - t]
    c = after_taps((c + lacks).astype(parts[0].dtype), silu, None)
    dim, eps, scales = unit or (None, 0.0, (None,) * len(parts))
    outs, lo = [], 0
    for n, p in enumerate(parts):
        hi = lo + p.shape[1]
        outs.append(short_conv.rows(
            p, taps[lo:hi], after_taps(c[:, lo:hi], False, unit, n), dest,
            silu=silu, head_dim=dim if scales[n] is not None else None,
            scale=scales[n], eps=eps, interpret=interpret))
        lo = hi
    return tuple(outs)


def ragged_short_conv(mdl, u: Union[jax.Array, Tuple[jax.Array, ...]],
                      taps: jax.Array, pos: jax.Array,
                      ragged_meta: Dict[str, jax.Array], *,
                      silu: bool = False, unit: Optional[Unit] = None,
                      interpret: bool = False):
    """Causal depthwise convolution of ``K = taps.shape[1]`` taps over a
    packed ragged token batch, inside a flax module ``mdl``.

    ``u [T, C]``: the convolution's input, row ``t`` a token of the
    sequence ``cu_q_lens`` puts it in, at position ``pos [T]`` of it;
    ``taps [C, K]``, tap ``K - 1`` on the token itself.  Returns ``c [T,
    C]`` with ``c_t = sum_k taps[:, k] * u_{t - (K - 1 - k)}`` where ``u``
    before a sequence's first token (position 0) is zero, before a CHUNK's
    first token is the sequence's carried state, and no row of another
    sequence is ever read.

    The state is ``cache/slot_state [max_seqs, K - 1, C]``: the last
    ``K - 1`` inputs of the sequence in slot ``s``, oldest first.  A
    sequence's slot is ``ragged_meta["seq_slot"][j]`` by packed sequence
    number (a decode program, one query a slot, leaves it out: row ``s``
    IS slot ``s``).  Every sequence with a live row in this call leaves
    its last ``K - 1`` inputs there; no other slot's state changes.  A
    chunk at position 0 reads zeros whatever its slot held, so a slot's
    previous tenant cannot leak.

    What is built where a call has more rows than sequences (a fused
    tick): the ``T`` rows are convolved from ``u`` shifted, in one pass, and
    the state is gathered a SEQUENCE (``[S, K - 1, C]``, the gather the
    state's update makes anyway) for the first ``K - 1`` rows of each chunk,
    the only ones that reach behind it.  A copy of the state a TOKEN (``[T,
    K - 1, C]``) is never built there
    (``tests/unit/inference/test_conv_state.py`` reads the jaxpr for it).
    Where a call has no more rows than sequences (a decode program) a row
    IS a sequence, and each row selects its taps from the state as it is
    made.

    ONE PASS (``ops/short_conv.py``, PR 67): ``u`` may be the input's PARTS
    side by side, as their projections leave them (``(q~, k~, v~)``:
    concatenated they would be a pass of their own), each ``[T, C_n]``
    bf16.  The taps are then the kernel's, which writes bf16 rows, and the
    ``S (K - 1)`` rows that reach behind their chunk are computed whole and
    set into them (:func:`_one_pass`) where the lines below add what they
    lack to the float32 sums.  The kernel's pass holds what FOLLOWS the taps
    too: the parts come back a tuple, through SiLU where ``silu``, and where
    ``unit = (head_dim, eps, scales)`` part ``n``'s heads at unit length
    times ``scales[n]`` (``None``: as it is).  A caller hands parts where
    ``short_conv.route`` said the kernel runs (a fused tick on the chip),
    and keeps its own lines after ``c`` where it said the twin does: a
    decode program is then the text it was.  The state every sequence
    leaves is the same either way."""
    parts = u if isinstance(u, tuple) else (u,)
    T, C = parts[0].shape[0], sum(p.shape[1] for p in parts)
    K = taps.shape[1]
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    state = mdl.variable("cache", SLOT_STATE, jnp.zeros, (S, K - 1, C),
                         parts[0].dtype)
    seq_slot = ragged_meta.get("seq_slot")
    if seq_slot is None:
        seq_slot = jnp.arange(S, dtype=jnp.int32)
    if isinstance(u, tuple):
        chunks = _chunks(ragged_meta, T)
        old_j = jnp.take(state.value, seq_slot, axis=0)
        outs = _one_pass(u, taps, pos, cu, chunks[0], old_j, silu=silu,
                         unit=unit, interpret=interpret)
        _leave_state(state, lambda idx: jnp.concatenate(
            [jnp.take(p, idx, axis=0) for p in u], axis=-1),
            old_j, seq_slot, chunks, T)
        return outs
    assert not silu and unit is None, "the twin ends at the taps"
    t_idx = jnp.arange(T, dtype=jnp.int32)
    seq_of_t = jnp.minimum(jnp.sum(
        (t_idx[:, None] >= cu[None, 1:]).astype(jnp.int32), axis=1), S - 1)
    off = t_idx - jnp.take(cu[:-1], seq_of_t)         # row within its chunk
    # no more rows than sequences (a decode program: every row is a chunk's
    # first): the state a row is then no larger than the state a sequence
    # and its selects fuse with the taps, where a scatter of S (K - 1) rows
    # costs more than it saves (Step 0 of PR 51, PERF.md)
    by_row = T <= S
    if by_row:
        old = jnp.take(state.value, jnp.take(seq_slot, seq_of_t), axis=0)
    w = taps.astype(jnp.float32)
    c = u.astype(jnp.float32) * w[:, K - 1]
    # the body: tap d steps back is row t - d of u where the chunk reaches
    # that far (which a position under d never does); [T + K - 1, C] is
    # never built
    for d in range(1, K):
        shifted = jnp.pad(u, ((d, 0), (0, 0)))[:T]
        if by_row:
            # else row K - 1 - (d - off) of the state
            carried = jnp.take_along_axis(
                old, jnp.clip(K - 1 - d + off, 0, K - 2)[:, None, None],
                axis=1)[:, 0]
            back = jnp.where((off >= d)[:, None], shifted, carried)
            back = jnp.where((pos >= d)[:, None], back, 0)
        else:
            back = jnp.where((off >= d)[:, None], shifted, 0)
        c = c + back.astype(jnp.float32) * w[:, K - 1 - d]
    chunks = _chunks(ragged_meta, T)
    old_j = jnp.take(state.value, seq_slot, axis=0)   # [S, K - 1, C]
    if not by_row:
        # the boundary: S (K - 1) rows, added where the chunk has a row r
        # and dropped where it has not
        lacks, dest = _lacks(old_j, w, pos, cu, chunks[0], T)
        c = c.at[dest.reshape(-1)].add(lacks.reshape(-1, C), mode="drop")
    _leave_state(state, lambda idx: jnp.take(u, idx, axis=0), old_j,
                 seq_slot, chunks, T)
    return c.astype(u.dtype)


class ConvState(SlotState):
    """The short-convolution layers of a model whose others keep K/V pages."""

    def stages(self, st, cache, cfg, kernels, pages_peak):
        # a model with layers of two kinds: which keep K/V, which keep
        # per-slot state, what a sequence's state costs, how its router
        # scores and which routes its attention takes
        return {"hybrid": {
            "kv_layers": self.of - self.layers,
            "state_layers": self.layers,
            "state_bytes_per_seq": state_bytes_per_seq(cache),
            "kv_head_dim_stored": kv_lane_dim(int(getattr(cfg, "head_dim", 0))),
            "routing": ("sigmoid+bias" if getattr(
                cfg, "use_expert_bias", False) else "sigmoid"),
            "bias_swaps": st.moe_bias_swaps,
            "attention_routes": dict(kernels)}}
