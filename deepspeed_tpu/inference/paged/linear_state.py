"""Linear-attention state: the cache kind of a lightning-attention layer
(``models/minicpm_sala.py``), which keeps a decaying ``d x d`` float32 matrix
a head instead of keys and values: ``[max_seqs, H, d, d]`` under
``tree.py:SLOT_STATE`` with ``conv_state.py``'s contract
(``ops/linear_attention.py``): 2 MiB a layer a sequence at 32 heads of 128.

Inside :func:`ragged_linear_attention` a fused tick's rows are 2-D, ``[T, H *
d]`` (rows down, a head a lane block), from the first line to the last, the
way the projections leave them and ``o_proj`` takes them: on the chip every
reshape to ``[T, H, d]`` or back is two relayout copies, a transpose to heads
first a third, and a scatter back writes a row at a time.  The ``[T, H, d]``
of the signature is undone at the door and restored on the last line, so XLA
computes the model's per-head norms where the rows lie.  And a tick whose
chunks start on row tiles is scanned WHERE ITS ROWS LIE
(``ops/linear_attention.py:scan_tick``): the gathers to tiles and back are
the boundary tick's alone."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.paged.tree import (SLOT_STATE, SlotState,
                                                _one_row_seqs)
from deepspeed_tpu.ops import linear_attention, routes

# the mixers of a model whose config names them (models/minicpm_sala.py)
LINEAR_MIXER = "lightning-attn"


def linear_layers(cfg) -> int:
    """The ``"lightning-attn"`` entries of a config's ``mixer_types`` (it may
    name none)."""
    return sum(t == LINEAR_MIXER for t in getattr(cfg, "mixer_types", ()))


def ragged_linear_attention(mdl, q: jax.Array, k: jax.Array, v: jax.Array,
                            log_decay: jax.Array, pos: jax.Array,
                            ragged_meta: Dict[str, jax.Array]) -> jax.Array:
    """Decaying linear attention over a packed ragged token batch, inside a
    flax module ``mdl``: ``q, k, v [T, H, d]`` (normed, rotated, scaled),
    ``log_decay [H]``, ``pos [T]`` -> ``o [T, H, d]`` with ``S_t = l S_(t-1)
    + k_t^T v_t``, ``o_t = q_t S_t`` along each sequence.

    The state is ``cache/slot_state [max_seqs, H, d, d]`` float32 with
    :func:`ragged_short_conv`'s contract: a sequence's slot is
    ``ragged_meta["seq_slot"][j]`` by packed sequence number (a decode
    program leaves it out: row ``s`` IS slot ``s``); every sequence with a
    live row in this call leaves its state there and no other slot's
    changes; a sequence whose first row here is at position 0 reads zeros
    whatever its slot held.

    A decode program (one query a slot) takes the one-token step
    (``ops/linear_attention.py:decode_step``: live rows first, each on its
    slot's state where it lies; an idle slot's state does not move).  Every
    other batch (a fused tick) is separated by ``cu_q_lens``: sequences of
    two rows or more take the chunked scan over tiles of their own
    (:func:`~deepspeed_tpu.ops.linear_attention.tile_layout`), sequences of
    one row the same one-token step; the two write disjoint rows and
    slots.  A tick's rows stay ``[T, H * d]`` all the way (a head a lane
    block, as the projections leave them and ``o_proj`` takes them), and
    where the tick's chunks start on row tiles they ARE the scan's tiles
    and nothing moves (``linear_attention.scan_tick``: the plan of a tick
    lays whole-tile chunks from row 0, ``ragged_engine.py:_pack_tick``);
    else one gather an operand to the tiles' rows, one gather back.  No
    transpose to heads first either way."""
    T, H, d = q.shape
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    state = mdl.variable("cache", SLOT_STATE, jnp.zeros, (S, H, d, d),
                         jnp.float32)
    la = linear_attention
    take = lambda a, rows: jnp.take(a, rows, axis=0)

    def one_token(rows, slots, on, state_now, pick=take):
        """The one-token step for the sequences where ``on`` holds: their
        rows of q, k, v (by ``pick``), their slots' states in place."""
        order, n = la.live_first(on)
        rows, slots = take(rows, order), take(slots, order)
        o, new = la.decode_step(
            pick(q, rows), pick(k, rows), pick(v, rows), log_decay,
            state_now, slots, take(pos, rows) == 0, n)
        return rows, jnp.arange(S) < n[0], o, new

    if ragged_meta.get("one_query_a_slot") is True:
        live = ragged_meta.get("live")
        live = jnp.ones((S,), bool) if live is None else live
        slots = jnp.arange(S, dtype=jnp.int32)
        rows, on, o, state.value = one_token(slots, slots, live, state.value)
        return jnp.zeros_like(q).at[jnp.where(on, rows, T)].set(
            o, mode="drop")
    seq_slot = ragged_meta.get("seq_slot")
    if seq_slot is None:
        seq_slot = jnp.arange(S, dtype=jnp.int32)
    one, first = _one_row_seqs(ragged_meta, T)
    first_pos = jnp.take(pos, first)
    lay = la.tile_layout(cu, ragged_meta["num_seqs"], first_pos, T,
                         la.CHUNK)
    # rows as the projections leave them, ``[T, H * d]``, a head a lane
    # block, from here to the last line: no operand is re-tiled by head
    flat = lambda a: a.reshape(a.shape[0], -1)
    o, st = la.scan_tick(
        lambda rows, _, st, *tiles: la.chunk_scan(*rows, log_decay, st,
                                                  *tiles),
        lay, seq_slot, T, la.CHUNK, (flat(q), flat(k), flat(v)), (),
        state.value)
    # the one-row sequences, each on its slot's state where it lies
    live = ragged_meta.get("live")
    if live is not None:
        one = jnp.logical_and(one, jnp.take(live, first))
    by_row = lambda a, rows: take(flat(a), rows).reshape((S,) + a.shape[1:])
    rows, on, o_one, state.value = one_token(first, seq_slot, one, st, by_row)
    o = o.at[jnp.where(on, rows, T)].set(flat(o_one), mode="drop")
    return o.reshape(T, H, d)


class LinearState(SlotState):
    """The linear-attention layers of a model."""

    def kernels(self, route):
        return routes.taken(prefix=linear_attention.KERNEL)

    def stages(self, st, cache, cfg, kernels, pages_peak):
        return {"linear_state": {**self.span_attrs(cache),
                                 "state_rows": st.state_rows}}
