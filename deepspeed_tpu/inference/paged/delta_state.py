"""Gated-delta-rule state: the cache kind of a ``linear_attention`` layer
(``models/olmo_hybrid.py``), which keeps TWO per-slot leaves and no K/V, each
under ``tree.py:SLOT_STATE`` in a module of its own: the last ``taps - 1``
inputs of the short convolution before q, k and v (``conv_state.py``'s leaf
and function, reused as they are: ``[max_seqs, 3, 11520]`` bf16 at the
published widths, 69,120 bytes a layer a sequence) and a ``dk x dv`` float32
matrix a head, corrected token by token (``ops/gated_delta_rule.py``):
``[max_seqs, dk, heads * dv]``, heads along the lanes, 2,211,840 bytes a layer
a sequence at 30 heads of 96 x 192 and stored as counted (``[.., heads, 96,
192]`` would be stored as ``[.., 96, 256]``, 2,949,120).

Inside :func:`ragged_gated_delta` a fused tick's rows are 2-D, ``[T, H * d]``
(rows down, a head a lane block), from the first line to the last, the way
the projections leave them and ``o_proj`` takes them, as in
``linear_state.py:ragged_linear_attention`` and for its reason: on the chip
every reshape to ``[T, H, d]`` or back is two relayout copies, a transpose to
heads first a third, and a scatter back writes a row at a time.  And a tick
whose chunks start on row tiles is scanned WHERE ITS ROWS LIE
(``ops/linear_attention.py:scan_tick``): the gathers to tiles and back are
the boundary tick's alone."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paged.kv_pages import kv_heads_stored
from deepspeed_tpu.inference.paged.tree import (MATRIX_STATE_REFUSES,
                                                SLOT_STATE,
                                                SLOT_STATE_REFUSES, SlotState,
                                                _one_row_seqs,
                                                state_bytes_per_seq)
from deepspeed_tpu.ops import (gated_delta_rule, linear_attention, routes,
                               short_conv)

# the layers of a model whose config names them (models/olmo_hybrid.py)
DELTA_LAYER = "linear_attention"


def delta_layers(cfg) -> int:
    """The ``"linear_attention"`` entries of a config's ``layer_types`` (it
    may name none)."""
    return sum(t == DELTA_LAYER for t in getattr(cfg, "layer_types", ()))


def ragged_gated_delta(mdl, q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, b: jax.Array, pos: jax.Array,
                       ragged_meta: Dict[str, jax.Array],
                       rule=gated_delta_rule) -> jax.Array:
    """The gated delta rule over a packed ragged token batch, inside a flax
    module ``mdl``: ``q, k [T, H, dk]`` (convolved, normed, scaled), ``v [T,
    H, dv]``, ``g, b [T, H]`` float32 (the log of a token's decay; the
    correction's strength), ``pos [T]`` -> ``o [T, H, dv]`` with ``S_t = a_t
    (I - b_t k_t k_t^T) S_(t-1) + b_t k_t v_t^T``, ``o_t = S_t^T q_t`` along
    each sequence.

    The state is ``cache/slot_state [max_seqs, dk, H * dv]`` float32 with
    :func:`~.conv_state.ragged_short_conv`'s contract: a sequence's slot is
    ``ragged_meta["seq_slot"][j]`` by packed sequence number (a decode
    program leaves it out: row ``s`` IS slot ``s``); every sequence with a
    live row in this call leaves its state there and no other slot's
    changes; a sequence whose first row here is at position 0 reads zeros
    whatever its slot held.

    A decode program (one query a slot) takes the one-token step
    (``ops/gated_delta_rule.py:decode_step``: live rows first, each on its
    slot's state where it lies; an idle slot's state does not move).  Every
    other batch (a fused tick) is separated by ``cu_q_lens``: sequences of
    two rows or more take the chunked scan over tiles of their own
    (``ops/linear_attention.py:tile_layout``), sequences of one row the same
    one-token step; the two write disjoint rows and slots.  A tick's rows
    stay ``[T, H * d]`` all the way (a head a lane block, as the projections
    leave them and ``o_proj`` takes them), and where the tick's chunks start
    on row tiles they ARE the scan's tiles and nothing moves
    (``linear_attention.scan_tick``); else one gather an operand to the
    tiles' rows, one gather back.  No transpose to heads first either way.

    ``rule``: the module whose ``chunk_scan``, ``decode_step`` and ``CHUNK``
    are the rule's two forms (``ops/kda.py`` for a decay that is a vector a
    key channel: ``g [T, H * dk]``, ``kda_state.py``)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    state = mdl.variable("cache", SLOT_STATE, jnp.zeros, (S, dk, H * dv),
                         jnp.float32)
    gd, la = rule, linear_attention
    take = lambda a, rows: jnp.take(a, rows, axis=0)
    g, b = g.astype(jnp.float32), b.astype(jnp.float32)

    def one_token(rows, slots, on, state_now, pick=take):
        """The one-token step for the sequences where ``on`` holds: their
        rows of q, k, v (by ``pick``) and the gates, their slots' states in
        place."""
        order, n = la.live_first(on)
        rows, slots = take(rows, order), take(slots, order)
        o, new = gd.decode_step(
            pick(q, rows), pick(k, rows), pick(v, rows), take(g, rows),
            take(b, rows), state_now, slots, take(pos, rows) == 0, n)
        return rows, jnp.arange(S) < n[0], o, new

    if ragged_meta.get("one_query_a_slot") is True:
        live = ragged_meta.get("live")
        live = jnp.ones((S,), bool) if live is None else live
        slots = jnp.arange(S, dtype=jnp.int32)
        rows, on, o, state.value = one_token(slots, slots, live, state.value)
        return jnp.zeros((T, H, dv), v.dtype).at[
            jnp.where(on, rows, T)].set(o, mode="drop")
    seq_slot = ragged_meta.get("seq_slot")
    if seq_slot is None:
        seq_slot = jnp.arange(S, dtype=jnp.int32)
    one, first = _one_row_seqs(ragged_meta, T)
    first_pos = jnp.take(pos, first)
    lay = la.tile_layout(cu, ragged_meta["num_seqs"], first_pos, T, gd.CHUNK)
    # rows as the projections leave them, ``[T, H * d]``, a head a lane
    # block, from here to the last line: no operand is re-tiled by head
    flat = lambda a: a.reshape(a.shape[0], -1)
    # a tile row that holds no token: b = 0, and g = 0 where it is a number
    # a head; a ``g`` a key channel wide is a row like q's, which the rule's
    # scan zeroes past a tile's ``len`` itself (``ops/kda.py``)
    wide = g.shape[1] != H

    def scan(rows, gates, st, *tiles):
        q_t, k_t, v_t, g_t = rows if wide else rows + gates[:1]
        return gd.chunk_scan(q_t, k_t, v_t, g_t, gates[-1], st, *tiles)

    o, st = la.scan_tick(
        scan, lay, seq_slot, T, gd.CHUNK,
        (flat(q), flat(k), flat(v)) + ((g,) if wide else ()),
        (b,) if wide else (g, b), state.value)
    # the one-row sequences, each on its slot's state where it lies
    live = ragged_meta.get("live")
    if live is not None:
        one = jnp.logical_and(one, jnp.take(live, first))
    by_row = lambda a, rows: take(flat(a), rows).reshape((S,) + a.shape[1:])
    rows, on, o_one, state.value = one_token(first, seq_slot, one, st, by_row)
    o = o.at[jnp.where(on, rows, T)].set(flat(o_one), mode="drop")
    return o.reshape(T, H, dv)


def _levels_halved(what: str, chunk: int, first: int) -> Dict[str, int]:
    """``<what>_levels`` of a scan's doubling at its tile and how many of
    them multiply half the tile, read from the function the kernel unrolls
    (``gated_delta_rule.levels``)."""
    halved, of = gated_delta_rule.levels_halved(chunk, first)
    return {f"{what}_levels_compacted": halved, f"{what}_levels": of}


class DeltaState(SlotState):
    """The gated-delta-rule layers of a model whose others keep K/V pages."""

    # the module of the rule's two kernels (``kda_state.py``: ``ops/kda.py``)
    rule = gated_delta_rule

    def refusals(self, model):
        model_is, _ = super().refusals(model)
        return model_is, {**SLOT_STATE_REFUSES, **MATRIX_STATE_REFUSES}

    def span_attrs(self, cache):
        return {**super().span_attrs(cache), "delta_layers": self.layers}

    def counts(self, st, positions, q_lens=None):
        counts = super().counts(st, positions, q_lens)
        if q_lens is not None:
            # the sequences of a fused tick that take the one-token step
            counts["one_row_seqs"] = int((np.asarray(q_lens) == 1).sum())
        return counts

    def kernels(self, route):
        # the rule's two kernels, and the convolution before them
        return {**routes.taken(prefix=self.rule.KERNEL),
                **routes.taken(prefix=short_conv.KERNEL)}

    def stages(self, st, cache, cfg, kernels, pages_peak):
        # what a sequence's state costs as stored and as counted (a matrix
        # a head and the convolution's rows, a layer), and the heads a token
        # costs the pool of the model's other layers
        H, dk, dv = (int(cfg.linear_num_key_heads),
                     int(cfg.linear_key_head_dim),
                     int(cfg.linear_value_head_dim))
        conv_rows = (int(cfg.linear_conv_kernel_dim) - 1) * H * (2 * dk + dv)
        counted = self.layers * (H * dk * dv * 4 + conv_rows
                                 * jnp.dtype(cfg.dtype).itemsize)
        heads = kv_heads_stored(int(cfg.num_key_value_heads))
        return {"delta_state": {
            "state_layers": self.layers,
            "kv_layers": self.of - self.layers,
            "state_bytes_per_seq": state_bytes_per_seq(cache),
            "state_bytes_per_seq_counted": int(counted),
            "state_rows": st.state_rows,
            "kv_heads_stored": heads,
            "kv_bytes_per_token": (self.of - self.layers) * 2 * heads
            * int(cfg.head_dim) * jnp.dtype(cfg.dtype).itemsize,
            **_levels_halved("solve", gated_delta_rule.CHUNK, 2),
            "routes": dict(kernels)}}
