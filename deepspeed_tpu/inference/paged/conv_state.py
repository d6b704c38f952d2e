"""Short-convolution state: the cache kind of a gated short-convolution
layer (``models/lfm2_moe.py``), which keeps the last ``conv_L_cache - 1``
inputs of its convolution and no K/V: a ``[max_seqs, rows, width]`` leaf under
``tree.py:SLOT_STATE``, indexed by slot, that ``KVState`` owns as it owns
pages."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.paged.kv_pages import kv_lane_dim
from deepspeed_tpu.inference.paged.tree import (SLOT_STATE, SlotState,
                                                state_bytes_per_seq)


def conv_layers(cfg) -> int:
    """The ``"conv"`` entries of a config's ``layer_types`` (it may name
    none)."""
    return sum(t == "conv" for t in getattr(cfg, "layer_types", ()))


def ragged_short_conv(mdl, u: jax.Array, taps: jax.Array, pos: jax.Array,
                      ragged_meta: Dict[str, jax.Array]) -> jax.Array:
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
    state's update makes anyway) and added to the first ``K - 1`` rows of
    each chunk, the only ones that reach behind it.  A copy of the state a
    TOKEN (``[T, K - 1, C]``) is never built there
    (``tests/unit/inference/test_conv_state.py`` reads the jaxpr for it).
    Where it has no more rows than sequences (a decode program) a row IS a
    sequence, and each row selects its taps from the state as it is made.
    """
    T, C = u.shape
    K = taps.shape[1]
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    state = mdl.variable("cache", SLOT_STATE, jnp.zeros, (S, K - 1, C),
                         u.dtype)
    seq_slot = ragged_meta.get("seq_slot")
    if seq_slot is None:
        seq_slot = jnp.arange(S, dtype=jnp.int32)
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
    q_len = cu[1:] - cu[:-1]
    last = cu[1:] - 1                                 # its last row
    live = ragged_meta.get("live")
    seq_live = (q_len > 0) & (jnp.arange(S) < ragged_meta["num_seqs"][0])
    if live is not None:
        seq_live = seq_live & jnp.take(live, jnp.clip(last, 0, T - 1))
    old_j = jnp.take(state.value, seq_slot, axis=0)   # [S, K - 1, C]
    if not by_row:
        # the boundary: row r < K - 1 of sequence j's chunk lacks its taps
        # d > r, which lie in row K - 1 - (d - r) of the slot's state (row
        # i of it zero where it lies before position 0): S (K - 1) rows,
        # added where the chunk has a row r and dropped where it has not
        first = cu[:-1]
        i = jnp.arange(K - 1, dtype=jnp.int32)
        start = jnp.take(pos, jnp.clip(first, 0, T - 1))
        carried = jnp.where((start[:, None] + i >= K - 1)[:, :, None],
                            old_j, 0).astype(jnp.float32)
        lacks = jnp.stack([sum(
            carried[:, K - 1 - d + r] * w[:, K - 1 - d]
            for d in range(r + 1, K)) for r in range(K - 1)], axis=1)
        dest = jnp.where(i < q_len[:, None], first[:, None] + i, T)
        c = c.at[dest.reshape(-1)].add(lacks.reshape(-1, C), mode="drop")
    # the new state of sequence j: the last K - 1 rows of (state, chunk)
    rows = []
    for i in range(K - 1):                            # new row i, oldest 0
        d = K - 2 - i                                 # steps back from last
        from_chunk = jnp.take(u, jnp.clip(last - d, 0, T - 1), axis=0)
        from_state = jnp.take_along_axis(
            old_j, jnp.clip(K - 1 - d + q_len - 1, 0, K - 2)[:, None, None],
            axis=1)[:, 0]
        rows.append(jnp.where((q_len > d)[:, None], from_chunk, from_state))
    new = jnp.stack(rows, axis=1)
    state.value = state.value.at[
        jnp.where(seq_live, seq_slot, S)].set(new, mode="drop")
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
