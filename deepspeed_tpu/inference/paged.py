"""Blocked (paged) KV cache for ragged continuous batching.

TPU-native re-design of the reference's FastGen blocked-KV machinery
(``inference/v2/ragged/blocked_allocator.py:1`` ``BlockedAllocator``,
``ragged/kv_cache.py`` ``BlockedKVCache``, and the ragged attention
kernels under ``inference/v2/kernels/ragged_ops/``): KV lives in
fixed-size pages addressed through a per-sequence page table, so device
memory scales with tokens in flight — not ``max_seqs x max_seq_len`` —
and one fused token batch mixes decode tokens with prefill chunks
(Dynamic SplitFuse, ``engine_v2.py:107``).

Device side, attention over the paged cache is JAX's built-in vLLM-TPU
Pallas kernel (``jax.experimental.pallas.ops.tpu.ragged_paged_attention``)
on TPU, and :func:`ref_paged_attention` — an XLA-compilable, mask-based
equivalent of the kernel's reference math — everywhere else (CPU tests).
A decode program, which has one query row a sequence slot and says so in
its ``ragged_meta``, takes ``ops/paged_decode.py`` instead wherever that
kernel's layout holds; a fused tick, which says so too, takes
``ops/paged_prefill.py`` for its prompt chunks and the decode kernel for
its one-query rows.
The page allocator is host-side Python, like the reference's scheduler
tier.

Layout contract (the kernel's): pages are
``[num_pages, page_size, 2 * Hkv, Dh]`` with K at even combined-head
indices and V at odd; a tick's new K/V rows are scattered into the flat
page buffer BEFORE attention, and ``kv_lens`` includes this tick's
tokens.  Page 0 is reserved as the trash page: padding tokens write
there, no sequence is ever allocated it.

A model whose layers run ``U`` times a token (``models/ouro.py``) needs
``U`` K/V slots a layer.  Its layer's pool is ``[num_pages, U, page_size,
2 * Hkv, Dh]``: a page holds all ``U`` slots of its tokens, so whatever
copies, spills or shares ``leaf[page]`` moves them together and the
allocator counts pages as before.  Pass ``u`` (``ragged_meta["kv_slot"]``)
reads and writes the same buffer as ``[num_pages * U, ...]`` at page
``p * U + u``; the kernels see an ordinary pool.

Heads of 64 (``models/lfm2_moe.py``) are stored **zero-padded to 128**
(:func:`kv_lane_dim`): both Pallas kernels are built for 128 lanes, a
padded key adds ``0 * 0`` to a score and a padded value column is sliced
off the output, so the result is exact and the softmax scale stays the
true head's.  A token then costs the pool ``2 * Hkv * 128`` values a K/V
layer, twice its unpadded size.

A **latent-attention** layer (``models/glm4_moe_lite.py``) keeps ONE row a
token and no heads: the normalised compressed vector beside the rotated key
its heads share, ``W = kv_lora_rank + qk_rope_head_dim`` wide
(:func:`latent_width`).  Its pool is ``[num_pages, page_size, W]`` under the
leaf name :data:`LATENT_PAGES`; the row is every head's key and its first
``kv_lora_rank`` columns the value (:func:`latent_update_and_attend`,
``ops/latent_attention.py``).  It leads with the page dim like every pool,
so whatever copies, spills or shares ``leaf[page]`` moves it unchanged, and
a page's bytes are its leaf's own.  ``W`` is the row **zero-padded to whole
lane tiles** (576 -> 640 at the published widths: 1,280 bytes a token a
layer in bf16, a ninth over the unpadded 1,152): the chip lays a minor dim
out in tiles of 128 lanes whatever the program declares, so an unpadded
pool would take the same memory, and Mosaic moves whole tiles only (it
refuses a page copy 576 wide).  A padded key column adds ``0 * 0`` to a
score and no padded column is a value, so the result is exact.

Beside its pages a sequence may own **per-slot state** that is no page:
the last ``conv_L_cache - 1`` inputs of each short-convolution layer
(:func:`ragged_short_conv`), a ``[max_seqs, rows, width]`` leaf named
:data:`SLOT_STATE` in the same ``cache`` collection, indexed by slot.
``inference/v2/kv_state.py`` tells the two kinds of leaf apart by that
name.  A **linear-attention** layer (``models/minicpm_sala.py``) keeps a
decaying ``d x d`` float32 matrix a head there instead of keys and values:
``[max_seqs, H, d, d]`` under the same name and the same contract
(:func:`ragged_linear_attention`, ``ops/linear_attention.py``): 2 MiB a
layer a sequence at 32 heads of 128.

A **block-selecting** layer of the same model keeps two pool leaves:
:data:`SPARSE_KV_PAGES` ``[num_pages, 2 * Hkv, page_size, D]`` (heads lead
the tokens: with two KV heads a slab a head is whole tiles, where ``[page,
4, D]`` would be laid out in sixteen sublanes) and :data:`CKEY_PAGES`
``[num_pages, Hkv * page_size / stride, D]`` float32, the mean key of every
window of ``kernel`` tokens, kept in the page the window starts in.  From
``dense_len`` on a query attends the ``topk`` pages its compressed scores
choose (:func:`sparse_update_and_attend`,
``ops/block_select_attention.py``).  Both lead with the page dim.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import (block_select_attention, latent_attention,
                               linear_attention, routes)
from deepspeed_tpu.ops.paged_decode import paged_decode_attention
from deepspeed_tpu.ops.paged_prefill import fused_tick_attention
from deepspeed_tpu.utils.compat import shard_map as _shard_map_compat

TRASH_PAGE = 0
_LANES = 128
# the name of a per-slot state leaf in the ``cache`` collection
SLOT_STATE = "slot_state"
# and of a latent-attention layer's pool of one row a token
LATENT_PAGES = "latent_pages"
# and of a block-selecting layer's two pools: keys and values, heads first,
# and the compressed keys its selection scores
SPARSE_KV_PAGES = "sparse_kv_pages"
CKEY_PAGES = "ckey_pages"
# the mixers of a model whose config names them (models/minicpm_sala.py)
LINEAR_MIXER = "lightning-attn"

# block sizes handed to the upstream ragged paged kernel (see ``attend``):
# a KV block of at most 512 tokens, and of at most 4096 rows of K or V
# (tokens x KV heads) — what 8 KV heads x 512 tokens hold
_RPA_KV_BLOCK_TOKENS = 512
_RPA_KV_BLOCK_HEAD_TOKENS = 4096
_RPA_QUERY_BLOCK = 32


def rpa_kv_pages_per_block(kv_heads: int, page_size: int,
                           pages_per_seq: int) -> int:
    """KV pages one block of the upstream kernel holds.  The block lives
    in scoped VMEM, double-buffered, and its bytes grow with the KV heads
    (pages are ``[P, page, 2 * Hkv, D]``): 512 tokens at up to 8 KV heads,
    256 at 16, never under one page."""
    tokens = min(_RPA_KV_BLOCK_TOKENS,
                 _RPA_KV_BLOCK_HEAD_TOKENS // max(kv_heads, 1))
    return max(1, min(pages_per_seq, tokens // page_size))

# same mask-value family as ops/flash_attention.py and the Pallas
# quantized kernel: vanishes under softmax, (mask - mask) stays exact 0
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# Host-side page allocator (reference blocked_allocator.py)
# ---------------------------------------------------------------------------

def kv_slots_per_layer(cfg) -> int:
    """K/V slots a layer holds for one token: the passes of a looped model
    (its config's ``total_ut_steps``), 1 for every other."""
    return int(getattr(cfg, "total_ut_steps", 1))


def kv_lane_dim(head_dim: int, kv_cache_dtype: str = "none") -> int:
    """Width of a K/V head AS THE POOL STORES IT: heads of 64 in a
    full-width pool are zero-padded to the kernels' 128 lanes, every other
    head is stored as it is."""
    full_width = (kv_cache_dtype or "none") == "none"
    return _LANES if head_dim == 64 and full_width else head_dim


def latent_width(cfg) -> int:
    """Width of the one row a latent-attention layer caches for a token AS
    THE POOL STORES IT: its config's ``kv_lora_rank + qk_rope_head_dim``
    zero-padded to whole 128-lane tiles (576 -> 640); 0 for a model whose
    layers keep per-head keys and values."""
    rank = getattr(cfg, "kv_lora_rank", None)
    if not rank:
        return 0
    return -(-(int(rank) + int(cfg.qk_rope_head_dim)) // _LANES) * _LANES


def state_layers(cfg) -> int:
    """Layers of a model that keep per-slot state beside the pool: the
    ``"conv"`` entries of its config's ``layer_types`` and the
    ``"lightning-attn"`` entries of its ``mixer_types``; 0 for a model
    whose config names neither (every layer holds K/V)."""
    return (sum(t == "conv" for t in getattr(cfg, "layer_types", ()))
            + sum(t == LINEAR_MIXER
                  for t in getattr(cfg, "mixer_types", ())))


def sparse_selection(cfg) -> Optional[block_select_attention.Selection]:
    """The block selection of a model whose attention layers choose their
    keys (its config's ``selection``), None for every other."""
    return getattr(cfg, "selection", None)


def sparse_key_counts(positions: np.ndarray, sel,
                      one_row: Optional[np.ndarray] = None
                      ) -> Dict[str, int]:
    """What the queries at ``positions`` ask of a block-selecting layer, a
    KV head.  Of those at or past ``dense_len``: how many they are
    (``sparse_rows``), the keys their chosen blocks hold at or before them
    (``sparse_key_tokens``) and the keys a dense pass would read
    (``sparse_visible_tokens``).  Of all of them: the keys attended, chosen
    or every earlier one, by the queries of prompt chunks
    (``attend_prefill_tokens``) and by those that are their sequence's one
    row (``one_row`` true; ``attend_decode_tokens``).  Host-side, from
    positions alone: the chosen set's SIZE is the architecture's, which
    blocks are in it the device's to say."""
    p = np.asarray(positions, np.int64).reshape(-1)
    one = (np.ones(p.shape, bool) if one_row is None
           else np.asarray(one_row, bool).reshape(-1))
    far = p >= sel.dense_len
    blocks = np.minimum(sel.topk, p // sel.block_size + 1)
    chosen = blocks * sel.block_size - (sel.block_size - 1
                                        - p % sel.block_size)
    attended = np.where(far, chosen, p + 1)
    return {"sparse_rows": int(far.sum()),
            "sparse_key_tokens": int(chosen[far].sum()),
            "sparse_visible_tokens": int((p[far] + 1).sum()),
            "attend_prefill_tokens": int(attended[~one].sum()),
            "attend_decode_tokens": int(attended[one].sum())}

def kv_layers(cfg) -> int:
    """Layers of a model that keep keys and values in the pool."""
    return int(cfg.num_hidden_layers) - state_layers(cfg)


def is_slot_state(path) -> bool:
    """Is the cache leaf at ``path`` (a ``tree_flatten_with_path`` key
    path) per-slot state and not a page pool?"""
    return any(getattr(k, "key", None) == SLOT_STATE for k in path)


def split_cache(cache):
    """``(pools, states)``: the leaves of a cache tree that are page pools
    (leading dim: pages) and those that are per-slot state (leading dim:
    slots), each in the tree's own leaf order."""
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    return ([l for p, l in flat if not is_slot_state(p)],
            [l for p, l in flat if is_slot_state(p)])


def map_cache(cache, pool_fn=None, state_fn=None):
    """``cache`` with ``pool_fn`` applied to every pool leaf and
    ``state_fn`` to every per-slot state leaf (None: left as it is)."""
    keep = lambda l: l
    pool_fn, state_fn = pool_fn or keep, state_fn or keep
    return jax.tree_util.tree_map_with_path(
        lambda p, l: state_fn(l) if is_slot_state(p) else pool_fn(l), cache)


def ragged_short_conv(mdl, u: jax.Array, taps: jax.Array, pos: jax.Array,
                      ragged_meta: Dict[str, jax.Array]) -> jax.Array:
    """Causal depthwise convolution of ``K = taps.shape[1]`` taps over a
    packed ragged token batch, inside a flax module ``mdl``.

    ``u [T, C]``: the convolution's input, row ``t`` a token of the
    sequence ``cu_q_lens`` puts it in, at position ``pos [T]`` of it;
    ``taps [C, K]``, tap ``K - 1`` on the token itself.  Returns ``c [T,
    C]`` with ``c_t = sum_k taps[:, k] * u_{t - (K - 1 - k)}`` where ``u``
    before a sequence's first token (position 0) is zero, before a CHUNK's first token is the sequence's
    carried state, and no row of another sequence is ever read.

    The state is ``cache/slot_state [max_seqs, K - 1, C]``: the last
    ``K - 1`` inputs of the sequence in slot ``s``, oldest first.  A
    sequence's slot is ``ragged_meta["seq_slot"][j]`` by packed sequence
    number (a decode program, one query a slot, leaves it out: row ``s``
    IS slot ``s``).  Every sequence with a live row in this call leaves
    its last ``K - 1`` inputs there; no other slot's state changes.  A
    chunk at position 0 reads zeros whatever its slot held, so a slot's
    previous tenant cannot leak.
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
    old = jnp.take(state.value, jnp.take(seq_slot, seq_of_t), axis=0)
    # the chunk's rows behind its carried state: [T + K - 1, C] is never
    # built; tap d steps back is row t - d of u where the chunk reaches
    # that far, else row K - 1 - (d - off) of the state
    w = taps.astype(jnp.float32)
    c = u.astype(jnp.float32) * w[:, K - 1]
    for d in range(1, K):
        shifted = jnp.pad(u, ((d, 0), (0, 0)))[:T]
        carried = jnp.take_along_axis(
            old, jnp.clip(K - 1 - d + off, 0, K - 2)[:, None, None],
            axis=1)[:, 0]
        back = jnp.where((off >= d)[:, None], shifted, carried)
        back = jnp.where((pos >= d)[:, None], back, 0)
        c = c + back.astype(jnp.float32) * w[:, K - 1 - d]
    # the new state of sequence j: the last K - 1 rows of (state, chunk)
    q_len = cu[1:] - cu[:-1]
    last = cu[1:] - 1                                 # its last row
    live = ragged_meta.get("live")
    seq_live = (q_len > 0) & (jnp.arange(S) < ragged_meta["num_seqs"][0])
    if live is not None:
        seq_live = seq_live & jnp.take(live, jnp.clip(last, 0, T - 1))
    old_j = jnp.take(state.value, seq_slot, axis=0)   # [S, K - 1, C]
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


def _seq_of_rows(cu: jax.Array, T: int) -> jax.Array:
    """The packed sequence each of ``T`` rows lies in, by ``cu_q_lens``."""
    S = cu.shape[0] - 1
    t_idx = jnp.arange(T, dtype=jnp.int32)
    return jnp.minimum(jnp.sum(
        (t_idx[:, None] >= cu[None, 1:]).astype(jnp.int32), axis=1), S - 1)


def _one_row_seqs(ragged_meta, T: int):
    """``(one [S], first [S])``: which packed sequences of a ragged batch
    have exactly one query row, and every sequence's first row."""
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    first = jnp.minimum(cu[:-1], T - 1)
    one = jnp.logical_and(jnp.arange(S) < ragged_meta["num_seqs"][0],
                          cu[1:] - cu[:-1] == 1)
    return one, first


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
    slots."""
    T, H, d = q.shape
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    state = mdl.variable("cache", SLOT_STATE, jnp.zeros, (S, H, d, d),
                         jnp.float32)
    la = linear_attention
    take = lambda a, rows: jnp.take(a, rows, axis=0)

    def one_token(rows, slots, on, state_now):
        """The one-token step for the sequences where ``on`` holds: their
        rows of q, k, v, their slots' states in place."""
        order, n = la.live_first(on)
        rows, slots = take(rows, order), take(slots, order)
        o, new = la.decode_step(
            take(q, rows), take(k, rows), take(v, rows), log_decay,
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
    tiles = lambda a: la.to_tiles(a, lay["rows"]).transpose(1, 0, 2)
    o_t, st = la.chunk_scan(
        tiles(q), tiles(k), tiles(v), log_decay, state.value,
        jnp.take(seq_slot, lay["seq"]), lay["init"], lay["len"])
    o = la.from_tiles(o_t.transpose(1, 0, 2), lay["rows"], T)
    # the one-row sequences, each on its slot's state where it lies
    live = ragged_meta.get("live")
    if live is not None:
        one = jnp.logical_and(one, jnp.take(live, first))
    rows, on, o_one, state.value = one_token(first, seq_slot, one, st)
    return o.at[jnp.where(on, rows, T)].set(o_one, mode="drop")


def _write_compressed_keys(flat_kv, ckeys, pos, seq_of_t, live,
                           page_indices, sel, Hkv: int, page: int):
    """The compressed key of every window a live row of this call
    completes: row at position ``p`` with ``(p + 1) % stride == 0`` ends
    window ``(p + 1 - kernel) / stride``, whose ``kernel`` keys are read
    back from the pool (this call's rows are in it) and whose mean goes to
    the page the window starts in."""
    T = pos.shape[0]
    S = page_indices.shape[0]
    D = flat_kv.shape[-1]
    kernel, stride, wpb = (sel.kernel_size, sel.kernel_stride,
                           sel.windows_per_block)
    done = jnp.logical_and((pos + 1) % stride == 0, pos >= kernel - 1)
    if live is not None:
        done = jnp.logical_and(done, live)
    W = T // stride + S + 1
    rows = jnp.nonzero(done, size=W, fill_value=T)[0]
    ok = rows < T
    p_w = jnp.take(pos, rows, mode="fill", fill_value=kernel - 1)
    s_w = jnp.take(seq_of_t, rows, mode="fill", fill_value=0)
    key_pos = p_w[:, None] - (kernel - 1) + jnp.arange(kernel)[None]
    pg = jnp.maximum(page_indices[s_w[:, None], key_pos // page], 0)
    g = jnp.arange(Hkv)
    src = ((pg[:, :, None] * 2 * Hkv + 2 * g[None, None]) * page
           + (key_pos % page)[:, :, None])                 # [W, kernel, Hkv]
    mean = jnp.mean(jnp.take(flat_kv, src, axis=0).astype(jnp.float32),
                    axis=1)                                 # [W, Hkv, D]
    j = (p_w - (kernel - 1)) // stride
    home = jnp.maximum(page_indices[s_w, j // wpb], 0)
    dest = (home[:, None] * Hkv + g[None]) * wpb + (j % wpb)[:, None]
    dest = jnp.where(ok[:, None], dest, ckeys.shape[0] * ckeys.shape[1])
    P = ckeys.shape[0]
    return ckeys.reshape(P * Hkv * wpb, D).at[dest.reshape(-1)].set(
        mean.reshape(-1, D), mode="drop").reshape(ckeys.shape)


def sparse_update_and_attend(mdl, q: jax.Array, k: jax.Array, v: jax.Array,
                             pos: jax.Array,
                             ragged_meta: Dict[str, jax.Array], cfg, *,
                             sm_scale: float) -> jax.Array:
    """Inside a block-selecting attention module: write this tick's keys
    and values into the layer's pool and the windows they complete into
    its compressed keys, choose each query's blocks and attend them.

    ``q [T, H, D]``, ``k, v [T, Hkv, D]`` (normed; no rotation), ``pos
    [T]`` -> ``[T, H, D]``.  As :func:`paged_update_and_attend`:
    ``kv_lens`` includes this tick's tokens, page 0 is the trash page,
    ``mutable=["cache"]`` on apply.  A query before ``dense_len`` attends
    every earlier key, one from there on the keys of its chosen blocks
    (``ops/block_select_attention.py``; scopes ``compress`` and
    ``select``).  A decode program's rows take the chosen-page kernel;
    every other batch is separated by ``cu_q_lens`` as
    :func:`ragged_linear_attention` separates it: sequences of two rows or
    more take the chunk kernel in tiles (the choice handed over a block a
    bit), sequences of one row the chosen-page kernel."""
    bsa = block_select_attention
    sel = sparse_selection(cfg).check(cfg.kv_page_size)
    T, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    P, page = cfg.kv_num_pages, cfg.kv_page_size
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    wpb = sel.windows_per_block
    pages_var = mdl.variable("cache", SPARSE_KV_PAGES, jnp.zeros,
                             (P, 2 * Hkv, page, D), k.dtype)
    ckey_var = mdl.variable("cache", CKEY_PAGES, jnp.zeros,
                            (P, Hkv * wpb, D), jnp.float32)
    page_indices = ragged_meta["page_indices"]
    S, pp = page_indices.shape
    cu = ragged_meta["cu_q_lens"]
    live = ragged_meta.get("live")
    decode = ragged_meta.get("one_query_a_slot") is True

    dest = ragged_meta["new_kv_dest"]
    combined = jnp.stack([k, v], axis=2).reshape(T * 2 * Hkv, D)
    flat_idx = (((dest // page)[:, None] * 2 * Hkv
                 + jnp.arange(2 * Hkv)[None]) * page
                + (dest % page)[:, None]).reshape(-1)
    flat = pages_var.value.reshape(P * 2 * Hkv * page, D).at[flat_idx].set(
        combined.astype(k.dtype), mode="drop")
    pages = flat.reshape(P, 2 * Hkv, page, D)
    pages_var.value = pages
    seq_of_t = (jnp.arange(S, dtype=jnp.int32) if decode
                else _seq_of_rows(cu, T))
    with jax.named_scope("compress"):
        ckeys = _write_compressed_keys(flat, ckey_var.value, pos, seq_of_t,
                                       live, page_indices, sel, Hkv, page)
        ckey_var.value = ckeys
        # every sequence's windows, in order: [S, Hkv, pp * wpb, D]
        seq_keys = jnp.take(ckeys, jnp.maximum(page_indices, 0), axis=0
                            ).reshape(S, pp, Hkv, wpb, D).transpose(
                                0, 2, 1, 3, 4).reshape(S, Hkv, pp * wpb, D)

    def one_row(q_rows, pos_rows, on):
        """One query a sequence (``[S, H, D]``) over its chosen pages."""
        with jax.named_scope("select"):
            blocks, count = bsa.block_list(
                q_rows[:, None], seq_keys, pos_rows[:, None], sel, sm_scale)
            page_list = jnp.take_along_axis(
                jnp.broadcast_to(page_indices[:, None], (S, Hkv, pp)),
                jnp.minimum(blocks[:, 0], pp - 1), axis=-1)
            count = jnp.where(on, count[:, 0], 0)
        return bsa.decode_attention(
            q_rows.reshape(S, Hkv, G, D), pages, page_list, count,
            pos_rows % page + 1, sm_scale=sm_scale).reshape(S, H, D)

    if decode:
        return one_row(q, pos, jnp.ones((S,), bool) if live is None
                       else live)
    one, first = _one_row_seqs(ragged_meta, T)
    first_pos = jnp.take(pos, first)
    lay = linear_attention.tile_layout(cu, ragged_meta["num_seqs"],
                                       first_pos, T, bsa.TILE)
    NT = lay["seq"].shape[0]
    rows = lay["rows"]
    q_t = linear_attention.to_tiles(q, rows)               # [NT*TILE, H, D]
    pos_t = linear_attention.to_tiles(pos, rows).reshape(NT, bsa.TILE)
    blocks = bsa.chunk_blocks(pp)
    with jax.named_scope("select"):
        # a block a bit, blocks down and a tile's rows across; the kernel
        # reads it in the tiles with a row at or past ``dense_len`` alone
        chosen = jax.lax.cond(
            jnp.any(jnp.logical_and(
                lay["len"] > 0, lay["pos0"] + lay["len"] > sel.dense_len)),
            lambda: jnp.pad(
                bsa.block_mask(
                    q_t.reshape(NT, bsa.TILE, H, D),
                    jnp.take(seq_keys, lay["seq"], axis=0), pos_t, sel,
                    sm_scale).transpose(2, 0, 3, 1).astype(jnp.float32),
                ((0, 0), (0, 0), (0, blocks - pp), (0, 0))),
            lambda: jnp.zeros((Hkv, NT, blocks, bsa.TILE), jnp.float32))
    y_t = bsa.chunk_attention(
        q_t.reshape(NT * bsa.TILE, Hkv, G, D).transpose(1, 2, 0, 3), pages,
        chosen, lay["seq"], lay["pos0"], lay["len"], page_indices,
        dense_len=sel.dense_len, sm_scale=sm_scale)
    y = linear_attention.from_tiles(
        y_t.transpose(2, 0, 1, 3).reshape(NT * bsa.TILE, H, D), rows, T)
    if live is not None:
        one = jnp.logical_and(one, jnp.take(live, first))
    y_one = one_row(jnp.take(q, first, axis=0), first_pos, one)
    return y.at[jnp.where(one, first, T)].set(y_one, mode="drop")


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` (ceil-div, min 1) — the single
    rounding rule shared by the allocator and the engine's page-table
    sizing."""
    return -(-max(n_tokens, 1) // page_size)


class PageAllocator:
    """Free-list page allocator over ``num_pages`` fixed-size pages.

    Page 0 is reserved (trash page for padding-token writes).  Sequences
    either reserve their worst case (``prompt + max_new_tokens``) at
    admission or — the reference's on-demand model
    (``ragged/blocked_allocator.py:1`` + ``engine_v2.py:184``
    ``can_schedule``) — take pages as they grow via :meth:`grow`, with
    the engine's scheduler providing admission backpressure and
    eviction when the pool runs dry mid-flight.

    Pages are refcounted so the prefix cache can share one physical
    page across many sequences (vLLM/SGLang copy-on-write model):

    - :meth:`allocate` / :meth:`grow` hand out pages at refcount 1 —
      never a page whose refcount is still > 0;
    - :meth:`attach` maps an already-resident page into another slot
      read-only (incref);
    - :meth:`free` is a per-page decref — the page returns to the free
      list only when the last reference drops;
    - :meth:`incref` / :meth:`decref` track references held outside any
      slot (the prefix index, spill-holds);
    - :meth:`cow` resolves a write to a shared page: a page at
      refcount 1 is already private, otherwise a fresh private page is
      granted and the old reference dropped (the device copy is the
      caller's job).
    """

    def __init__(self, num_pages: int, page_size: int):
        assert num_pages >= 2, "need at least one non-trash page"
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: List[int] = list(range(num_pages - 1, TRASH_PAGE, -1))
        self._owned: Dict[int, List[int]] = {}     # slot -> page ids
        self._ref = np.zeros(num_pages, dtype=np.int64)  # per-page refcount

    def pages_for(self, n_tokens: int) -> int:
        return pages_for(n_tokens, self.page_size)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_allocate(self, n_tokens: int) -> bool:
        return self.pages_for(n_tokens) <= len(self._free)

    def _pop_fresh(self) -> int:
        page = self._free.pop()
        assert self._ref[page] == 0, (
            f"free list held page {page} with refcount {self._ref[page]}")
        self._ref[page] = 1
        return page

    def allocate(self, slot: int, n_tokens: int) -> List[int]:
        need = self.pages_for(n_tokens)
        assert slot not in self._owned, f"slot {slot} already allocated"
        assert need <= len(self._free), "out of KV pages"
        pages = [self._pop_fresh() for _ in range(need)]
        self._owned[slot] = pages
        return pages

    def owned(self, slot: int) -> int:
        return len(self._owned.get(slot, ()))

    def owned_pages(self, slot: int) -> List[int]:
        return list(self._owned.get(slot, ()))

    def grow(self, slot: int, n_pages: int) -> List[int]:
        """Extend ``slot`` by ``n_pages`` (on-demand growth; caller
        checks ``free_pages`` first — running dry here is a scheduler
        bug, not backpressure).  Granted pages are exclusively owned
        (refcount 1): a page never leaves the free list while any other
        reference to it is live."""
        assert n_pages <= len(self._free), "out of KV pages (grow)"
        pages = [self._pop_fresh() for _ in range(n_pages)]
        self._owned.setdefault(slot, []).extend(pages)
        return pages

    def attach(self, slot: int, pages: List[int]) -> None:
        """Map already-resident ``pages`` into ``slot`` read-only
        (prefix-cache hit).  Must precede any :meth:`grow` for the slot
        so the slot's page list stays in logical-position order."""
        for p in pages:
            assert p != TRASH_PAGE and self._ref[p] >= 1, (
                f"attach of non-resident page {p} (ref={self._ref[p]})")
            self._ref[p] += 1
        self._owned.setdefault(slot, []).extend(pages)

    def take_page(self) -> int:
        """Grant one fresh page (refcount 1) to an external holder —
        the prefix index reviving a demoted entry owns its page through
        :meth:`incref`/:meth:`decref`, not through a slot."""
        assert self._free, "out of KV pages (take_page)"
        return self._pop_fresh()

    def incref(self, page: int) -> None:
        """Add an external (non-slot) reference — prefix-index entry or
        spill-hold keeping a shared page resident."""
        assert page != TRASH_PAGE and self._ref[page] >= 1, (
            f"incref of non-resident page {page}")
        self._ref[page] += 1

    def decref(self, page: int) -> None:
        assert self._ref[page] >= 1, f"decref of free page {page}"
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def cow(self, slot: int, k: int):
        """Resolve a write to ``slot``'s ``k``-th page.  Returns
        ``(old, new)``: ``old is new`` when the page was already private
        (refcount 1 — nothing to do), otherwise ``new`` is a fresh
        private page already remapped in the slot's page list and the
        caller must device-copy ``old -> new`` and update its page
        table."""
        old = self._owned[slot][k]
        if self._ref[old] == 1:
            return old, old
        assert len(self._free) >= 1, "out of KV pages (cow)"
        new = self._pop_fresh()
        self._owned[slot][k] = new
        self.decref(old)
        return old, new

    def release_pages(self, slot: int, pages: List[int]) -> None:
        """Release a specific subset of ``slot``'s pages while the slot
        stays live (partial-residency parking: the parked middle leaves
        the page list, sinks and the recent window remain).  The
        remaining pages keep their relative order, so page-table rows
        rebuilt from :meth:`owned_pages` stay position-consistent."""
        owned = self._owned[slot]
        for p in pages:
            owned.remove(p)
            self.decref(p)

    def free(self, slot: int) -> None:
        for p in self._owned.pop(slot, ()):
            self.decref(p)

    def audit(self, external: Optional[Dict[int, int]] = None
              ) -> Dict[str, int]:
        """Conservation check for the pool.  Free pages and referenced
        pages partition the non-trash pool; every page's refcount is
        covered by slot ownership plus ``external`` references (prefix
        index entries, spill-holds) when the caller supplies that map —
        i.e. each physical page's refcount equals the number of
        page-table rows referencing it plus held non-slot refs.  Raises
        ``AssertionError`` on a leak or double-grant; returns the
        counts.  The speculative-decoding rollback path keeps pages it
        over-allocated for rejected draft positions (they cover the very
        next block's writes), so accounting exactness — not
        owned==pages_for(length) minimality — is the invariant."""
        owned = [p for pages in self._owned.values() for p in pages]
        counts: Dict[int, int] = {}
        for p in owned:
            counts[p] = counts.get(p, 0) + 1
        if external:
            for p, n in external.items():
                counts[p] = counts.get(p, 0) + n
        free_set = set(self._free)
        assert len(free_set) == len(self._free), (
            f"free list duplicate: {sorted(p for p in free_set if self._free.count(p) > 1)}")
        assert TRASH_PAGE not in free_set and TRASH_PAGE not in counts, (
            "trash page entered circulation")
        ref_pages = {p for p in range(self.num_pages)
                     if self._ref[p] > 0}
        assert not (free_set & ref_pages), (
            f"page both free and referenced: {sorted(free_set & ref_pages)}")
        for p in range(1, self.num_pages):
            r = int(self._ref[p])
            c = counts.get(p, 0)
            if external is not None:
                assert r == c, (
                    f"page {p}: refcount {r} != {c} references "
                    "(page-table rows + external holds)")
            else:
                assert r >= c, (
                    f"page {p}: refcount {r} < {c} slot references")
            if r == 0:
                assert p in free_set, f"page leak: page {p} ref 0 not free"
        assert len(free_set) + len(ref_pages) == self.num_pages - 1, (
            f"page leak: {self.num_pages - 1 - len(free_set) - len(ref_pages)} "
            "pages neither free nor referenced")
        shared = sum(1 for p in ref_pages if self._ref[p] > 1)
        return {"free": len(self._free), "owned": len(owned),
                "total": self.num_pages - 1, "shared": shared,
                "referenced": len(ref_pages)}


# ---------------------------------------------------------------------------
# XLA-compilable reference attention (CPU path / parity oracle)
# ---------------------------------------------------------------------------

def _masked_stats(att: jax.Array, mask: jax.Array, v_r: jax.Array):
    """Streaming-softmax statistics of masked logits.

    att: ``[H, T, R]`` scaled logits already filled with ``_MASK_VALUE``
    where masked; mask: ``[T, R]``; v_r: ``[R, H, D]``.  Returns
    ``(m [T,H], l [T,H], acc [T,H,D])`` — the flash-attention carry
    triple; a query row with no valid key keeps the neutral carry
    ``(m=_MASK_VALUE, l=0, acc=0)``.
    """
    m_cur = jnp.max(att, axis=-1).T                        # [T, H]
    m_safe = jnp.where(m_cur > jnp.float32(_MASK_VALUE * 0.5), m_cur,
                       jnp.float32(_MASK_VALUE))
    p = jnp.exp(att - m_safe.T[:, :, None])                # [H, T, R]
    p = jnp.where(mask[None], p, 0.0)
    l_cur = jnp.sum(p, axis=-1).T                          # [T, H]
    acc_cur = jnp.einsum("htr,rhd->thd", p, v_r)           # [T, H, D]
    return m_safe, l_cur, acc_cur


def fold_stats(carry, m_cur, l_cur, acc_cur):
    """Fold an incoming flash-attention carry ``(m, l, acc)`` with a
    fresh stat triple — the associative streaming-softmax combine the
    chunked partial-residency scan threads across dispatches.  The
    neutral carry ``(m=_MASK_VALUE, l=0, acc=0)`` folds exactly."""
    m0, l0, acc0 = (x.astype(jnp.float32) for x in carry)
    m_new = jnp.maximum(m0, m_cur)
    a0 = jnp.exp(m0 - m_new)
    a1 = jnp.exp(m_cur - m_new)
    l_new = a0 * l0 + a1 * l_cur
    acc_new = a0[..., None] * acc0 + a1[..., None] * acc_cur
    return m_new, l_new, acc_new


def neutral_carry(T: int, H: int, D: int):
    """The identity element for :func:`fold_stats` (all-masked stats)."""
    return (jnp.full((T, H), _MASK_VALUE, jnp.float32),
            jnp.zeros((T, H), jnp.float32),
            jnp.zeros((T, H, D), jnp.float32))


def ref_paged_attention(q: jax.Array, pages: jax.Array, kv_lens: jax.Array,
                        page_indices: jax.Array, cu_q_lens: jax.Array,
                        num_seqs: jax.Array, *, sm_scale: float,
                        sliding_window=None, carry=None) -> jax.Array:
    """Same math as the kernel's ``ref_ragged_paged_attention`` but with
    static control flow (where-masks over the flat page buffer), so it
    jits on any backend.  ``page_indices`` may pad unused entries with -1
    (never matches a real page — including interior holes, which is how
    a partially-resident sequence's parked columns drop out while the
    surviving columns keep their true positions).  O(T * P * page_size)
    — test scale.

    ``carry``: optional incoming flash-attention stats ``(m [T,H],
    l [T,H], acc [T,H,D])`` from earlier dispatches of a chunked scan;
    when given the output folds them in via streaming-softmax math
    (bit-identical shapes, ulp-level numeric difference vs the plain
    softmax path, which is preserved untouched when ``carry is None``).
    """
    T, H, D = q.shape
    P, page, combined, _ = pages.shape
    Hkv = combined // 2
    S, pp = page_indices.shape
    k_flat = pages[:, :, 0::2, :].reshape(P * page, Hkv, D)
    v_flat = pages[:, :, 1::2, :].reshape(P * page, Hkv, D)

    page_of_r = jnp.arange(P * page, dtype=jnp.int32) // page     # [R]
    pos_in_page = jnp.arange(P * page, dtype=jnp.int32) % page

    # token -> sequence (padding tokens map past num_seqs and mask out)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    seq_of_t = jnp.sum((t_idx[:, None] >= cu_q_lens[None, 1:]).astype(
        jnp.int32), axis=1)                                       # [T]
    token_valid = t_idx < cu_q_lens[num_seqs[0]]
    seq_of_t = jnp.minimum(seq_of_t, S - 1)

    # per (seq, flat row): does the row belong to the seq, at which pos
    match = page_indices[:, :, None] == page_of_r[None, None, :]  # [S,pp,R]
    owned = jnp.any(match, axis=1)                                # [S, R]
    kvpos = (jnp.sum(jnp.where(
        match, jnp.arange(pp, dtype=jnp.int32)[None, :, None], 0),
        axis=1) * page + pos_in_page[None, :])                    # [S, R]

    q_len = cu_q_lens[1:] - cu_q_lens[:-1]                        # [S]
    # absolute position of token t within its sequence
    q_pos = (jnp.take(kv_lens - q_len, seq_of_t) +
             (t_idx - jnp.take(cu_q_lens[:-1], seq_of_t)))        # [T]

    mask = (jnp.take(owned, seq_of_t, axis=0) &
            (jnp.take(kvpos, seq_of_t, axis=0) <= q_pos[:, None]) &
            token_valid[:, None])                                 # [T, R]
    if sliding_window is not None:
        mask = mask & (jnp.take(kvpos, seq_of_t, axis=0) >
                       q_pos[:, None] - sliding_window)

    groups = H // Hkv
    k_r = jnp.repeat(k_flat, groups, axis=1)
    v_r = jnp.repeat(v_flat, groups, axis=1)
    att = jnp.einsum("thd,rhd->htr", q.astype(jnp.float32),
                     k_r.astype(jnp.float32)) * sm_scale
    att = jnp.where(mask[None], att, jnp.float32(_MASK_VALUE))
    if carry is None:
        p = jax.nn.softmax(att, axis=-1)
        y = jnp.einsum("htr,rhd->thd", p, v_r.astype(jnp.float32))
        return jnp.where(token_valid[:, None, None], y, 0.0).astype(
            q.dtype)
    m_c, l_c, acc_c = _masked_stats(att, mask, v_r.astype(jnp.float32))
    m_n, l_n, acc_n = fold_stats(carry, m_c, l_c, acc_c)
    y = acc_n / jnp.maximum(l_n, 1e-30)[..., None]
    return jnp.where(token_valid[:, None, None], y, 0.0).astype(q.dtype)


def ref_paged_attention_quant(q: jax.Array, pages: jax.Array,
                              scales: jax.Array, kv_lens: jax.Array,
                              page_indices: jax.Array, cu_q_lens: jax.Array,
                              num_seqs: jax.Array, *, sm_scale: float,
                              sliding_window=None, carry=None) -> jax.Array:
    """Dequant-free XLA read path for a QUANTIZED page pool: gather each
    sequence's attended pages (still 1-byte) through ``page_indices``,
    dequantize ONLY the gathered operand, then masked attention.  The
    dequantized intermediate is ``[S, pp*page, ...]`` — bounded by the
    pages sequences actually attend, never the ``[P, ...]`` pool
    (``test_paged_quant.py`` pins that on the traced jaxpr).  Rows
    gathered in page-table order sit at their kv position directly, so
    masking is ``row < kv_len`` + causal bound + per-column validity
    (a ``-1`` page-table entry — padding or a parked partial-residency
    hole — gathers the trash page, so its rows must mask out even when
    they sit below ``kv_len``).  ``carry`` as
    :func:`ref_paged_attention`.

    q: ``[T, H, D]``; pages: ``[P, page, 2*Hkv, D]`` int8/fp8_e4m3;
    scales: ``[P, page, 2*Hkv]`` fp32.  O(T * pp * page_size) — the
    same test-scale contract as :func:`ref_paged_attention`, but over
    per-sequence attended rows instead of the whole pool.
    """
    T, H, D = q.shape
    P, page, combined, _ = pages.shape
    Hkv = combined // 2
    S, pp = page_indices.shape
    R = pp * page                          # attended rows per sequence

    safe = jnp.maximum(page_indices, 0).reshape(-1)       # [S*pp]
    g_pages = jnp.take(pages, safe, axis=0)               # quantized
    g_scales = jnp.take(scales, safe, axis=0)
    kv = (g_pages.astype(jnp.float32) *
          g_scales[..., None]).reshape(S, R, combined, D)
    k_g = kv[:, :, 0::2, :]                               # [S, R, Hkv, D]
    v_g = kv[:, :, 1::2, :]

    t_idx = jnp.arange(T, dtype=jnp.int32)
    seq_of_t = jnp.sum((t_idx[:, None] >= cu_q_lens[None, 1:]).astype(
        jnp.int32), axis=1)                               # [T]
    token_valid = t_idx < cu_q_lens[num_seqs[0]]
    seq_of_t = jnp.minimum(seq_of_t, S - 1)

    q_len = cu_q_lens[1:] - cu_q_lens[:-1]                # [S]
    q_pos = (jnp.take(kv_lens - q_len, seq_of_t) +
             (t_idx - jnp.take(cu_q_lens[:-1], seq_of_t)))  # [T]
    r_idx = jnp.arange(R, dtype=jnp.int32)
    kv_len_t = jnp.take(kv_lens, seq_of_t)                # [T]
    # column validity: -1 entries (padding OR interior residency holes)
    # gathered the trash page above — their rows never attend
    col_valid = jnp.repeat(page_indices >= 0, page, axis=1)  # [S, R]
    mask = ((r_idx[None, :] <= q_pos[:, None]) &
            (r_idx[None, :] < kv_len_t[:, None]) &
            jnp.take(col_valid, seq_of_t, axis=0) &
            token_valid[:, None])                         # [T, R]
    if sliding_window is not None:
        mask = mask & (r_idx[None, :] > q_pos[:, None] - sliding_window)

    groups = H // Hkv
    k_t = jnp.repeat(jnp.take(k_g, seq_of_t, axis=0), groups, axis=2)
    v_t = jnp.repeat(jnp.take(v_g, seq_of_t, axis=0), groups, axis=2)
    att = jnp.einsum("thd,trhd->htr", q.astype(jnp.float32),
                     k_t) * sm_scale
    att = jnp.where(mask[None], att, jnp.float32(_MASK_VALUE))
    if carry is None:
        p = jax.nn.softmax(att, axis=-1)
        y = jnp.einsum("htr,trhd->thd", p, v_t)
        return jnp.where(token_valid[:, None, None], y, 0.0).astype(
            q.dtype)
    # carry path reuses the flat-row helper: v as [T, R, H, D] must be
    # indexed per token, so fold with einsum over the token-gathered v
    m_cur = jnp.max(att, axis=-1).T                       # [T, H]
    m_safe = jnp.where(m_cur > jnp.float32(_MASK_VALUE * 0.5), m_cur,
                       jnp.float32(_MASK_VALUE))
    p = jnp.exp(att - m_safe.T[:, :, None])
    p = jnp.where(mask[None], p, 0.0)
    l_cur = jnp.sum(p, axis=-1).T                         # [T, H]
    acc_cur = jnp.einsum("htr,trhd->thd", p, v_t)         # [T, H, D]
    m_n, l_n, acc_n = fold_stats(carry, m_safe, l_cur, acc_cur)
    y = acc_n / jnp.maximum(l_n, 1e-30)[..., None]
    return jnp.where(token_valid[:, None, None], y, 0.0).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flax-side: write new KV into pages, attend
# ---------------------------------------------------------------------------

def _staged_attend_stats(mdl, q: jax.Array, ragged_meta, cfg) -> jax.Array:
    """Chunk-stats dispatch of the partial-residency scan: attend the
    query tokens against a STAGED dense KV block (parked pages uploaded
    through the staging buffer, never entering the pool) and sow the
    flash-attention stat triple into the ``"carry"`` collection instead
    of producing attention output.

    ``ragged_meta`` carries ``staged_kv [R, 2*Hkv, D]`` (store dtype —
    int8/fp8 pages stay 1-byte and dequantize here via
    ``staged_scales [R, 2*Hkv]``), ``staged_kpos [R]`` absolute key
    positions, ``staged_qpos [T]`` absolute query positions, and
    optionally an incoming carry (``carry_m``/``carry_l``/``carry_acc``)
    folded before sowing.  The pool is untouched: no cache variable is
    created, so chunk dispatches need no ``cache`` collection at all.
    Returns zeros shaped like the normal attention output — the driver
    reads the stats, not the module output.
    """
    _, H, T, D = q.shape
    staged = ragged_meta["staged_kv"]
    R, combined, _ = staged.shape
    Hkv = combined // 2
    sf = staged.astype(jnp.float32)
    if "staged_scales" in ragged_meta:
        sf = sf * ragged_meta["staged_scales"][..., None].astype(
            jnp.float32)
    k_s = sf[:, 0::2, :]                                   # [R, Hkv, D]
    v_s = sf[:, 1::2, :]
    groups = H // Hkv
    k_r = jnp.repeat(k_s, groups, axis=1)                  # [R, H, D]
    v_r = jnp.repeat(v_s, groups, axis=1)
    qt = q[0].transpose(1, 0, 2).astype(jnp.float32)       # [T, H, D]
    sm_scale = float(1.0 / np.sqrt(D))
    att = jnp.einsum("thd,rhd->htr", qt, k_r) * sm_scale
    kpos = ragged_meta["staged_kpos"]
    qpos = ragged_meta["staged_qpos"]
    # parked groups are full pages of live tokens strictly below the
    # query frontier, so the causal bound is usually all-true — kept
    # anyway (with the window bound) so a partially-covered group near
    # a sliding window stays exact
    mask = kpos[None, :] <= qpos[:, None]                  # [T, R]
    window = getattr(cfg, "sliding_window", None)
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    att = jnp.where(mask[None], att, jnp.float32(_MASK_VALUE))
    m_c, l_c, acc_c = _masked_stats(att, mask, v_r)
    if "carry_m" in ragged_meta:
        m_c, l_c, acc_c = fold_stats(
            (ragged_meta["carry_m"], ragged_meta["carry_l"],
             ragged_meta["carry_acc"]), m_c, l_c, acc_c)
    mdl.sow("carry", "stats", (m_c, l_c, acc_c))
    return jnp.zeros((1, H, T, D), q.dtype)


def paged_update_and_attend(mdl, q: jax.Array, k: jax.Array, v: jax.Array,
                            ragged_meta: Dict[str, jax.Array], cfg
                            ) -> jax.Array:
    """Inside an attention module: scatter this tick's K/V rows into the
    layer's page buffer, then ragged-paged attention for all T tokens.

    q: [1, H, T, D]; k, v: [1, Hkv, T, D] (rotary already applied).
    Returns [1, H, T, D].  Requires ``mutable=["cache"]`` on apply.

    Partial residency hooks (the chunked multi-dispatch scan): a
    ``staged_kv`` key in ``ragged_meta`` short-circuits to
    :func:`_staged_attend_stats` BEFORE any cache variable exists —
    chunk dispatches never touch the pool.  ``carry_m``/``carry_l``/
    ``carry_acc`` keys make the normal (finish) dispatch fold the
    accumulated chunk stats into its attention via the explicit-carry
    paths of the reference functions / quantized kernel.

    A looped model (``cfg.total_ut_steps = U > 1``) says which pass this
    is in ``ragged_meta["kv_slot"]``: the layer's pool has ``U`` slots a
    page and this call reads and writes slot ``u`` alone, in place.
    """
    _, H, T, D = q.shape
    sm_scale = float(1.0 / np.sqrt(D))
    head_dim = D
    D = kv_lane_dim(head_dim, getattr(cfg, "kv_cache_dtype", "none"))
    if D != head_dim:
        # heads of 64 live in the pool zero-padded to the kernels' lanes
        assert "staged_kv" not in ragged_meta and \
            "carry_m" not in ragged_meta, (
                "padded K/V heads have no partial-residency scan")
        pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, D - head_dim),))
        q, k, v = pad(q), pad(k), pad(v)
    slots = kv_slots_per_layer(cfg)
    if "staged_kv" in ragged_meta:
        assert slots == 1, "partial residency has one K/V slot a layer"
        return _staged_attend_stats(mdl, q, ragged_meta, cfg)
    carry = None
    if "carry_m" in ragged_meta:
        carry = (ragged_meta["carry_m"], ragged_meta["carry_l"],
                 ragged_meta["carry_acc"])
    Hkv = k.shape[1]
    P, page = cfg.kv_num_pages, cfg.kv_page_size
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    new_kv_dest = ragged_meta["new_kv_dest"]
    page_indices = ragged_meta["page_indices"]
    pool_shape = (P, page, 2 * Hkv, D)
    if slots > 1:
        assert carry is None and (
            getattr(cfg, "kv_cache_dtype", "none") or "none") == "none", (
            "a looped model's pool is full-width and wholly resident: no "
            "quantized pages, no chunk-scan carry")
        # slot u of page p is page p * U + u of the same buffer; a row
        # bound for no page (past the pool) stays past it, and the -1 of
        # an unused page-table entry stays -1
        slot = ragged_meta["kv_slot"]
        pool_shape = (P, slots, page, 2 * Hkv, D)
        new_kv_dest = ((new_kv_dest // page * slots + slot) * page
                       + new_kv_dest % page)
        page_indices = jnp.where(page_indices >= 0,
                                 page_indices * slots + slot, page_indices)
        P = P * slots

    # KV-cache quantization (reference csrc/fp_quantizer selective_dequant
    # + inference v2 KV configs): pages persist in fp8 e4m3 or int8 with a
    # per-(row, head) fp32 scale and are READ quantized — per-tile
    # register dequant in ops/ragged_paged_quant.py (TPU) or the
    # gathered-pages XLA reference below; never a full-width pool operand
    kv_quant = getattr(cfg, "kv_cache_dtype", "none") or "none"
    if kv_quant in ("fp8", "fp8_e4m3"):
        store_dtype, qmax = jnp.float8_e4m3fn, float(
            jnp.finfo(jnp.float8_e4m3fn).max)
    elif kv_quant == "int8":
        store_dtype, qmax = jnp.int8, 127.0
    else:
        assert kv_quant == "none", f"unknown kv_cache_dtype {kv_quant!r}"
        store_dtype, qmax = k.dtype, None

    pages_var = mdl.variable(
        "cache", "kv_pages", jnp.zeros, pool_shape, store_dtype)

    # interleave K/V onto combined heads: [T, 2Hkv, D], K even, V odd
    k_rows = k[0].transpose(1, 0, 2)                   # [T, Hkv, D]
    v_rows = v[0].transpose(1, 0, 2)
    combined = jnp.stack([k_rows, v_rows], axis=2).reshape(T, 2 * Hkv, D)

    flat = pages_var.value.reshape(P * page, 2 * Hkv, D)
    if qmax is None:
        flat = flat.at[new_kv_dest].set(
            combined.astype(flat.dtype), mode="drop")
        pages = flat.reshape(P, page, 2 * Hkv, D)
        pages_var.value = pages.reshape(pool_shape)
    else:
        scales_var = mdl.variable(
            "cache", "kv_scales", jnp.zeros, (P, page, 2 * Hkv),
            jnp.float32)
        cf = combined.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(cf), axis=-1)         # [T, 2Hkv]
        # floor the QUOTIENT at the smallest normal f32, not absmax at
        # an arbitrary 1e-12: fp8's qmax=448 can push absmax/qmax
        # subnormal, and a subnormal scale's reciprocal overflows
        # qv = cf/scale to inf before the store-dtype cast.  For any
        # absmax >= 1e-12 this is bit-identical to the old floor.
        scale = jnp.maximum(absmax / qmax,
                            jnp.float32(np.finfo(np.float32).tiny))
        qv = cf / scale[..., None]
        if store_dtype == jnp.int8:
            qv = jnp.clip(jnp.round(qv), -qmax, qmax)
        flat = flat.at[new_kv_dest].set(
            qv.astype(store_dtype), mode="drop")
        flat_s = scales_var.value.reshape(P * page, 2 * Hkv)
        flat_s = flat_s.at[new_kv_dest].set(scale, mode="drop")
        scales_var.value = flat_s.reshape(P, page, 2 * Hkv)
        pages = flat.reshape(P, page, 2 * Hkv, D)
        pages_var.value = pages
        # NO transient dequant: the quantized pool is read directly by
        # the dequant-free attention variants below — per-tile register
        # dequant in the Pallas kernel on TPU, gathered-pages dequant
        # (O(attended rows), never O(pool)) in the XLA reference
        kv_scales = scales_var.value

    qt = q[0].transpose(1, 0, 2)                       # [T, H, D]
    kv_lens = ragged_meta["kv_lens"]
    cu_q_lens = ragged_meta["cu_q_lens"]
    num_seqs = ragged_meta["num_seqs"]
    window = getattr(cfg, "sliding_window", None)

    # a decode program says so itself (build_decode_block: one query a
    # slot, ``cu_q_lens`` an arange): its attention is the decode kernel's
    # wherever that kernel's layout holds for this shard of the pool, and
    # otherwise, as for every other program, the call in attend_ragged
    one_query_a_slot = (ragged_meta.get("one_query_a_slot") is True
                        and carry is None and qmax is None)
    # so does a fused tick (programs._fused_forward: decode rows and
    # prompt chunks packed by ``cu_q_lens``): its chunks are the chunk
    # kernel's and its one-query rows the decode kernel's, under the same
    # rule.  Every other caller (the speculative verify, the chunk scan)
    # keeps the call in attend_ragged
    fused_tick = (ragged_meta.get("fused_tick") is True
                  and carry is None and qmax is None)

    def attend(qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
               live=None):
        ragged = (qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs)
        if fused_tick:
            return fused_tick_attention(
                *ragged, sm_scale=sm_scale, sliding_window=window,
                fallback=lambda: attend_ragged(*ragged),
                fallback_route=paged_attention_path(head_dim))
        if not one_query_a_slot:
            return attend_ragged(*ragged)
        return paged_decode_attention(
            qt, pages, kv_lens, page_indices, live, sm_scale=sm_scale,
            sliding_window=window, fallback=lambda: attend_ragged(*ragged),
            fallback_route=paged_attention_path(head_dim))

    def attend_ragged(qt, pages, kv_lens, page_indices, cu_q_lens,
                      num_seqs):
        # the vLLM-TPU kernel is built for 128 lanes (its lane-width row
        # stats assert on a smaller D): heads of 128 take it as they are,
        # heads of 64 zero-padded to 128 (``kv_lane_dim``), every other
        # width the XLA reference — correct but O(T * total_page_rows).
        # An incoming chunk-scan carry always routes to the reference:
        # the upstream kernel has no carry operand (the quantized pool's
        # own kernel does).
        if paged_attention_path(
                head_dim, carry=carry is not None).startswith(
                    "pallas-ragged"):
            from jax.experimental.pallas.ops.tpu.ragged_paged_attention \
                import kernel as rpa

            # explicit blocks: for a shape outside its tuned table the
            # upstream lookup answers (128 pages, 32 queries), and from
            # max_seq_len ~2k up that KV block no longer fits the chip's
            # scoped VMEM (the v5e compiler: "Ran out of memory in memory
            # space vmem").  512 KV tokens x 32 queries at up to 8 KV
            # heads (fewer tokens at more heads) compiles under the
            # default limit at every page size the engine offers.
            return rpa.ragged_paged_attention(
                qt, pages, kv_lens, jnp.maximum(page_indices, 0),
                cu_q_lens, num_seqs, sm_scale=sm_scale,
                sliding_window=window,
                num_kv_pages_per_block=rpa_kv_pages_per_block(
                    pages.shape[2] // 2, page, page_indices.shape[1]),
                num_queries_per_block=min(T, _RPA_QUERY_BLOCK))
        if carry is None and routes.on_tpu():
            from deepspeed_tpu.utils.logging import logger

            logger.warning(
                f"paged attention: head_dim={head_dim} — the Pallas "
                "kernels serve heads of 128, and heads of 64 padded to "
                "128; this width takes the dense XLA route "
                "(serving_stages()['kernels'] reports it)")
        return ref_paged_attention(
            qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, sliding_window=window, carry=carry)

    def attend_quant(qt, pages, scales, kv_lens, page_indices, cu_q_lens,
                     num_seqs):
        # quantized pool: both routes read the 1-byte pages + scale rows
        # directly (see kv_dequant_path for the route the engine reports)
        if kv_dequant_path(D) == "pallas-quant":
            from deepspeed_tpu.ops.ragged_paged_quant import \
                ragged_paged_attention_quant

            return ragged_paged_attention_quant(
                qt, pages, scales, kv_lens, page_indices, cu_q_lens,
                num_seqs, sm_scale=sm_scale, sliding_window=window,
                carry=carry)
        return ref_paged_attention_quant(
            qt, pages, scales, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, sliding_window=window, carry=carry)

    # TP serving (reference v2 sharding/attn.py: heads split over the TP
    # group): attention is embarrassingly parallel over heads, so under a
    # >1 `tensor` mesh axis run it shard_map-manual over `tensor` with q
    # and the KV pages head-sharded and the ragged metadata replicated —
    # required for the Pallas kernel, which composes with shard_map, not
    # with GSPMD auto-sharding
    tp = _serving_tp(cfg)
    # which slots of a decode program are in use, where it says
    live = ([ragged_meta["live"]]
            if one_query_a_slot and "live" in ragged_meta else [])
    if tp > 1:
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.sequence.layer import resolve_mesh

        assert carry is None, (
            "chunked partial-residency scan requires tensor_parallel=1 "
            "(the long-context driver gates admission on it)")
        assert H % tp == 0 and Hkv % tp == 0, (
            f"TP serving requires heads divisible by tp={tp} "
            f"(H={H}, Hkv={Hkv})")
        mesh = resolve_mesh(None, "tensor")
        if qmax is None:
            y = _shard_map_compat(
                attend, mesh=mesh,
                in_specs=(P(None, "tensor", None),
                          P(None, None, "tensor", None), P(), P(), P(),
                          P()) + (P(),) * len(live),
                out_specs=P(None, "tensor", None),
                axis_names={"tensor"}, check_vma=False)(
                    qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
                    *live)
        else:
            # the scale buffer shards its combined-head dim with the pool
            y = _shard_map_compat(
                attend_quant, mesh=mesh,
                in_specs=(P(None, "tensor", None),
                          P(None, None, "tensor", None),
                          P(None, None, "tensor"), P(), P(), P(), P()),
                out_specs=P(None, "tensor", None),
                axis_names={"tensor"}, check_vma=False)(
                    qt, pages, kv_scales, kv_lens, page_indices,
                    cu_q_lens, num_seqs)
    elif qmax is None:
        y = attend(qt, pages, kv_lens, page_indices, cu_q_lens, num_seqs,
                   *live)
    else:
        y = attend_quant(qt, pages, kv_scales, kv_lens, page_indices,
                         cu_q_lens, num_seqs)
    y = y.transpose(1, 0, 2)[None]                     # [1, H, T, D]
    return y if D == head_dim else y[..., :head_dim]


def ref_latent_paged_attention(q: jax.Array, pages: jax.Array,
                               kv_lens: jax.Array, page_indices: jax.Array,
                               cu_q_lens: jax.Array, num_seqs: jax.Array, *,
                               rank: int, sm_scale: float) -> jax.Array:
    """The XLA twin of ``ops/latent_attention.py``: ``q [T, H, W]`` over
    the latent pool ``pages [P, page, W]``, ``[T, H, rank]``.  Latent
    attention is attention with ONE key/value head whose key is the row and
    whose value is its first ``rank`` columns, so this is
    :func:`ref_paged_attention` over the pool seen that way (a copy of the
    pool: test scale, as that function is)."""
    W = pages.shape[-1]
    value = jnp.where(jnp.arange(W) < rank, pages, 0)
    kv = jnp.stack([pages, value], axis=2)              # K even, V odd
    return ref_paged_attention(q, kv, kv_lens, page_indices, cu_q_lens,
                               num_seqs, sm_scale=sm_scale)[..., :rank]


def latent_update_and_attend(mdl, q: jax.Array, rows: jax.Array,
                             ragged_meta: Dict[str, jax.Array], cfg, *,
                             value_width: int, sm_scale: float
                             ) -> jax.Array:
    """Inside a latent-attention module: write this tick's rows into the
    layer's latent page buffer, then attend for all T tokens.

    ``q [T, H, w]``: every head's ABSORBED query (the up-projection of the
    keys folded in, the rotated part behind it); ``rows [T, w]``: ``[c |
    k_r]`` of this tick's tokens; narrower than the pool's ``W``
    (:func:`latent_width`) they are zero-padded here.  Returns ``[T, H,
    value_width]``: the weighted sums of the rows' first ``value_width``
    columns, which the caller up-projects.  A FUSED TICK (``ragged_meta``
    says which program it is) hands ``q`` as the pair ``ops/
    latent_attention.py:fused_tick_attention`` takes (heads first as the
    module holds it, and the packed sequences' first rows), and gets that
    function's answer in the layout the up-projection reads; a decode
    program takes the decode kernel, every other caller the XLA twin."""
    assert "staged_kv" not in ragged_meta and "carry_m" not in ragged_meta, (
        "latent pages have no partial-residency scan")
    W = latent_width(cfg)
    pad = lambda a: a if a.shape[-1] == W else jnp.pad(  # noqa: E731
        a, ((0, 0),) * (a.ndim - 1) + ((0, W - a.shape[-1]),))
    q, rows = jax.tree_util.tree_map(pad, (q, rows))
    P, page = cfg.kv_num_pages, cfg.kv_page_size
    assert P > 1, "paged_decode requires kv_num_pages (engine sets it)"
    pages_var = mdl.variable("cache", LATENT_PAGES, jnp.zeros, (P, page, W),
                             rows.dtype)
    flat = pages_var.value.reshape(P * page, W)
    pages = flat.at[ragged_meta["new_kv_dest"]].set(
        rows.astype(flat.dtype), mode="drop").reshape(P, page, W)
    pages_var.value = pages
    ragged = (pages, ragged_meta["kv_lens"], ragged_meta["page_indices"],
              ragged_meta["cu_q_lens"], ragged_meta["num_seqs"])
    twin = lambda q=q: ref_latent_paged_attention(      # noqa: E731
        q, *ragged, rank=value_width, sm_scale=sm_scale)
    if ragged_meta.get("fused_tick") is True:
        return latent_attention.fused_tick_attention(
            *q, *ragged, rank=value_width, sm_scale=sm_scale, fallback=twin)
    if ragged_meta.get("one_query_a_slot") is True:
        return latent_attention.decode_attention(
            q, *ragged[:3], ragged_meta.get("live"), rank=value_width,
            sm_scale=sm_scale, fallback=twin)
    return twin()


def kv_dequant_path(head_dim: int) -> str:
    """Which dequant-free read path a quantized pool takes on this
    backend: the Pallas quantized-pages kernel
    (:mod:`deepspeed_tpu.ops.ragged_paged_quant`; TPU, head_dim 128) or
    the gathered-pages XLA reference
    (:func:`ref_paged_attention_quant`).  Neither materializes a
    full-width pool operand.  The engine reports this in its
    ``serving_stages()['kv_quant']`` block."""
    return routes.take("paged_attention_quant", eligible=head_dim == 128,
                       pallas="pallas-quant", xla="xla-gather")


def paged_attention_path(head_dim: int, carry: bool = False) -> str:
    """Which read path a full-width pool takes on this backend: the
    upstream Pallas ragged kernel (TPU, no incoming chunk-scan carry — it
    has no carry operand) for heads of 128 (``pallas-ragged``) and for
    heads of 64 stored zero-padded to 128 (``pallas-ragged-pad128``), or
    the dense XLA reference (:func:`ref_paged_attention`: ``xla-dense``,
    over the padded pool ``xla-dense-pad128``).  The engine reports this
    in ``serving_stages()['kernels']``."""
    pad = "-pad128" if kv_lane_dim(head_dim) != head_dim else ""
    return routes.take("paged_attention",
                       eligible=kv_lane_dim(head_dim) == _LANES
                       and not carry,
                       pallas="pallas-ragged" + pad, xla="xla-dense" + pad)


def _serving_tp(cfg) -> int:
    """Tensor-parallel degree for the paged path: the model must be
    TP-annotated AND a multi-device `tensor` mesh axis installed."""
    if not getattr(cfg, "tensor_parallel", False):
        return 1
    import deepspeed_tpu.comm as dist

    topo = dist.peek_topology()
    if topo is None:
        return 1
    return int(topo.mesh.shape.get("tensor", 1))
