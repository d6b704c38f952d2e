"""Blocked (paged) KV cache for ragged continuous batching.

TPU-native re-design of the reference's FastGen blocked-KV machinery
(``inference/v2/ragged/blocked_allocator.py:1`` ``BlockedAllocator``,
``ragged/kv_cache.py`` ``BlockedKVCache``, and the ragged attention
kernels under ``inference/v2/kernels/ragged_ops/``): KV lives in
fixed-size pages addressed through a per-sequence page table, so device
memory scales with tokens in flight — not ``max_seqs x max_seq_len`` —
and one fused token batch mixes decode tokens with prefill chunks
(Dynamic SplitFuse, ``engine_v2.py:107``).

Layout contract: every pool leaf of a model's ``cache`` collection leads
with the page dim, so what copies, spills or shares ``leaf[page]`` need not
know what a page holds; a tick's new rows are scattered into the flat page
buffer BEFORE attention, and ``kv_lens`` includes this tick's tokens.  Page
0 is reserved as the trash page: padding tokens write there, no sequence is
ever allocated it.  Per-slot state that is no page leads with the slot.

One file a thing.  ``allocator.py``: the host's page allocator; ``tree.py``:
the contract ``KVState`` stands on (pools and per-slot state); ``reference.py``:
the XLA twins.  One file a CACHE KIND, which owns its leaves' names and
shapes, its predicate on a model config, its update-and-attend function and
what the engine reads of it (a :class:`~.tree.Part`): ``kv_pages.py`` (per-head
K/V pages; a looped model's slots a layer; thirty heads stored as thirty-two),
``conv_state.py`` (a short convolution's last inputs), ``linear_state.py`` (a
decaying matrix a head), ``delta_state.py`` (a matrix a head corrected by the
delta rule, and the rows of the convolution before it: two leaves a layer),
``kda_state.py`` (the same two leaves, the matrix's rows decaying each by a
factor of its own),
``block_select.py`` (pages of keys and values chosen by compressed keys),
``index_select.py`` (tokens chosen by an indexer whose keys are a pool leaf),
``latent.py`` (one latent row a token), ``latent_select.py`` (latent rows
chosen by an indexer whose keys are a second leaf), ``window_pages.py`` (the window layers
of a model that has full layers too: a pool, an allocator and a page table of
their own, whose pages a sequence gives back as its window leaves them
behind).  :func:`describe` composes a model's parts into the ONE description
the engine reads.

The package imports ``deepspeed_tpu.ops`` and ``deepspeed_tpu.utils`` (and, in
the tensor-parallel branch of ``kv_pages.py`` alone, ``comm`` and
``sequence``): never ``models``, ``inference/v2`` or ``inference/config.py``,
both of which stand on it (``tests/unit/inference/test_cache_kind_seam.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.inference.paged.allocator import (TRASH_PAGE,  # noqa: F401
                                                     PageAllocator, pages_for)
from deepspeed_tpu.inference.paged.block_select import (  # noqa: F401
    CKEY_PAGES, SPARSE_KV_PAGES, BlockSelect, sparse_key_counts,
    sparse_selection, sparse_update_and_attend)
from deepspeed_tpu.inference.paged.conv_state import (  # noqa: F401
    ConvState, conv_layers, ragged_short_conv)
from deepspeed_tpu.inference.paged.delta_state import (  # noqa: F401
    DELTA_LAYER, DeltaState, delta_layers, ragged_gated_delta)
from deepspeed_tpu.inference.paged.index_select import (  # noqa: F401
    INDEX_KEY_PAGES, TOKENSEL_KV_PAGES, IndexSelect, TickShape,
    index_key_steps, token_select_counts, token_selection,
    tokensel_update_and_attend)
from deepspeed_tpu.inference.paged.kda_state import (  # noqa: F401
    KdaState, kda_layers, ragged_kda)
from deepspeed_tpu.inference.paged.kv_pages import (  # noqa: F401
    _RPA_QUERY_BLOCK, KVPages, kv_dequant_path, kv_heads_stored, kv_lane_dim,
    kv_slots_per_layer, layer_window, paged_attention_path,
    paged_update_and_attend, rpa_kv_pages_per_block)
from deepspeed_tpu.inference.paged.latent import (  # noqa: F401
    LATENT_PAGES, Latent, latent_update_and_attend, latent_width,
    ref_latent_paged_attention)
from deepspeed_tpu.inference.paged.latent_select import (  # noqa: F401
    LatentSelect, chosen_latent_update_and_attend)
from deepspeed_tpu.inference.paged.linear_state import (  # noqa: F401
    LINEAR_MIXER, LinearState, linear_layers, ragged_linear_attention)
from deepspeed_tpu.inference.paged.reference import (  # noqa: F401
    ref_paged_attention, ref_paged_attention_quant)
from deepspeed_tpu.inference.paged.tree import (SLOT_STATE,  # noqa: F401
                                                WINDOW_POOL, Part,
                                                is_slot_state, map_cache,
                                                split_cache,
                                                state_bytes_per_seq,
                                                window_pool,
                                                window_pool_layers)
from deepspeed_tpu.inference.paged.window_pages import (  # noqa: F401
    WindowPages, WindowPool, pages_bound)


def _merged(answers) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for answer in answers:
        out.update(answer)
    return out


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """What a model's layers keep for a token: its ``parts`` (a model is a
    set of layer kinds), and the numbers the programs and ``KVState`` read."""
    parts: Tuple[Part, ...]
    kv_slots: int           # K/V slots a layer holds for one token
    state_layers: int       # layers that keep per-slot state beside the pool
    kv_layers: int          # and those that keep rows in the pool
    latent_width: int       # of a latent layer's row as stored; 0: per-head
    selection: Optional[Any]    # of a block-selecting layer
    # the part whose layers keep a pool and a page table of their own; None:
    # every layer that keeps pages keeps them behind the one table
    window: Optional[WindowPages] = None

    def refusals(self, model: str) -> List[Tuple[str, Dict[str, str]]]:
        """Each part's ``(model_is, {feature: why})``, for ``model`` (a
        class's name)."""
        return [r for r in (p.refusals(model) for p in self.parts) if r]

    def span_attrs(self, cache) -> Dict[str, int]:
        return _merged(p.span_attrs(cache) for p in self.parts)

    def counts(self, st, positions, q_lens=None) -> Dict[str, int]:
        return _merged(p.counts(st, positions, q_lens) for p in self.parts)

    def kernels(self, route: str) -> Dict[str, str]:
        return _merged(p.kernels(route) for p in self.parts)

    def stages(self, st, cache, cfg, kernels, pages_peak) -> Dict[str, Any]:
        return _merged(p.stages(st, cache, cfg, kernels, pages_peak)
                       for p in self.parts)


def describe(cfg, tick: Optional[TickShape] = None) -> CacheKind:
    """The cache of the model whose config is ``cfg``, read off the config's
    own keys (``total_ut_steps``, ``layer_types``, ``mixer_types``,
    ``linear_attn_config`` and ``gqa_layers``, ``selection``, ``sa_config``,
    ``kv_lora_rank``, ``sliding_window``) by each kind's
    predicate; ``tick``: the fused program's shape, for a kind that counts
    a tick's work in the program's tiles."""
    of = int(cfg.num_hidden_layers)
    conv, linear = conv_layers(cfg), linear_layers(cfg)
    delta, vector_delta = delta_layers(cfg), kda_layers(cfg)
    rows = of - conv - linear - delta - vector_delta
    slots, width = kv_slots_per_layer(cfg), latent_width(cfg)
    selection, indexer = sparse_selection(cfg), token_selection(cfg)
    parts: List[Part] = []
    own = window_pool_layers(cfg)
    if selection is None and indexer is None and not width:
        parts.append(KVPages(rows - own, of, slots))
    window = None
    if own:
        window = WindowPages(
            own, of, int(cfg.sliding_window),
            0 if tick is None else pages_bound(
                int(cfg.sliding_window), tick.rows, tick.page_size))
        parts.append(window)
    if conv:
        parts.append(ConvState(conv, of))
    if linear:
        parts.append(LinearState(linear, of))
    if delta:
        parts.append(DeltaState(delta, of))
    if vector_delta:
        parts.append(KdaState(vector_delta, of))
    if selection is not None:
        parts.append(BlockSelect(rows, of, selection))
    elif indexer is not None and width:
        parts.append(LatentSelect(rows, of, indexer, tick, width))
    elif indexer is not None:
        parts.append(IndexSelect(rows, of, indexer, tick))
    elif width:
        parts.append(Latent(rows, of, width))
    return CacheKind(tuple(parts), slots,
                     conv + linear + delta + vector_delta, rows, width,
                     selection, window)


def state_layers(cfg) -> int:
    """Layers of a model that keep per-slot state beside the pool: the
    ``"conv"`` and the ``"linear_attention"`` entries of its config's
    ``layer_types``, the ``"lightning-attn"`` entries of its ``mixer_types``
    and the layers its ``gqa_layers`` leaves to ``linear_attn_config``; 0 for
    a model whose config names none of the four (every layer holds K/V)."""
    return describe(cfg).state_layers


def kv_layers(cfg) -> int:
    """Layers of a model that keep keys and values in the pool."""
    return describe(cfg).kv_layers
