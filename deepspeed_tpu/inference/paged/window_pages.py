"""Window layers' pages: the cache kind of the ``sliding_attention`` layers of
a model whose ``layer_types`` names ``full_attention`` layers beside them
(``models/trinity.py``: three window layers, then a full one).

A window layer at row ``p`` attends keys ``p - window + 1 .. p`` and no
other, ever again.  Under the one page table a sequence's full layers need
(every key since position 0) its window layers would hold pages they can
never read: at a window of 4,096 and prompts of 32k, seven pages of eight.
So the window layers keep a **pool of their own** (leaf
``tree.py:WINDOW_POOL``, ``[window pages, page_size, 2 * Hkv, Dh]`` a layer,
leading with ITS page count), a **page allocator of their own** and a **page
table of their own**, ``[max_seqs, pages_per_seq]`` with column =
``position // page_size`` as in the first, so the attention kernels read it
as they read ``page_indices`` (``kv_pages.py:paged_update_and_attend`` takes
the ``window_`` keys of the metadata for such a layer).  :class:`WindowPool`
is the host's half, owned by ``KVState`` beside the first allocator:

- **growth**: a tick that brings a sequence to length ``n`` needs columns up
  to ``(n - 1) // page_size`` in BOTH tables;
- **release while the sequence lives**: before a tick grows, every column
  wholly below ``first_row - window`` (``first_row``: the first position the
  tick writes; no row of this or any later tick can reach such a column)
  goes back to the allocator and reads ``-1``, which the kernels' work lists
  never reach (they start at ``kv_len - window``) and their fetches clamp to
  the trash page (as ``kv_state.py:park``'s holes);
- so a sequence never holds more than :func:`pages_bound` pages, whatever its
  length, and admission reserves that many at most.

Work in flight is safe under a release: a released page is written again
only by a tick planned later, which the device runs later, and each tick
reads the table as it was copied at its own plan.

What would have to know TWO cursors a sequence and cannot yet is refused by
name (:data:`WINDOW_REFUSES`), not served wrongly."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

from deepspeed_tpu.inference.paged.allocator import PageAllocator, pages_for
from deepspeed_tpu.inference.paged.tree import Part, window_pool


def pages_bound(window: int, tick_rows: int, page_size: int) -> int:
    """The most window pages a sequence holds: the columns that ``window``
    keys behind a tick's first row and the tick's ``tick_rows`` rows touch,
    at the worst alignment."""
    return pages_for(window + tick_rows, page_size) + 1


class WindowPool:
    """The window layers' allocator and page table, for ``max_seqs`` slots;
    ``stats``: the engine's ``HostStageStats``, whose ``window_*`` gauges
    follow every verb."""

    def __init__(self, num_pages: int, page_size: int, max_seqs: int,
                 pages_per_seq: int, window: int, tick_rows: int, stats):
        self.num_pages, self.page_size = int(num_pages), int(page_size)
        self.window = int(window)
        self.bound = pages_bound(self.window, int(tick_rows), self.page_size)
        self.allocator = PageAllocator(self.num_pages, self.page_size)
        self.page_table = np.full((max_seqs, pages_per_seq), -1, np.int32)
        # the columns ``[low, top)`` of a slot's row hold pages
        self._low = np.zeros((max_seqs,), np.int64)
        self._top = np.zeros((max_seqs,), np.int64)
        self.stats = stats

    def pages_needed(self, n_tokens: int) -> int:
        """Pages an admission covering ``n_tokens`` reserves here."""
        return min(pages_for(n_tokens, self.page_size), self.bound)

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= self.allocator.free_pages

    def check_capacity(self, total: int) -> None:
        usable = self.num_pages - 1
        if self.pages_needed(total) > usable:
            raise ValueError(
                f"request needs {self.pages_needed(total)} pages of the "
                f"window layers' pool but the engine owns {usable} usable "
                "pages there — it could never be scheduled; raise "
                "window_pool_bytes")

    def attach(self, slot: int, n_tokens: int) -> bool:
        """Slot ``slot``'s first columns for an admission covering
        ``n_tokens``; False: the pool is short of them."""
        self.release(slot)
        n = self.pages_needed(n_tokens)
        if n > self.allocator.free_pages:
            return False
        self.page_table[slot, :n] = self.allocator.grow(slot, n)
        self._top[slot] = n
        return True

    def ensure(self, slot: int, upto_tokens: int, first_row=None) -> bool:
        """Give back what the tick that starts at position ``first_row``
        leaves behind (None: nothing), then grow ``slot``'s row to cover
        ``upto_tokens`` positions; False where the pool cannot."""
        if first_row is not None:
            self._leave_behind(slot, int(first_row))
        need, have = pages_for(upto_tokens, self.page_size), self._top[slot]
        if need > have:
            if need - have > self.allocator.free_pages:
                self._gauge()
                return False
            self.page_table[slot, have:need] = self.allocator.grow(
                slot, int(need - have))
            self._top[slot] = need
        self._gauge()
        return True

    def _leave_behind(self, slot: int, first_row: int) -> None:
        gone = min(max(first_row - self.window, 0) // self.page_size,
                   int(self._top[slot]))
        low = int(self._low[slot])
        if gone <= low:
            return
        self.allocator.release_pages(
            slot, [int(p) for p in self.page_table[slot, low:gone]])
        self.page_table[slot, low:gone] = -1
        self._low[slot] = gone
        self.stats.window_pages_released += gone - low

    def span(self, slot: int):
        """``(low, top)``: the columns of ``slot``'s row that hold pages."""
        return int(self._low[slot]), int(self._top[slot])

    def coverable_tokens(self, slot: int) -> int:
        """Positions ``slot`` could cover with every page still free."""
        return (int(self._top[slot]) + self.allocator.free_pages
                ) * self.page_size

    def release(self, slot: int) -> None:
        self.allocator.free(slot)
        self.page_table[slot, :] = -1
        self._low[slot] = self._top[slot] = 0
        self._gauge()

    def _gauge(self) -> None:
        st = self.stats
        st.window_pages_held = (self.num_pages - 1
                                - self.allocator.free_pages)
        st.window_pages_peak = max(st.window_pages_peak,
                                   st.window_pages_held)

    def audit(self, slots) -> Dict[str, int]:
        """Conservation over this pool: every live slot's row is what the
        allocator says it owns, no page is shared, none leaked."""
        for s, r in enumerate(slots):
            if r is None:
                continue
            row = [int(p) for p in self.page_table[s] if p >= 0]
            owned = self.allocator.owned_pages(s)
            assert row == owned and len(row) <= self.bound, (
                f"slot {s}: window page-table row {row} != allocator "
                f"ownership {owned} (bound {self.bound})")
        return self.allocator.audit(external={})


WINDOW_IS = ("{model} keeps the keys and values of {p.layers} of its {p.of} "
             "layers in a pool of their own, whose pages a sequence gives "
             "back as its window of {p.window} leaves them behind")
WINDOW_REFUSES = {
    "prefix_cache": ", and a shared prefix brings the first pool's pages "
    "only — a sequence that starts behind one needs the window layers' last "
    "{p.window} rows AT the prefix's end, which the index does not keep",
    "tiering": ", and a spilled request's record carries the rows of one "
    "page table",
    "quantized": "; the window layers' pool is full-width (the quantized "
    "pool's scale rows lead with the first pool's pages)",
    "tensor_parallel": ", and the cache's sharding rule knows the first "
    "pool's leaves only"}


@dataclasses.dataclass(frozen=True)
class WindowPages(Part):
    """The window layers of a model whose full layers keep the first pool;
    ``bound``: the most pages a sequence holds here (0: no tick shape was
    given to ``paged.describe``)."""
    window: int
    bound: int = 0

    def host_pool(self, num_pages: int, page_size: int, max_seqs: int,
                  pages_per_seq: int, tick_rows: int, stats) -> WindowPool:
        return WindowPool(num_pages, page_size, max_seqs, pages_per_seq,
                          self.window, tick_rows, stats)

    def refusals(self, model):
        return (WINDOW_IS.format(model=model, p=self),
                {k: v.format(p=self) for k, v in WINDOW_REFUSES.items()})

    def span_attrs(self, cache):
        return {"window_layers": self.layers,
                "window_pages_total": int(window_pool(cache)[0].shape[0]) - 1}

    def counts(self, st, positions, q_lens=None):
        # the pools as the allocators have them at this dispatch, and the
        # pages given back since the last one
        released = st.window_pages_released - st.window_pages_said
        st.window_pages_said = st.window_pages_released
        # the keys a window layer's rows can reach: ``min(p + 1, window)``
        # at position ``p``, the rows of a prompt chunk and the one-row
        # sequences (every row of a decode block) apart
        seen = np.minimum(np.asarray(positions, np.int64) + 1, self.window)
        chunk = (np.zeros(seen.shape, bool) if q_lens is None
                 else np.repeat(np.asarray(q_lens) > 1, q_lens))
        return {"window_pages_held": st.window_pages_held,
                "window_pages_released": released,
                "full_pages_held": st.full_pages_held,
                "window_prefill_key_tokens": int(seen[chunk].sum()),
                "window_decode_key_tokens": int(seen[~chunk].sum())}

    def stages(self, st, cache, cfg, kernels, pages_peak) -> Dict[str, Any]:
        leaves: List[Any] = window_pool(cache)
        return {"window_pool": {
            "layers": self.layers, "full_layers": self.of - self.layers,
            "window": self.window,
            "pages": int(leaves[0].shape[0]) - 1,
            "pages_peak": st.window_pages_peak,
            "pages_released": st.window_pages_released,
            "pages_bound_per_seq": self.bound,
            "bytes": sum(int(np.prod(l.shape)) * l.dtype.itemsize
                         for l in leaves),
            "full_pages_peak": pages_peak}}
