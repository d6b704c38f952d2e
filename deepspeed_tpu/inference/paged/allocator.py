"""The host-side page allocator (reference ``ragged/blocked_allocator.py:1``):
plain Python like the reference's scheduler tier, no ``jax``.  Page 0 is
reserved as the trash page: padding tokens write there, no sequence is ever
allocated it."""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

TRASH_PAGE = 0


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
        counts.  A sequence may own pages past its length (a block's
        growth covers tokens it has yet to write), so accounting
        exactness — not owned==pages_for(length) minimality — is the
        invariant."""
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
