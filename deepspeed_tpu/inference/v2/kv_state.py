"""One owner for every form a sequence's KV state takes.

A sequence's keys and values live, at different moments, as pages of the
device pool that a slot owns, as read-only pages it shares with the prefix
index, as packed rows parked in the host-RAM / NVMe tiers, as a session blob
on its way to another replica.  :class:`KVState` owns all of it: the
:class:`~deepspeed_tpu.inference.paged.PageAllocator`, the host page table,
the device cache tree, the
:class:`~deepspeed_tpu.inference.kv_tiering.TieredKVStore`, the
:class:`~deepspeed_tpu.inference.prefix_cache.PrefixCacheIndex` and the
fixed-shape page-movement programs.  The scheduler
(:mod:`~deepspeed_tpu.inference.v2.ragged_engine`) speaks to it in verbs on
``(request, slot)`` and never moves a page or writes a page-table cell
itself:

==================  =====================================================
``need``            token coverage an admission reserves
``can_admit``       would that many fresh pages be found right now
``attach``          build a slot's page run (prefix attach, COW, fresh)
``ensure``          grow a slot's run to cover more tokens
``publish``         offer a slot's freshly written full pages to the index
``release``         give a slot's pages back (the ONE place)
``spill/restore``   park a slot's live rows in the tiers / bring them back
``export/adopt``    a parked session as a portable blob / install one
``forget``          drop what a parked request still holds
``audit``           refcount conservation over every holder
==================  =====================================================

A model with layers that keep **per-slot state** and no K/V (the short
convolutions of ``models/lfm2_moe.py``: a few rows a layer a sequence, in the
same device tree under the leaf name ``paged/tree.py:SLOT_STATE``) has
that state owned here too, with the pages' life: zeroed at ``attach``, carried
by ``spill``/``restore`` and ``export``/``adopt`` (in the request's ``spilled``
record: it is kilobytes, not pages), dead at ``release`` with the slot.  The
page verbs move pool leaves only.  What cannot carry it (a shared prefix
needs the state AT the prefix's end) is refused by the engine by name.

A model whose **window layers keep a pool of their own** beside its full
layers' (``paged/window_pages.py``: leaves named ``paged/tree.py:WINDOW_POOL``)
has that pool's allocator and page table owned here too, as ``window``:
``can_admit``, ``attach`` and ``ensure`` hold where BOTH pools have the
pages, ``ensure`` first gives back the window pages the tick leaves behind,
``release`` empties both rows and ``audit`` covers both allocators.  The
verbs that move ``leaf[page]`` (spill, restore, copy-on-write) move the
first pool's leaves only, and every feature that would call them for such a
model is refused by name.

A request is duck-typed (``uid``, ``slot``, ``ctx``, ``prompt``,
``generated``, ``prefill_done``, ``spilled``, ``pc_*``); nothing here imports
the engine.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.paged import (PageAllocator, map_cache,
                                           pages_for, split_cache,
                                           state_bytes_per_seq)
from deepspeed_tpu.inference.prefix_cache import ROOT_HASH, PrefixCacheIndex
from deepspeed_tpu.telemetry import trace
from deepspeed_tpu.utils.logging import logger

__all__ = ["KVState"]


class KVState:
    """The paged pool, its tiers and its prefix index, for ``max_seqs``
    slots.  ``cache`` is the device tree of page pools (every leaf leads
    with the page dim) and of per-slot state, if the model has any (those
    leaves lead with the slot dim).  ``host_stats`` / ``request_latency`` are
    the engine's: the ``prefix`` / ``spill`` / ``restore`` spans and the
    per-request spill and restore stalls are recorded where the pages
    move."""

    def __init__(self, cache, *, num_pages: int, page_size: int,
                 max_seqs: int, max_seq_len: int, kv_reserve: str,
                 tier_cfg, pfx_cfg, host_stats, request_latency,
                 window: Any = None):
        assert kv_reserve in ("on_demand", "worst_case"), kv_reserve
        self.cache = cache
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_seq_len = int(max_seq_len)
        self.pages_per_seq = pages_for(max_seq_len, self.page_size)
        self.kv_reserve = kv_reserve
        self.host_stats = host_stats
        self.request_latency = request_latency
        self.allocator = PageAllocator(self.num_pages, self.page_size)
        self.page_table = np.full((max_seqs, self.pages_per_seq), -1,
                                  np.int32)
        # the window layers' own allocator and table (``paged.WindowPool``);
        # None: every layer's pages lie behind the one table above
        self.window = window
        self.spills = 0                # sessions spilled to the tiers
        self.restores = 0              # sessions restored bit-identically
        self._tier_gather = None       # jitted fixed-shape page gather
        self._tier_scatter = None      # jitted fixed-shape page scatter
        self._cow_jit = None           # jitted fixed-shape page copy
        self._state_jits = None        # jitted per-slot state get / put / zero
        # bytes of per-slot state one sequence owns (0: the model has none)
        self.state_bytes_per_seq = state_bytes_per_seq(cache)

        # -- tiered KV spill store (HBM -> host RAM -> NVMe) --
        self.tier_cfg = tier_cfg
        self.tiering = None
        if tier_cfg.enabled:
            assert kv_reserve == "on_demand", (
                "kv_tiering requires kv_reserve='on_demand' — the spill "
                "tiers ARE the on-demand model's overflow story; a "
                "worst-case reservation could never admit what tiering "
                "holds")
            from deepspeed_tpu.inference.kv_tiering import TieredKVStore

            leaves = split_cache(cache)[0]
            assert all(leaf.shape[0] == self.num_pages
                       for leaf in leaves), (
                "every paged-KV cache leaf must lead with the page dim")
            self.tiering = TieredKVStore(
                page_shapes=[leaf.shape[1:] for leaf in leaves],
                page_dtypes=[np.dtype(leaf.dtype) for leaf in leaves],
                pages_per_seq=self.pages_per_seq,
                **{k: getattr(tier_cfg, k) for k in (
                    "host_pages", "nvme_pages", "nvme_dir", "use_odirect",
                    "prefetch", "verify", "checksum", "max_reread",
                    "nvme_fail_threshold", "probe_every")})
        # -- cross-request prefix cache over the paged pool --
        self.pfx_cfg = pfx_cfg
        self.pfx: Optional[PrefixCacheIndex] = None
        if pfx_cfg.enabled:
            self.pfx = PrefixCacheIndex(
                self.allocator, self.page_size,
                max_entries=pfx_cfg.max_index_entries,
                min_match_pages=pfx_cfg.min_match_pages)
            if self.tiering is not None:
                # under pool pressure, cold single-ref prefix pages
                # demote into the tier store keyed by prefix hash (one
                # restore serves every waiter) instead of being dropped
                self.pfx.demote = self._pfx_demote
                self.pfx.drop_spilled = self.tiering.drop

    def describe(self) -> str:
        """The tiers and the index in one log fragment."""
        note = ""
        if self.tiering is not None:
            note = (f" kv_tiering=host:{self.tier_cfg.host_pages}"
                    f"+nvme:{self.tier_cfg.nvme_pages}p")
        if self.pfx is not None:
            note += (f" prefix_cache=max:{self.pfx_cfg.max_index_entries}"
                     f"/min:{self.pfx_cfg.min_match_pages}p")
        return note

    def cache_bytes(self) -> int:
        """Device bytes held by the paged KV cache (scales with
        ``num_pages``, the blocked-KV contract the reference's allocator
        provides — NOT with ``max_seqs * max_seq_len``)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(self.cache))

    def close(self, parked: Iterable[Any] = ()) -> None:
        """Release the spill-holds of ``parked`` requests, the prefix
        index's references and the tier store's resources (AIO handle,
        staging buffers, digest pool, spill files).  Idempotent."""
        for r in parked:
            self.forget(r)
        if self.pfx is not None:
            self.pfx.clear()
            self.pfx = None
        if self.tiering is not None:
            self.tiering.close()
            self.tiering = None

    # -- capacity ---------------------------------------------------------

    def check_capacity(self, total: int) -> None:
        """Raise ``ValueError`` for a sequence of ``total`` tokens that
        could never be scheduled on this pool, even after evicting every
        other sequence."""
        total_pages = self.allocator.pages_for(total)
        usable = self.num_pages - 1
        if self.window is not None:
            self.window.check_capacity(total)
        if self.tiering is None:
            if total_pages > usable:
                raise ValueError(
                    f"request needs {total_pages} KV "
                    f"pages but the engine owns {usable} "
                    "usable pages — even after evicting every other "
                    "sequence it could never be scheduled; raise "
                    "num_pages")
            return
        # the working-set bound stays an admission-time check, so tiering
        # keeps accepting requests beyond HBM whose others spill
        # (max_new_tokens is a budget, not a promise); the COMBINED-TIER
        # total must fit HBM + host + NVMe
        cap = usable + self.tiering.budget_pages
        if total_pages > cap:
            raise ValueError(
                f"request needs {total_pages} KV pages in total but "
                f"the combined tiers — HBM ({usable} usable) + host "
                f"({self.tiering.host_budget}) + NVMe "
                f"({self.tiering.nvme_budget}) — hold only {cap} "
                "— it could never be scheduled; raise num_pages or "
                "the kv_tiering host_pages/nvme_pages budgets")

    # -- admission --------------------------------------------------------

    def need(self, req, block: int) -> int:
        """Token coverage an admission of ``req`` reserves (``block``:
        the decode block's ticks) — ONE formula for the admission and
        for the pipelined loop's probe, so the pipeline reconciles at
        precisely the steps where ``pipeline=False`` would admit."""
        ctx_len = req.ctx_len
        rem = max(req.max_new_tokens - len(req.generated), 1)
        if self.kv_reserve == "worst_case":
            # worst case INCLUDING re-prefilled output for evicted
            # continuations (their ctx carries earlier tokens)
            return ctx_len + req.max_new_tokens - len(req.generated)
        if req.spilled is not None and req.prefill_done >= ctx_len:
            # spilled decode-phase continuation: attach allocates for
            # its full restored length, not just the prompt
            return req.length + min(block, rem)
        # on-demand (reference can_schedule): context + the first
        # decode block; growth happens per block
        return ctx_len + min(block, rem)

    def fresh_pages_needed(self, req, need: int, touch: bool = False):
        """Free pages an admission of ``req`` would consume, after
        prefix-cache attaches (resident matched pages cost nothing; a
        FULL match costs one extra page for the COW re-prefill) and
        spill-hold re-attaches.  Returns ``(fresh, matched_entries)``.
        ``touch=False`` for probes — LRU order must not move until the
        admission actually happens."""
        total = self.allocator.pages_for(need)
        if req.spilled is not None:
            return total - len(req.spilled.get("shared_pages", ())), []
        if self.pfx is None:
            return total, []
        ctx = req.ctx if req.ctx is not None else req.prompt
        entries = self.pfx.match(ctx, touch=touch)
        resident = sum(1 for e in entries if e.state == "resident")
        full = bool(entries) and (
            len(entries) * self.page_size == ctx.size)
        return total - resident + (1 if full else 0), entries

    def can_admit(self, req, need: int) -> bool:
        """Would the pool cover an admission of ``req`` right now?
        Evaluated from EXACT state (free list + what the index could
        give back), without moving the index's LRU order."""
        fresh, entries = self.fresh_pages_needed(req, need, touch=False)
        avail = self.allocator.free_pages
        if self.pfx is not None:
            avail += self.pfx.reclaimable(exclude={e.key for e in entries})
        return fresh <= avail and (self.window is None
                                   or self.window.can_admit(need))

    def attach(self, req, need: int) -> bool:
        """Build slot ``req.slot``'s page run for an admission covering
        ``need`` tokens: prefix-cache attaches (and tombstone revivals)
        first, spill-hold re-attaches for a restoring request, COW of
        the last page on a FULL prefix match, then fresh pages for the
        remainder.  False when revival failures shrank the match below
        what the pool can cover (caller gives the slot back)."""
        i = req.slot
        st = self.host_stats
        self.page_table[i, :] = -1
        attached = 0
        full = False
        if req.spilled is not None:
            shared = [int(p) for p in req.spilled.get("shared_pages", ())]
            if shared:
                self.allocator.attach(i, shared)
                self.page_table[i, :len(shared)] = shared
                attached = len(shared)
        elif self.pfx is not None:
            with st.stage("prefix"):
                entries = self.pfx.match(req.ctx, touch=True)
                pages_att: List[int] = []
                parent = ROOT_HASH
                for e in entries:
                    if e.state == "spilled" and not self._pfx_revive(e):
                        break
                    pages_att.append(int(e.page))
                    parent = e.key
                attached = len(pages_att)
                if attached:
                    self.allocator.attach(i, pages_att)
                    self.page_table[i, :attached] = pages_att
                    full = attached * self.page_size == req.ctx_len
                    # the matched pages are already prefilled: schedule
                    # only the suffix.  A FULL match still re-prefills
                    # its LAST token through the fused program (after a
                    # COW below) so the first sampled token's logits
                    # come from the same compiled program as a cache-off
                    # run — the bit-parity contract
                    req.prefill_done = attached * self.page_size - (
                        1 if full else 0)
                    req.pc_parent = parent
                    req.pc_pages = attached
                    req.pc_cached = req.prefill_done
                    st.prefix_hits += 1
                    st.prefix_hit_pages += attached
                    st.prefix_hit_tokens += req.prefill_done
                else:
                    st.prefix_misses += 1
        grow_n = self.allocator.pages_for(need) - attached
        want_free = grow_n + (1 if full else 0)
        self._reclaim_for(want_free)
        if want_free > self.allocator.free_pages:
            return False
        if self.window is not None and not self.window.attach(i, need):
            return False
        if full:
            # the write frontier (position ctx_len - 1) lands in the
            # last matched page: make it private before the re-prefill
            old, new = self.allocator.cow(i, attached - 1)
            if new != old:
                self._cow_copy(old, new)
                self.page_table[i, attached - 1] = new
        if grow_n > 0:
            pages = self.allocator.grow(i, grow_n)
            self.page_table[i, attached:attached + grow_n] = pages
        if self.state_bytes_per_seq:
            # the slot's previous tenant must not leak into this one
            self._put_state(i, None)
        self._gauge()
        self.request_latency.on_admit(req.uid)
        if trace.enabled:
            trace.event("request_admit", cat="request", uid=req.uid,
                        slot=i, pages=self.allocator.owned(i),
                        cached_pages=req.pc_pages
                        if req.spilled is None else 0,
                        spilled=req.spilled is not None)
        return True

    # -- growth, publication, release --------------------------------------

    def ensure(self, slot: int, upto_tokens: int,
               first_row: Optional[int] = None) -> bool:
        """Grow ``slot``'s page run to cover ``upto_tokens`` cache
        positions; False when the pool can't (scheduler backpressure —
        the sequence sits this tick out, or gets evicted).  ``first_row``:
        the first position the tick that asks will write; a window pool
        first takes back what lies a window behind it, and both pools must
        cover ``upto_tokens``."""
        upto_tokens = min(upto_tokens, self.max_seq_len)
        if self.window is not None and not self.window.ensure(
                slot, upto_tokens, first_row):
            return False
        need = pages_for(upto_tokens, self.page_size)
        have = self.allocator.owned(slot)
        if need <= have:
            return True
        self._reclaim_for(need - have)
        if need - have > self.allocator.free_pages:
            return False
        pages = self.allocator.grow(slot, need - have)
        self.page_table[slot, have:have + len(pages)] = pages
        self._gauge()
        return True

    def coverable_tokens(self, slot: int) -> int:
        """Tokens ``slot`` could cover if it took every page the pool
        can still give (cold prefix-index pages count: :meth:`ensure`
        reclaims them on demand); the lesser of the two pools' answers."""
        tokens = (self.allocator.owned(slot) + self.allocator.free_pages +
                  (self.pfx.reclaimable() if self.pfx is not None else 0)
                  ) * self.page_size
        return tokens if self.window is None else min(
            tokens, self.window.coverable_tokens(slot))

    def _gauge(self) -> None:
        """The first pool's pages in use, for the dispatch spans of a model
        that has a window pool beside it."""
        if self.window is not None:
            self.host_stats.full_pages_held = (
                self.num_pages - 1 - self.allocator.free_pages)

    def _reclaim_for(self, n_pages: int) -> None:
        """Ask the prefix index to give back cold single-reference pages
        when the free list can't cover ``n_pages``.  Safe against any
        page a live slot uses: those hold a slot reference on top of the
        index's, so the index never reclaims them."""
        if self.pfx is None:
            return
        short = n_pages - self.allocator.free_pages
        if short > 0:
            self.pfx.reclaim(short)

    def publish(self, req, tokens, written: int) -> None:
        """Offer every full page of ``tokens[:written]`` that ``req``'s
        slot holds and has not registered yet to the prefix index
        (chain-hashed onto the request's registered prefix).  Keys off
        how far the rows are written, not dispatch timing, so it is
        identical in both pipeline modes.  A no-op with the index off."""
        if self.pfx is None:
            return
        page = self.page_size
        while (req.pc_pages + 1) * page <= written:
            k = req.pc_pages
            req.pc_parent = self.pfx.register(
                req.pc_parent, tokens[k * page:(k + 1) * page],
                int(self.page_table[req.slot, k]))
            req.pc_pages += 1

    def release(self, slot: int) -> None:
        """Give ``slot``'s pages back: every page loses the slot's
        reference (shared ones stay with their other holders) and the
        page-table row empties.  The slot's per-slot state dies with it:
        nothing reads it before the next ``attach`` zeroes it."""
        self.allocator.free(slot)
        self.page_table[slot, :] = -1
        if self.window is not None:
            self.window.release(slot)
            self._gauge()

    @staticmethod
    def pick_victim(stalled, last_sched):
        """Coldest page-stalled sequence: least-recently scheduled by
        the scheduler's per-slot stamp (tie: youngest) — it has waited
        longest for pages and will wait longest for them, so parking it
        frees the most useful HBM."""
        return min(stalled, key=lambda r: (last_sched[r.slot], -r.uid))

    # -- page movement (fixed shapes: compiled once) -----------------------

    def _tier_jits(self):
        """The two fixed-shape page-movement programs (compiled once,
        first spill/restore — the zero-new-compilation guard covers the
        steady state after that):

        - gather: ``[pages_per_seq]`` page rows out of every cache leaf
          (indices padded with the trash page 0 — always allocated).
        - scatter: the same rows back in, donating the cache buffers;
          pad indices point one past the pool and ``mode='drop'``
          discards them, so a partial restore writes exactly its live
          rows."""
        if self._tier_gather is None:
            def gather(cache, idx):
                return [jnp.take(l, idx, axis=0)
                        for l in split_cache(cache)[0]]

            def scatter(cache, idx, rows):
                rows = iter(rows)      # one a pool leaf, in leaf order
                return map_cache(
                    cache, lambda l: l.at[idx].set(next(rows), mode="drop"))

            self._tier_gather = jax.jit(gather)
            self._tier_scatter = jax.jit(scatter, donate_argnums=(0,))
        return self._tier_gather, self._tier_scatter

    def _gather_rows(self, pages) -> List[np.ndarray]:
        """The rows of ``pages`` out of every cache leaf, on the host."""
        n = len(pages)
        gather, _ = self._tier_jits()
        idx = np.zeros((self.pages_per_seq,), np.int32)     # pad: trash
        idx[:n] = pages
        rows = jax.device_get(gather(self.cache, jnp.asarray(idx)))
        return [np.asarray(leaf[:n]) for leaf in rows]

    def _scatter_rows(self, pages, arrs) -> None:
        """Write ``arrs`` (one ``[n, ...]`` array per cache leaf) into
        ``pages`` of the pool."""
        n = len(pages)
        _, scatter = self._tier_jits()
        # pad indices past the pool: mode='drop' drops them
        idx = np.full((self.pages_per_seq,), self.num_pages, np.int32)
        idx[:n] = pages
        leaves = []
        for a in arrs:
            full = np.zeros((self.pages_per_seq,) + a.shape[1:], a.dtype)
            full[:n] = a
            leaves.append(jnp.asarray(full))
        self.cache = scatter(self.cache, jnp.asarray(idx), leaves)

    def _cow_copy(self, src: int, dst: int) -> None:
        """Fixed-shape device copy of one page row across every cache
        leaf — the copy half of copy-on-write.  ``src``/``dst`` are
        traced int32 operands, so every COW reuses one compiled
        program."""
        st = self.host_stats
        if self._cow_jit is None:
            self._cow_jit = jax.jit(
                lambda cache, s, d: map_cache(
                    cache, lambda l: l.at[d].set(l[s])),
                donate_argnums=(0,))
        with st.stage("prefix"):
            self.cache = self._cow_jit(self.cache, jnp.int32(src),
                                       jnp.int32(dst))
        st.prefix_cow_copies += 1
        if trace.enabled:
            trace.event("prefix_cow", cat="request", src=int(src),
                        dst=int(dst))

    # -- per-slot state beside the pages ------------------------------------

    def _state_programs(self):
        """The three fixed-shape programs that move one slot's state: get
        (every state leaf's row of the slot), put (the same rows back) and
        zero (the slot's rows cleared, nothing uploaded); the last two
        donate the cache."""
        if self._state_jits is None:
            def get(cache, slot):
                return [l[slot] for l in split_cache(cache)[1]]

            def put(cache, slot, rows):
                rows = iter(rows)      # one a state leaf, in leaf order
                return map_cache(
                    cache, state_fn=lambda l: l.at[slot].set(next(rows)))

            def zero(cache, slot):
                return map_cache(
                    cache, state_fn=lambda l: l.at[slot].set(0))

            self._state_jits = (jax.jit(get),
                                jax.jit(put, donate_argnums=(0,)),
                                jax.jit(zero, donate_argnums=(0,)))
        return self._state_jits

    def _get_state(self, slot: int) -> List[np.ndarray]:
        """``slot``'s state on the host, one array a state leaf."""
        get = self._state_programs()[0]
        return [np.asarray(a) for a in jax.device_get(
            get(self.cache, jnp.int32(slot)))]

    def _put_state(self, slot: int, rows: Optional[List[np.ndarray]]
                   ) -> None:
        """Write ``rows`` (as :meth:`_get_state` gave them; None: zeros)
        into ``slot``'s state."""
        _, put, zero = self._state_programs()
        if rows is None:
            self.cache = zero(self.cache, jnp.int32(slot))
        else:
            self.cache = put(self.cache, jnp.int32(slot),
                             [jnp.asarray(a) for a in rows])

    # -- tiered spill / restore (HBM <-> host RAM <-> NVMe) ----------------

    def _state_attrs(self) -> Dict[str, int]:
        """What the ``engine.spill`` / ``engine.restore`` spans say of the
        per-slot state they carry; nothing for a model without any."""
        n = self.state_bytes_per_seq
        return {"state_bytes": n} if n else {}

    def live_tokens(self, r) -> int:
        """Cache rows that hold real KV for ``r`` RIGHT NOW.  Decode
        phase: the last sampled token's row is written by the NEXT tick
        (at position length-1), so ``length - 1`` rows are live.
        Prefill phase: exactly the prefilled prefix."""
        if r.prefill_done >= r.ctx_len:
            return r.length - 1
        return r.prefill_done

    def spill(self, r, last_tok: int) -> bool:
        """Park ``r``'s KV in the spill tiers instead of destroying it:
        gather its live page rows and hand them to the tier store,
        recording in ``r.spilled`` what a restore needs (``last_tok``:
        its pending token).  Returns False when the tiers can't take it
        (caller falls back to a re-prefill eviction); on True the caller
        gives the slot back and requeues ``r`` as a RESTORABLE
        continuation.  Restore is bit-identical to never having
        spilled: the exact cache rows come back."""
        live = self.live_tokens(r)
        n_live = pages_for(live, self.page_size) if live > 0 else 0
        if n_live == 0 or self.tiering is None:
            return False
        # shared-prefix pages (refcount > 1: the prefix index or another
        # sequence also holds them) are maximally hot — they never leave
        # HBM.  Take a spill-hold (+1 ref) on the maximal shared PREFIX
        # of the live run and spill only the private suffix; no owner
        # ever writes a shared page (COW precedes any write), so the
        # rows stay valid for re-attach at restore
        j = 0
        while j < n_live and self.allocator.refcount(
                int(self.page_table[r.slot, j])) > 1:
            j += 1
        n_priv = n_live - j
        if n_priv > 0 and not self.tiering.can_spill(n_priv):
            return False
        shared = [int(p) for p in self.page_table[r.slot, :j]]
        state = self._state_attrs()
        with self.host_stats.stage("spill", **state):
            if n_priv > 0:
                rows = self._gather_rows(self.page_table[r.slot, j:n_live])
                try:
                    self.tiering.spill(r.uid, rows, n_priv)
                except RuntimeError:
                    return False           # tiers full: caller evicts
            r.spilled = {"last_tok": int(last_tok),
                         "n_pages": n_priv, "live_tokens": live,
                         "shared_pages": shared}
            if state:
                # the rows are the state after exactly ``live`` tokens
                r.spilled["state"] = self._get_state(r.slot)
            for p in shared:
                self.allocator.incref(p)   # spill-hold survives release
        self.spills += 1
        self.request_latency.on_spill(r.uid)
        if trace.enabled:
            trace.event("request_spill", cat="request", uid=r.uid,
                        pages=int(n_live), live_tokens=int(live))
        logger.info(f"ragged engine: spilled uid={r.uid} ({n_live} pages,"
                    f" {live} live tokens) to the KV tiers — restore is "
                    "a page upload, not a re-prefill")
        return True

    def restore(self, req) -> Optional[int]:
        """Upload ``req``'s spilled page rows into its freshly attached
        pages and return its pending last token.  On unrecoverable
        corruption (:class:`KVRestoreError` — the store already
        quarantined the payload) returns None, loudly: the caller gives
        the slot back and re-prefills the session from its own tokens.
        Either way the spill-holds are released (the slot's attach owns
        its references) and ``req.spilled`` clears."""
        from deepspeed_tpu.inference.kv_tiering import KVRestoreError

        info = req.spilled
        n = info["n_pages"]                 # private pages in the tiers
        shared = info.get("shared_pages", [])
        jn = len(shared)                    # shared prefix: attach's
        t_restore0 = time.perf_counter()
        failed = None
        try:
            if n > 0 or "state" in info:
                with self.host_stats.stage("restore",
                                           **self._state_attrs()):
                    if n > 0:
                        self._scatter_rows(
                            self.page_table[req.slot, jn:jn + n],
                            self.tiering.restore(req.uid))
                    if "state" in info:
                        self._put_state(req.slot, info["state"])
        except KVRestoreError as e:
            failed = e
        req.spilled = None
        for p in shared:
            self.allocator.decref(p)
        self.request_latency.on_restore_stall(
            req.uid, time.perf_counter() - t_restore0)
        if failed is not None:
            self.request_latency.on_error(req.uid)
            if trace.enabled:
                trace.event("request_restore_failed", cat="request",
                            uid=req.uid, page=int(failed.page))
            logger.error(
                f"ragged engine: restore of uid={req.uid} failed "
                f"verification (page {failed.page}; payload quarantined) "
                "— re-prefilling the session from its own tokens")
            return None
        self.restores += 1
        if trace.enabled:
            trace.event("request_restore", cat="request", uid=req.uid,
                        pages=int(n), shared_pages=int(jn))
        return int(info["last_tok"])

    def _pfx_demote(self, e) -> bool:
        """Index-LRU hook: move a single-reference prefix page's KV into
        the tiered store under the entry's PREFIX-HASH key (not a uid —
        one restore serves every future waiter).  Returns False when the
        tiers can't take it, in which case the index drops the entry."""
        if self.tiering is None or not self.tiering.can_spill(1):
            return False
        with self.host_stats.stage("spill"):
            rows = self._gather_rows([e.page])
            try:
                self.tiering.spill(PrefixCacheIndex.tier_key(e.key), rows, 1)
            except RuntimeError:
                return False
        if trace.enabled:
            trace.event("prefix_demote", cat="request",
                        key=PrefixCacheIndex.tier_key(e.key))
        return True

    def _pfx_revive(self, e) -> bool:
        """Bring a demoted prefix page back into a fresh pool page so an
        admission can attach it.  On failure the tombstone is dropped —
        the requester falls back to computing that page itself."""
        from deepspeed_tpu.inference.kv_tiering import KVRestoreError

        if self.tiering is None:
            self.pfx._drop(e)
            return False
        if self.allocator.free_pages < 1:
            return False
        page = self.allocator.take_page()
        try:
            with self.host_stats.stage("restore"):
                self._scatter_rows(
                    [page],
                    self.tiering.restore(PrefixCacheIndex.tier_key(e.key)))
        except KVRestoreError:
            self.allocator.decref(page)
            self.pfx._drop(e)              # payload quarantined: forget
            return False
        self.pfx.revive(e, page)
        if trace.enabled:
            trace.event("prefix_revive", cat="request",
                        key=PrefixCacheIndex.tier_key(e.key),
                        page=int(page))
        return True

    # -- parked sessions ----------------------------------------------------

    def forget(self, r) -> None:
        """Release what a PARKED spilled request still holds: the
        spill-holds pinning its shared prefix pages resident, then the
        tiered payload itself."""
        if r.spilled is None:
            return
        for p in r.spilled.get("shared_pages", ()):
            self.allocator.decref(int(p))
        r.spilled["shared_pages"] = []
        if self.tiering is not None:
            self.tiering.drop(r.uid)

    def export(self, r) -> Dict[str, Any]:
        """Portable session blob for :meth:`adopt` on another replica.
        A spilled session's private pages travel in spill format via
        ``TieredKVStore.export_spilled`` (packed bytes + the spill-time
        digests, so the receiver's restore verifies them end to end);
        one pinning shared-prefix pages (rows in THIS pool — they cannot
        travel) folds to a re-prefill continuation."""
        blob: Dict[str, Any] = {
            "uid": int(r.uid),
            "prompt": np.asarray(r.prompt, np.int32),
            "max_new_tokens": int(r.max_new_tokens),
            "eos_token_id": r.eos_token_id,
            "do_sample": bool(r.do_sample),
            "temperature": float(r.temperature),
            "top_k": int(r.top_k),
            "top_p": float(r.top_p),
            "generated": [int(t) for t in r.generated],
            "ctx": (None if r.ctx is None
                    else np.asarray(r.ctx, np.int32)),
            "prefill_done": int(r.prefill_done),
            "spill": None}
        if r.spilled is not None:
            shared = r.spilled.get("shared_pages", ())
            n_priv = int(r.spilled.get("n_pages", 0))
            holds = (self.tiering is not None
                     and self.tiering.holds(r.uid))
            if shared or (n_priv > 0 and not holds):
                # fold to a re-prefill continuation; release the
                # spill-holds and the orphaned payload
                self.forget(r)
                blob["ctx"] = np.concatenate(
                    [r.prompt, np.asarray(r.generated, np.int32)])
                blob["prefill_done"] = 0
            else:
                blob["spill"] = {
                    "last_tok": int(r.spilled["last_tok"]),
                    "live_tokens": int(r.spilled["live_tokens"]),
                    "payload": (self.tiering.export_spilled(r.uid)
                                if n_priv > 0 else None)}
                if "state" in r.spilled:
                    blob["spill"]["state"] = r.spilled["state"]
        return blob

    def adopt(self, spill: Dict[str, Any], req) -> bool:
        """Install an exported session's ``spill`` part for the local
        request ``req``: its payload parks in the local tier store under
        ``req.uid`` with the donor's digests, and ``req.spilled`` is set
        so the normal spilled re-admission restores it.  False when the
        local tiers can't hold it — the caller folds ``req`` to a
        re-prefill continuation instead of dropping it."""
        payload = spill.get("payload")
        if payload is not None:
            if self.tiering is None:
                return False
            try:
                self.tiering.import_spilled(req.uid, payload)
            except (ValueError, RuntimeError):
                return False
        req.spilled = {
            "last_tok": int(spill["last_tok"]),
            "n_pages": (int(payload["n_pages"])
                        if payload is not None else 0),
            "live_tokens": int(spill["live_tokens"]),
            "shared_pages": []}
        if "state" in spill:
            req.spilled["state"] = [np.asarray(a) for a in spill["state"]]
        return True

    # -- audit ----------------------------------------------------------------

    def audit(self, slots, parked: Iterable[Any] = ()) -> Dict[str, int]:
        """Refcount-conservation audit: every physical page's refcount
        must equal the number of holders that can reach it — the
        page-table rows of ``slots`` (the scheduler's slot list),
        resident prefix-index entries, and spill-holds on ``parked``
        requests' shared prefixes.  Spilled payloads (tiers) hold no
        pool pages by construction.  Delegates the per-page equality to
        :meth:`PageAllocator.audit`."""
        external: Dict[int, int] = {}
        if self.pfx is not None:
            for e in self.pfx._entries.values():
                if e.state == "resident":
                    external[e.page] = external.get(e.page, 0) + 1
        for r in parked:
            if r.spilled is not None:
                for p in r.spilled.get("shared_pages", ()):
                    external[p] = external.get(p, 0) + 1
        for s, r in enumerate(slots):
            if r is None:
                continue
            row = [int(p) for p in self.page_table[s] if p >= 0]
            owned = self.allocator.owned_pages(s)
            assert row == owned, (
                f"slot {s}: page-table row {row} != allocator "
                f"ownership {owned}")
        counts = self.allocator.audit(external=external)
        if self.window is not None:
            counts["window"] = self.window.audit(slots)
        return counts
