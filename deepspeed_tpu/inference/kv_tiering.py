"""Tiered paged-KV store: HBM -> host RAM -> NVMe spill tiers.

Concurrent serving sessions are capped by HBM because KV pages live
only in the device pool (``inference/paged/``): an idle or page-
stalled session holds hot memory until the engine evicts it and repays
its whole prefill from scratch.  This store extends the pool past HBM
the same way ZeRO-Infinity extends optimizer state past device memory:

    HBM (PageAllocator pool)  --spill-->  host RAM  --overflow-->  NVMe
         live decode pages         pinned page-aligned       bucketed AIO
                                   staging buffers           qd-128 files

A spilled sequence's pages are packed page-major into a page-aligned
host buffer (one contiguous slice per page across every cache leaf,
stride padded to the 4096-byte O_DIRECT alignment), digested per page
(``resilience/sdc.py`` — the spill path trusts neither host DRAM nor
disk), and demoted to NVMe through the hardened AIO path (qd-128,
optional O_DIRECT, fallocate preallocation) when the host budget
overflows.  Restore verifies every page against its spill-time digest
behind the ``kv.read_page`` fault hook: a transient flip heals via
re-read (NVMe) / re-copy (host tier), persistent corruption
quarantines the spilled payload (``.quarantine`` rename for
postmortem, like the swap and checkpoint layers) and raises
:class:`KVRestoreError` so the engine re-prefills loudly instead of
decoding on garbage.

All asynchrony (NVMe write-back, predictive NVMe->host prefetch under
the decode block) runs on the shared bounded-async-stage substrate
(``utils/async_stage.py``): bounded in-flight windows, forced-drain
points, per-stage timers in the existing telemetry schema.

**Degraded mode** (serving fault tolerance): a failing NVMe device must
not take serving down with it.  ``nvme_fail_threshold`` hard NVMe
failures since the last clean probe (EIO/ENOSPC at write submit or
cold read, or a quarantine of an NVMe-backed payload) trip the tier
OFFLINE:
``can_spill``/``_demote`` fall back host-only, every parked NVMe-backed
payload is folded (its session re-prefills via
:class:`KVRestoreError` on the next restore — loud, never silent), and
a ``tier_degraded`` flight record + trace event + metric mark the
trip.  While offline, blocked spills periodically run
:meth:`probe_nvme` — a write/read/verify round-trip through the same
``kv.write`` fault hook as the spill path — and a clean probe re-arms
the tier (``tier_rearmed``).

The store holds HOST STATE ONLY — device-side gather/scatter of pages
stays in the engine (it owns the cache pytree and the jitted
fixed-shape programs).  The unit of exchange is a list of per-leaf
``[n_pages, *leaf_page_shape]`` numpy arrays.
"""
from __future__ import annotations

import atexit
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple, Union

import numpy as np

from deepspeed_tpu.resilience import faults
from deepspeed_tpu.resilience.sdc import DigestPool, digest as sdc_digest
from deepspeed_tpu.telemetry import trace
from deepspeed_tpu.utils.async_stage import (BoundedAsyncStage,
                                             HostBufferPool, StageTimers)
from deepspeed_tpu.utils.logging import logger

__all__ = ["TieredKVStore", "KVRestoreError"]

# Payload key: a request uid (int) for sequence spills, or an opaque
# string for payloads owned by other subsystems — the prefix cache
# demotes index pages under their prefix-hash key ("pfx-<hash>"), so
# one tier entry serves every future requester of that prefix.  Keys
# of both types coexist in one store (dict keys; the spill filename
# embeds the key via str()).
Key = Union[int, str]

_ALIGN = 4096                        # O_DIRECT / page alignment


class KVRestoreError(RuntimeError):
    """A spilled page failed verification beyond recovery; the payload
    is quarantined and the session must re-prefill."""

    def __init__(self, uid: "Key", page: int, msg: str) -> None:
        super().__init__(msg)
        self.uid = uid
        self.page = page


class _Entry:
    """One spilled sequence's payload in the tiers."""

    __slots__ = ("uid", "n_pages", "state", "buf", "slot", "path",
                 "digests", "seq")

    def __init__(self, uid: "Key", n_pages: int) -> None:
        self.uid = uid
        self.n_pages = n_pages
        self.state = "host"         # host | writing | nvme | reading
        self.buf: Optional[np.ndarray] = None   # packed bytes (host/writing)
        self.slot: Optional[int] = None          # staging pool slot (reading)
        self.path: Optional[str] = None          # spill file (writing/nvme)
        self.digests: Optional[List[tuple]] = None  # per-page (sum, nbytes)
        self.seq = 0                # spill order (demotion picks oldest)


class TieredKVStore:
    """Host-RAM + NVMe spill tiers for paged KV, per-page verified.

    Parameters
    ----------
    page_shapes / page_dtypes:
        per cache leaf (flattened tree order): the per-PAGE shape
        (leaf shape minus the leading page dim) and numpy dtype.  They
        fix the packed layout; the engine owns the treedef.
    host_pages / nvme_pages:
        tier budgets in KV pages (0 disables the tier).
    """

    def __init__(self, *, page_shapes: Sequence[tuple],
                 page_dtypes: Sequence[Any], pages_per_seq: int,
                 host_pages: int, nvme_pages: int = 0,
                 nvme_dir: Optional[str] = None, use_odirect: bool = False,
                 prefetch: bool = True, verify: bool = True,
                 checksum: str = "sum64", max_reread: int = 2,
                 write_depth: int = 4, read_depth: int = 2,
                 nvme_fail_threshold: int = 3,
                 probe_every: int = 8) -> None:
        self.pages_per_seq = int(pages_per_seq)
        self.host_budget = int(host_pages)
        self.nvme_budget = int(nvme_pages)
        self.verify = bool(verify)
        self.algo = str(checksum)
        self.max_reread = max(0, int(max_reread))
        self.prefetch_enabled = bool(prefetch) and self.nvme_budget > 0
        self.use_odirect = bool(use_odirect)
        # degraded mode: nvme_fail_threshold hard NVMe failures since
        # the last clean probe trip the tier offline (host-only until a
        # clean probe_nvme round-trip re-arms it, attempted every
        # probe_every spills blocked on the missing tier)
        self.nvme_fail_threshold = max(1, int(nvme_fail_threshold))
        self.probe_every = max(1, int(probe_every))
        self.nvme_offline = False
        self._nvme_streak = 0        # hard failures since last clean probe
        self._probe_backoff = 0      # blocked spills since last probe
        self._lost: Set[Key] = set()  # folded at trip: restore re-prefills

        # packed page layout: each leaf's bytes at a fixed offset inside
        # the page's stride-aligned slice (padding zeroed at pack time
        # so digests and spill files are deterministic)
        self._shapes = [tuple(s) for s in page_shapes]
        self._dtypes = [np.dtype(d) for d in page_dtypes]
        self._widths = [int(np.prod(s)) * d.itemsize
                        for s, d in zip(self._shapes, self._dtypes)]
        self._offsets = list(np.cumsum([0] + self._widths[:-1]).astype(int))
        used = int(sum(self._widths))
        self.page_stride = (used + _ALIGN - 1) // _ALIGN * _ALIGN
        self._used_bytes = used

        # tier state
        self._entries: Dict[Key, _Entry] = {}
        self._host_used = 0          # pages resident in host buffers
        self._nvme_used = 0          # pages on (or being written to) NVMe
        self._seq = 0

        # substrate: timers + digest side pool + bounded IO windows
        # (cat="kv" labels every bracket's trace span — spill/restore
        # stalls land on the kv row of the exported trace)
        self.timers = StageTimers(cat="kv")
        self._digests = DigestPool(algo=self.algo, workers=2,
                                   timers=self.timers,
                                   thread_name_prefix="dstpu-kvtier")
        self._aio = None             # lazy aio_handle (NVMe tier only)
        self._writes = BoundedAsyncStage(self._wait_op, depth=write_depth,
                                         timers=self.timers, name="kv-write")
        self._reads = BoundedAsyncStage(self._wait_op, depth=read_depth,
                                        timers=self.timers, name="kv-read")
        # staging ring for NVMe reads (prefetch + sync restore); writes
        # stream from the entry's own buffer, which stays alive (and
        # immutable) until the bounded window joins the op
        self._staging: Optional[HostBufferPool] = None

        self.counters: Dict[str, int] = {
            "spills": 0, "restores": 0, "pages_spilled": 0,
            "pages_restored": 0, "pages_verified": 0, "demotions": 0,
            "nvme_spills": 0, "prefetch_hits": 0, "prefetch_misses": 0,
            "rereads": 0, "reread_recovered": 0, "quarantined": 0,
            "spill_fallbacks": 0, "bytes_spilled": 0, "bytes_restored": 0,
            "exports": 0, "imports": 0,
            # degraded mode (persistent NVMe failure -> host-only)
            "nvme_failures": 0, "tier_degraded": 0, "tier_rearmed": 0,
            "degraded_folds": 0, "probes": 0, "probe_failures": 0}

        self.spill_dir: Optional[str] = None
        if self.nvme_budget > 0:
            if not nvme_dir:
                raise ValueError("nvme_pages > 0 requires nvme_dir")
            os.makedirs(nvme_dir, exist_ok=True)
            self.spill_dir = tempfile.mkdtemp(prefix="kvtier-",
                                              dir=nvme_dir)
            atexit.register(shutil.rmtree, self.spill_dir,
                            ignore_errors=True)

    # -- substrate plumbing ----------------------------------------------

    def _wait_op(self, op: int) -> int:
        return self._handle().wait(op)

    def _handle(self):
        if self._aio is None:
            from deepspeed_tpu.io.aio import aio_handle

            self._aio = aio_handle(queue_depth=128, thread_count=2,
                                   use_odirect=self.use_odirect)
        return self._aio

    def _stage_ring(self) -> HostBufferPool:
        if self._staging is None:
            self._staging = HostBufferPool(
                self._reads.depth + 1,
                self.pages_per_seq * self.page_stride)
        return self._staging

    # -- capacity --------------------------------------------------------

    @property
    def budget_pages(self) -> int:
        """Total spill capacity in pages (what put_request may admit
        beyond the HBM pool)."""
        return self.host_budget + self.nvme_budget

    def free_pages(self) -> int:
        nvme_budget = 0 if self.nvme_offline else self.nvme_budget
        return ((self.host_budget - self._host_used)
                + max(0, nvme_budget - self._nvme_used))

    def can_spill(self, n_pages: int) -> bool:
        """Whether a ``n_pages`` spill can land somewhere (host, or
        host-after-demotion, or straight to NVMe).  With the NVMe tier
        offline (degraded mode) only the host budget counts; a spill
        blocked on the missing tier periodically triggers a
        :meth:`probe_nvme` revival attempt."""
        if self.nvme_offline and self.nvme_budget > 0:
            host_free = self.host_budget - self._host_used
            if n_pages <= self.host_budget and host_free >= n_pages:
                return True
            # the dead tier is the binding constraint: probe for revival
            self._probe_backoff += 1
            if self._probe_backoff >= self.probe_every:
                self._probe_backoff = 0
                if not self.probe_nvme():
                    return False
                # fall through re-armed
            else:
                return False
        if n_pages > max(self.host_budget,
                         0 if self.nvme_offline else self.nvme_budget):
            return False
        return self.free_pages() >= n_pages

    def holds(self, uid: Key) -> bool:
        return uid in self._entries

    # -- spill -----------------------------------------------------------

    def spill(self, uid: Key, arrs: List[np.ndarray],
              n_pages: int) -> None:
        """Take ownership of ``uid``'s pages (per-leaf
        ``[n_pages, ...]`` host arrays), digest them, and park them in
        the cheapest tier with room.  Raises ``RuntimeError`` when no
        tier fits (caller falls back to destructive eviction)."""
        assert uid not in self._entries, f"uid {uid} already spilled"
        if not self.can_spill(n_pages):
            self.counters["spill_fallbacks"] += 1
            raise RuntimeError(
                f"kv tiers full: need {n_pages} pages, host "
                f"{self.host_budget - self._host_used}/{self.host_budget} "
                f"nvme {self.nvme_budget - self._nvme_used}/"
                f"{self.nvme_budget} free")
        with self.timers.stage("spill"):
            ent = _Entry(uid, n_pages)
            self._seq += 1
            ent.seq = self._seq
            with self.timers.stage("spill_pack"):
                buf = self._pack(arrs, n_pages)
            ent.buf = buf
            host_free = self.host_budget - self._host_used
            try:
                if n_pages <= self.host_budget:
                    # host tier (demote oldest entries to make room)
                    if n_pages > host_free:
                        self._demote(n_pages - host_free)
                    self._entries[uid] = ent
                    self._host_used += n_pages
                else:
                    # oversized for host RAM: straight to NVMe
                    self._entries[uid] = ent
                    self._nvme_spill(ent)
            except RuntimeError:
                self._entries.pop(uid, None)
                self.counters["spill_fallbacks"] += 1
                raise
            # write-side digests overlap the write-back IO: the packed
            # buffer is immutable until the entry is restored or its
            # write is joined, so the side job races nothing
            if self.verify:
                self._digests.submit(
                    uid, lambda: [sdc_digest(b, self.algo)
                                  for b in buf.reshape(
                                      n_pages, self.page_stride)])
            self.counters["spills"] += 1
            self.counters["pages_spilled"] += n_pages
            self.counters["bytes_spilled"] += buf.nbytes

    def _pack(self, arrs: List[np.ndarray], n_pages: int) -> np.ndarray:
        from deepspeed_tpu.io.aio import aligned_empty

        buf = aligned_empty(n_pages * self.page_stride)
        b2 = buf.reshape(n_pages, self.page_stride)
        b2[:, self._used_bytes:] = 0
        for a, off, w in zip(arrs, self._offsets, self._widths):
            a = np.ascontiguousarray(a)
            b2[:, off:off + w] = a.reshape(n_pages, -1).view(np.uint8)
        return buf

    def _unpack(self, buf: np.ndarray, n_pages: int) -> List[np.ndarray]:
        b2 = buf[:n_pages * self.page_stride].reshape(n_pages,
                                                      self.page_stride)
        out = []
        for s, d, off, w in zip(self._shapes, self._dtypes,
                                self._offsets, self._widths):
            raw = np.ascontiguousarray(b2[:, off:off + w])
            out.append(raw.view(d).reshape((n_pages,) + s))
        return out

    # -- NVMe write-back -------------------------------------------------

    def _fname(self, uid: Key) -> str:
        return os.path.join(self.spill_dir, f"kv-{uid}.bin")

    def _nvme_spill(self, ent: _Entry) -> None:
        """Queue ``ent``'s buffer for NVMe write-back on the bounded
        window (fallocate sizes the file up-front inside async_pwrite;
        the buffer stays alive until the window joins the op).  A hard
        IO error at submit (or injected at the ``kv.write`` fault site)
        feeds the degraded-mode failure streak and raises
        ``RuntimeError`` so callers take their existing no-room
        fallback paths."""
        assert self.spill_dir is not None
        if self.nvme_offline:
            raise RuntimeError(
                "kv tiering: NVMe tier offline (degraded mode)")
        path = self._fname(ent.uid)
        try:
            d = faults.hook("kv.write", uid=ent.uid, path=path)
            if d is not None and d[0] in ("hang", "slow"):
                time.sleep(float(d[1]))
            with self.timers.stage("spill_write_submit"):
                op = self._handle().async_pwrite(ent.buf, path)
        except OSError as e:
            self._nvme_failure(e, f"write-back submit for uid {ent.uid}")
            raise RuntimeError(
                f"kv tiering: NVMe write-back failed for uid "
                f"{ent.uid}: {e}") from e
        ent.path = path
        ent.state = "writing"
        self._nvme_used += ent.n_pages
        buf = ent.buf               # keep a ref until the join

        def _done(_st, ent=ent, buf=buf):
            del buf
            if ent.state == "writing":      # not restored meanwhile
                ent.state = "nvme"
                ent.buf = None
            return _st

        self._writes.submit(("w", ent.uid), op, on_done=_done)
        self.counters["nvme_spills"] += 1

    def _demote(self, need_pages: int) -> None:
        """Move the oldest host-resident entries to NVMe until
        ``need_pages`` of host budget are free."""
        if self.nvme_offline:
            raise RuntimeError(
                "kv tiering: cannot demote — NVMe tier offline "
                "(degraded mode)")
        moved = 0
        for ent in sorted((e for e in self._entries.values()
                           if e.state == "host"), key=lambda e: e.seq):
            if moved >= need_pages:
                break
            if self.nvme_budget - self._nvme_used < ent.n_pages:
                continue
            self._nvme_spill(ent)
            self._host_used -= ent.n_pages
            self.counters["demotions"] += 1
            moved += ent.n_pages
        if moved < need_pages:
            raise RuntimeError(
                f"kv tiering: could not demote {need_pages} pages to "
                "NVMe (budget full)")

    # -- prefetch --------------------------------------------------------

    def prefetch(self, uids: Sequence[Key]) -> int:
        """Issue async NVMe->host reads for predicted next-scheduled
        spilled sequences; returns how many were started.  Runs under
        the decode block so restores overlap device work."""
        if not self.prefetch_enabled:
            return 0
        started = 0
        for uid in uids:
            ent = self._entries.get(uid)
            if ent is None or ent.state != "nvme":
                continue
            if self._stage_ring().free == 0:
                break
            slot, sbuf = self._stage_ring().acquire()
            ent.slot = slot
            ent.state = "reading"
            with self.timers.stage("prefetch_submit"):
                op = self._handle().async_pread(
                    sbuf[:ent.n_pages * self.page_stride], ent.path)
            self._reads.submit(("r", uid), op)
            started += 1
        return started

    # -- restore ---------------------------------------------------------

    def restore(self, uid: Key) -> List[np.ndarray]:
        """Hand back ``uid``'s pages as per-leaf ``[n_pages, ...]``
        arrays, each page verified against its spill-time digest (when
        ``verify``).  Drops the entry on success — the pages are HBM's
        again.  Raises :class:`KVRestoreError` after quarantining on
        unrecoverable corruption (the caller re-prefills loudly)."""
        self._check_lost(uid)
        ent = self._entries.get(uid)
        assert ent is not None, f"uid {uid} not spilled"
        with self.timers.stage("restore"):
            work = self._fetch(ent)
            digests = self._digests.pop(uid) if self.verify else None
            if self.verify:
                with self.timers.stage("restore_verify"):
                    self._verify_pages(ent, work, digests)
            arrs = self._unpack(work, ent.n_pages)
        self._drop(ent)
        self.counters["restores"] += 1
        self.counters["pages_restored"] += ent.n_pages
        self.counters["bytes_restored"] += ent.n_pages * self.page_stride
        return arrs

    def _fetch(self, ent: _Entry) -> np.ndarray:
        """Materialize the entry's packed bytes into a private working
        buffer (the tier copy / file stays pristine, so a re-read can
        heal a transient flip in the working copy)."""
        n = ent.n_pages * self.page_stride
        if ent.state == "writing":
            # write-back still in flight: the in-memory bytes are
            # authoritative; grab them before the join (whose on_done
            # flips the entry to nvme and drops the buffer ref)
            buf = ent.buf
            self._writes.pop(("w", ent.uid))
            with self.timers.stage("restore_copy"):
                return buf[:n].copy()
        if ent.state == "host":
            with self.timers.stage("restore_copy"):
                return ent.buf[:n].copy()
        if ent.state == "reading":
            self._reads.pop(("r", ent.uid))
            self.counters["prefetch_hits"] += 1
            sbuf = self._staging.peek(ent.slot)
            with self.timers.stage("restore_copy"):
                return sbuf[:n].copy()
        # cold NVMe read (prefetch missed this one)
        self.counters["prefetch_misses"] += 1
        from deepspeed_tpu.io.aio import aligned_empty

        work = aligned_empty(n)
        with self.timers.stage("restore_read"):
            try:
                self._handle().sync_pread(work, ent.path)
            except OSError as e:
                self._nvme_failure(e, f"cold read of spilled uid "
                                      f"{ent.uid}")
                # the trip may already have folded this entry; if not,
                # fold it here — either way the session re-prefills
                if ent.uid in self._entries:
                    self._drop(ent)
                self._lost.discard(ent.uid)
                err = KVRestoreError(
                    ent.uid, -1,
                    f"kv tiering: NVMe read for spilled uid {ent.uid} "
                    f"failed ({e}) — payload unreachable, the session "
                    "must re-prefill")
                from deepspeed_tpu.telemetry import flight

                flight.dump_on_fault("kv_restore_error", err,
                                     extra={"uid": str(ent.uid),
                                            "page": -1})
                raise err from e
        return work

    def _verify_pages(self, ent: _Entry, work: np.ndarray,
                      digests: List[tuple]) -> None:
        w2 = work.reshape(ent.n_pages, self.page_stride)
        src = ent.buf if ent.buf is not None else (
            self._staging.peek(ent.slot) if ent.slot is not None else None)
        for i in range(ent.n_pages):
            page = w2[i]
            action = faults.hook("kv.read_page", uid=ent.uid, page=i,
                                 path=ent.path)
            if action and action[0] == "bitflip":
                faults.apply_bitflip(page, action[1])
            ok = sdc_digest(page, self.algo) == tuple(digests[i])
            tries = 0
            while not ok and tries < self.max_reread:
                tries += 1
                self.counters["rereads"] += 1
                # re-read from the authoritative copy: the spill file
                # (NVMe) or the resident tier buffer (host) — then give
                # the fault hook its next firing (a count=1 transient
                # flip stays healed; a persistent fault flips again)
                if src is not None:
                    page[:] = src.reshape(ent.n_pages,
                                          self.page_stride)[i]
                else:
                    self._handle().sync_pread(page, ent.path,
                                              offset=i * self.page_stride)
                action = faults.hook("kv.read_page", uid=ent.uid,
                                     page=i, path=ent.path)
                if action and action[0] == "bitflip":
                    faults.apply_bitflip(page, action[1])
                ok = sdc_digest(page, self.algo) == tuple(digests[i])
                if ok:
                    self.counters["reread_recovered"] += 1
            if not ok:
                self._quarantine(ent, i)
                err = KVRestoreError(
                    ent.uid, i,
                    f"kv tiering: page {i} of spilled uid {ent.uid} "
                    f"failed {self.algo} verification after "
                    f"{tries} re-read(s) — payload quarantined, the "
                    "session must re-prefill")
                from deepspeed_tpu.telemetry import flight

                flight.dump_on_fault("kv_restore_error", err,
                                     extra={"uid": str(ent.uid),
                                            "page": int(i)})
                raise err
            self.counters["pages_verified"] += 1

    def _quarantine(self, ent: _Entry, page: int) -> None:
        """Never decode on garbage, never delete the evidence."""
        self.counters["quarantined"] += 1
        where = ent.path if ent.path else "host tier"
        if ent.path and os.path.exists(ent.path):
            dst = ent.path + ".quarantine"
            n = 0
            while os.path.exists(dst):
                n += 1
                dst = f"{ent.path}.quarantine.{n}"
            try:
                os.rename(ent.path, dst)
                where = dst
            except OSError:
                pass
        logger.error(
            f"kv tiering: QUARANTINED corrupt spilled page {page} of "
            f"uid {ent.uid} ({where}); session will re-prefill")
        self._drop(ent)
        if ent.path is not None:
            # a corrupt NVMe-backed payload counts toward the degraded-
            # mode streak (a dying device shows up as repeated
            # quarantines as readily as hard EIO)
            self._nvme_failure(None, f"quarantine of uid {ent.uid} "
                                     f"page {page}")

    def _drop(self, ent: _Entry) -> None:
        if self._entries.pop(ent.uid, None) is None:
            return
        if ent.state in ("host", "writing"):
            if ent.state == "writing":
                self._writes.pop(("w", ent.uid))
                self._nvme_used -= ent.n_pages
            else:
                self._host_used -= ent.n_pages
        elif ent.state in ("nvme", "reading"):
            if ent.state == "reading":
                self._reads.pop(("r", ent.uid))
            self._nvme_used -= ent.n_pages
        if ent.slot is not None:
            self._staging.release(ent.slot)
            ent.slot = None
        if ent.path and os.path.exists(ent.path):
            try:
                os.remove(ent.path)
            except OSError:
                pass
        self._digests.discard(ent.uid)
        ent.buf = None

    def drop(self, uid: Key) -> None:
        """Discard a spilled payload (session finished or re-prefills)."""
        self._lost.discard(uid)
        ent = self._entries.get(uid)
        if ent is not None:
            self._drop(ent)

    # -- degraded mode (NVMe tier offline) --------------------------------

    def _check_lost(self, uid: Key) -> None:
        """A payload folded at a degraded-mode trip is gone: raise the
        same typed error as a quarantine so the caller's existing
        re-prefill path takes over."""
        if uid in self._lost:
            self._lost.discard(uid)
            raise KVRestoreError(
                uid, -1,
                f"kv tiering: spilled uid {uid} was folded when the "
                "NVMe tier went offline (degraded mode) — the session "
                "must re-prefill")

    def _nvme_failure(self, exc: Optional[BaseException],
                      why: str) -> None:
        """Record one hard NVMe failure; trip the tier offline at
        ``nvme_fail_threshold`` failures since the last clean probe.
        Interleaved successful IO does NOT reset the streak — on a
        dying device reads of old data often keep succeeding while new
        writes fail, and only a full :meth:`probe_nvme` round-trip
        vouches for health."""
        self.counters["nvme_failures"] += 1
        self._nvme_streak += 1
        logger.error(
            f"kv tiering: NVMe failure {self._nvme_streak}/"
            f"{self.nvme_fail_threshold} ({why}): {exc}")
        if (not self.nvme_offline
                and self._nvme_streak >= self.nvme_fail_threshold):
            self._trip_nvme(why, exc)

    def _trip_nvme(self, why: str,
                   exc: Optional[BaseException]) -> None:
        """Take the NVMe tier offline: fold every parked NVMe-backed
        payload (each session re-prefills loudly via
        :class:`KVRestoreError`), stop demoting, and mark the trip in
        counters/metrics/trace/flight.  Host-tier payloads are
        untouched."""
        self.nvme_offline = True
        self._probe_backoff = 0
        folded: List[Key] = []
        for ent in list(self._entries.values()):
            if ent.state == "host":
                continue
            folded.append(ent.uid)
            if ent.state == "writing":
                # the in-flight write op targets a dead device: abandon
                # it un-joined (joining could wedge or re-raise EIO)
                self._writes.discard(("w", ent.uid))
            elif ent.state == "reading":
                self._reads.discard(("r", ent.uid))
                # deliberately LEAK the staging slot: the abandoned aio
                # read may still scribble into it, so reissuing the
                # buffer to a future read would race
                ent.slot = None
            self._nvme_used -= ent.n_pages
            self._entries.pop(ent.uid, None)
            self._digests.discard(ent.uid)
            ent.buf = None
            self._lost.add(ent.uid)
        self.counters["tier_degraded"] += 1
        self.counters["degraded_folds"] += len(folded)
        logger.error(
            f"kv tiering: NVMe tier OFFLINE after {self._nvme_streak} "
            f"consecutive failures ({why}); folded {len(folded)} parked "
            "payload(s) to re-prefill, demotions fall back host-only")
        from deepspeed_tpu.telemetry import flight
        from deepspeed_tpu.telemetry.metrics import metrics as _metrics

        if _metrics.enabled:
            _metrics.counter(
                "dstpu_tier_degraded_total",
                "KV spill tiers tripped offline (degraded mode)",
                labels=("tier",)).labels(tier="nvme").inc()
        if trace.enabled:
            trace.event("tier_degraded", cat="resilience", tier="nvme",
                        streak=int(self._nvme_streak),
                        folded=len(folded))
        flight.dump_on_fault(
            "tier_degraded",
            exc if exc is not None else RuntimeError(why),
            extra={"tier": "nvme", "streak": int(self._nvme_streak),
                   "folded_uids": [str(u) for u in folded],
                   "why": why})

    def probe_nvme(self) -> bool:
        """Degraded-mode recovery probe: write, read back, and verify
        one page-stride block through the same ``kv.write`` fault site
        as the spill path.  A clean round-trip re-arms the NVMe tier;
        a failed probe leaves it offline (and does NOT feed the
        failure streak — the tier is already down)."""
        if self.nvme_budget <= 0 or self.spill_dir is None:
            return False
        self.counters["probes"] += 1
        path = os.path.join(self.spill_dir, "probe.bin")
        from deepspeed_tpu.io.aio import aligned_empty

        buf = aligned_empty(self.page_stride)
        buf[:] = (np.arange(self.page_stride) % 251).astype(np.uint8)
        back = aligned_empty(self.page_stride)
        try:
            d = faults.hook("kv.write", uid="probe", path=path)
            if d is not None and d[0] in ("hang", "slow"):
                time.sleep(float(d[1]))
            self._handle().sync_pwrite(buf, path)
            self._handle().sync_pread(back, path)
            if not np.array_equal(buf, back):
                raise OSError("probe read-back mismatch")
        except OSError as e:
            self.counters["probe_failures"] += 1
            logger.warning(f"kv tiering: NVMe revival probe failed: {e}")
            return False
        finally:
            try:
                os.remove(path)
            except OSError:
                pass
        self._nvme_streak = 0       # a clean round-trip vouches for it
        if self.nvme_offline:
            self.nvme_offline = False
            self.counters["tier_rearmed"] += 1
            logger.info("kv tiering: NVMe tier re-armed after clean "
                        "revival probe")
            if trace.enabled:
                trace.event("tier_rearmed", cat="resilience",
                            tier="nvme")
        return True

    # -- cross-replica handoff (elastic shrink) --------------------------

    def export_spilled(self, uid: Key) -> Dict[str, Any]:
        """Hand off ``uid``'s payload in SPILL FORMAT — the packed page
        bytes plus the spill-time digests — without unpacking.  The
        receiving store installs the donor digests alongside the bytes,
        so its ``restore()`` verifies the pages against what the DONOR
        computed at spill time: the handoff is integrity-checked
        end-to-end, not re-trusted at the import boundary.  Drops the
        entry (ownership moves with the bytes)."""
        self._check_lost(uid)
        ent = self._entries.get(uid)
        assert ent is not None, f"uid {uid} not spilled"
        n = ent.n_pages
        work = self._fetch(ent)
        digests = self._digests.pop(uid) if self.verify else None
        self._drop(ent)
        self.counters["exports"] += 1
        return {"n_pages": n,
                "page_stride": int(self.page_stride),
                "algo": self.algo,
                "payload": bytes(work[:n * self.page_stride]),
                "digests": ([tuple(d) for d in digests]
                            if digests is not None else None)}

    def import_spilled(self, uid: Key, blob: Dict[str, Any]) -> None:
        """Receiving half of the handoff: install an exported payload
        under ``uid`` as a host-tier entry (demoting/overflowing to
        NVMe exactly like a local spill).  Raises ``ValueError`` on a
        layout mismatch and ``RuntimeError`` when no tier has room —
        the caller falls back to a re-prefill continuation."""
        assert uid not in self._entries, f"uid {uid} already spilled"
        if int(blob["page_stride"]) != self.page_stride:
            raise ValueError(
                f"kv tiering: imported payload page_stride "
                f"{blob['page_stride']} != local {self.page_stride} — "
                "handoff requires homogeneous replica cache layouts")
        n = int(blob["n_pages"])
        if not self.can_spill(n):
            self.counters["spill_fallbacks"] += 1
            raise RuntimeError(
                f"kv tiers full: cannot import {n} pages "
                f"(free {self.free_pages()})")
        from deepspeed_tpu.io.aio import aligned_empty

        raw = np.frombuffer(blob["payload"], np.uint8)
        assert raw.size == n * self.page_stride, (raw.size, n)
        buf = aligned_empty(n * self.page_stride)
        buf[:] = raw
        ent = _Entry(uid, n)
        self._seq += 1
        ent.seq = self._seq
        ent.buf = buf
        host_free = self.host_budget - self._host_used
        try:
            if n <= self.host_budget:
                if n > host_free:
                    self._demote(n - host_free)
                self._entries[uid] = ent
                self._host_used += n
            else:
                self._entries[uid] = ent
                self._nvme_spill(ent)
        except RuntimeError:
            self._entries.pop(uid, None)
            self.counters["spill_fallbacks"] += 1
            raise
        if self.verify:
            donor = blob.get("digests")
            if donor is not None and str(blob.get("algo")) == self.algo:
                # the donor's spill-time digests ARE the reference
                self._digests.submit(
                    uid, lambda d=donor: [tuple(x) for x in d])
            else:
                # algo mismatch (or unverified donor): digest what we
                # received — integrity from here on, not end-to-end
                self._digests.submit(
                    uid, lambda: [sdc_digest(b, self.algo)
                                  for b in buf.reshape(n,
                                                       self.page_stride)])
        self.counters["imports"] += 1
        self.counters["pages_spilled"] += n
        self.counters["bytes_spilled"] += buf.nbytes

    # -- accounting / telemetry ------------------------------------------

    def audit(self) -> Dict[str, int]:
        """Tier-side conservation check (the spill-tier analogue of
        ``PageAllocator.audit``): recomputes per-tier usage from the
        entry table and asserts it matches the running counters."""
        host = sum(e.n_pages for e in self._entries.values()
                   if e.state == "host")
        nvme = sum(e.n_pages for e in self._entries.values()
                   if e.state in ("writing", "nvme", "reading"))
        assert host == self._host_used, (host, self._host_used)
        assert nvme == self._nvme_used, (nvme, self._nvme_used)
        assert host <= self.host_budget and nvme <= self.nvme_budget
        return {"sessions": len(self._entries), "host_pages_used": host,
                "nvme_pages_used": nvme, "host_budget": self.host_budget,
                "nvme_budget": self.nvme_budget}

    def stats(self) -> Dict[str, Any]:
        """Flat numeric stats (stage seconds + counters) — one level so
        ``MonitorMaster.write_serving_health`` flattens it to
        ``Serving/kv_tiering/<name>`` series."""
        out = dict(self.timers.snapshot())
        out.update(self.counters)
        out["resident_spilled_sessions"] = len(self._entries)
        out["host_pages_used"] = self._host_used
        out["nvme_pages_used"] = self._nvme_used
        out["nvme_offline"] = int(self.nvme_offline)
        from deepspeed_tpu.telemetry.metrics import metrics as _metrics
        _metrics.sync_counters(
            "dstpu_kv_tiering_", self.counters,
            help="Tiered paged-KV store counters (cumulative)")
        if _metrics.enabled:
            g = _metrics.gauge("dstpu_kv_tiering_pages_used",
                               "Spilled pages resident per tier",
                               labels=("tier",))
            g.labels(tier="host").set(self._host_used)
            g.labels(tier="nvme").set(self._nvme_used)
        return out

    def close(self) -> None:
        for uid in list(self._entries):
            self.drop(uid)
        try:
            self._writes.drain()
            self._reads.drain()
        except Exception:
            pass
        self._digests.close()
        if self.spill_dir:
            shutil.rmtree(self.spill_dir, ignore_errors=True)
