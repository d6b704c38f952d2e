"""Degraded-mode tiering: a failing NVMe device must not take serving
down with it.

The contracts under test (``inference/kv_tiering.py`` degraded mode):

- ``nvme_fail_threshold`` consecutive hard NVMe failures (injected
  ``io_error`` at the ``kv.write`` fault site, or repeated quarantines
  of NVMe-backed payloads) trip the tier OFFLINE;
- at the trip, parked NVMe-backed payloads FOLD: their next restore
  raises :class:`KVRestoreError` (the engine's existing re-prefill
  path), while host-tier payloads survive untouched;
- while offline, ``can_spill``/demotion fall back host-only and the
  accounting audits stay clean;
- a clean :meth:`probe_nvme` round-trip (attempted automatically every
  ``probe_every`` blocked spills) re-arms the tier;
- at the engine level a tier trip mid-serve degrades to destructive
  eviction + re-prefill with BIT-EXACT greedy outputs, and the trip is
  observable (counters, ``tier_degraded`` flight record,
  ``cat="resilience"`` trace events that pass the validator).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_tiering import (KVRestoreError,
                                                TieredKVStore)
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.telemetry import (flight, read_flight_record,
                                     tracer as tracer_mod)

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                "..", "..", ".."))
from scripts.trace_summarize import validate_events  # noqa: E402

pytestmark = pytest.mark.faults

PAGE_SHAPES = [(8, 4, 6), (8, 4)]
PAGE_DTYPES = [np.float32, np.float32]


def _store(tmp_path, **kw):
    kw.setdefault("page_shapes", PAGE_SHAPES)
    kw.setdefault("page_dtypes", PAGE_DTYPES)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("host_pages", 2)
    kw.setdefault("nvme_pages", 8)
    kw.setdefault("nvme_dir", str(tmp_path))
    kw.setdefault("nvme_fail_threshold", 3)
    return TieredKVStore(**kw)


def _pages(n, seed=0):
    r = np.random.default_rng(seed)
    return [r.random((n,) + s).astype(d)
            for s, d in zip(PAGE_SHAPES, PAGE_DTYPES)]


class TestStoreDegradedMode:

    def test_consecutive_write_failures_trip_tier_offline(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path / "fl"))
        st = _store(tmp_path)
        a, b = _pages(2, seed=1), _pages(2, seed=2)
        st.spill(1, a, 2)                     # host tier
        with faults.FaultInjector(seed=3) as inj:
            # first NVMe write succeeds, everything after hard-EIOs
            inj.io_error("kv.write", after=1, count=100)
            st.spill(2, b, 2)                 # demotes uid 1 -> NVMe ok
            st._writes.drain()
            assert st._entries[1].state == "nvme"
            # three spills each blocked on a failing demote: the streak
            # reaches nvme_fail_threshold and the tier trips
            for uid in (3, 4, 5):
                with pytest.raises(RuntimeError):
                    st.spill(uid, _pages(2, seed=uid), 2)
            assert st.nvme_offline
            assert st.counters["tier_degraded"] == 1
            assert st.counters["nvme_failures"] == 3
        # the parked NVMe payload folded: restore raises the same typed
        # error as a quarantine, so the session re-prefills
        assert st.counters["degraded_folds"] == 1
        with pytest.raises(KVRestoreError, match="degraded mode"):
            st.restore(1)
        # the host payload survived, bit-exact
        back = st.restore(2)
        for x, y in zip(b, back):
            np.testing.assert_array_equal(x, y)
        assert st.audit()["sessions"] == 0
        # the trip dumped a parseable flight record naming the tier
        path = flight.last_dump_path()
        assert path is not None
        header, _events = read_flight_record(path)
        assert header["reason"] == "tier_degraded"
        assert header["extra"]["tier"] == "nvme"
        assert header["extra"]["folded_uids"] == ["1"]
        st.close()

    def test_offline_capacity_is_host_only(self, tmp_path):
        st = _store(tmp_path)
        with faults.FaultInjector(seed=4) as inj:
            inj.io_error("kv.write", count=100)
            for uid in (1, 2, 3):
                with pytest.raises(RuntimeError):
                    st.spill(uid, _pages(4, seed=uid), 4)  # NVMe-sized
            assert st.nvme_offline
            # host budget (2) is all that's left: a 2-page spill fits,
            # a 4-page one cannot land anywhere
            assert st.can_spill(2)
            assert not st.can_spill(4)
            assert st.free_pages() == 2
            st.spill(9, _pages(2, seed=9), 2)
            assert st._entries[9].state == "host"
        st.close()

    def test_probe_rearms_after_fault_clears(self, tmp_path):
        st = _store(tmp_path, probe_every=2)
        with faults.FaultInjector(seed=5) as inj:
            inj.io_error("kv.write", count=100)
            for uid in (1, 2, 3):
                with pytest.raises(RuntimeError):
                    st.spill(uid, _pages(4, seed=uid), 4)
            assert st.nvme_offline
            # the fault still fires at the probe's kv.write site: the
            # tier stays down
            assert not st.probe_nvme()
            assert st.counters["probe_failures"] == 1
            assert st.nvme_offline
        # fault cleared: blocked spills auto-probe every probe_every
        # attempts and the clean round-trip re-arms the tier
        assert not st.can_spill(4)            # backoff 1/2
        assert st.can_spill(4)                # probe fires, re-arms
        assert not st.nvme_offline
        assert st.counters["tier_rearmed"] == 1
        st.spill(7, _pages(4, seed=7), 4)     # straight to NVMe again
        st._writes.drain()
        assert st._entries[7].state == "nvme"
        back = st.restore(7)
        for x, y in zip(_pages(4, seed=7), back):
            np.testing.assert_array_equal(x, y)
        assert st.audit()["sessions"] == 0
        st.close()

    def test_quarantine_streak_trips_tier(self, tmp_path):
        st = _store(tmp_path, host_pages=1, nvme_fail_threshold=2,
                    max_reread=1)
        with faults.FaultInjector(seed=6) as inj:
            inj.bitflip("kv.read_page", bits=1, count=1000)
            for uid in (1, 2):
                st.spill(uid, _pages(2, seed=uid), 2)  # NVMe-sized
                st._writes.drain()
                with pytest.raises(KVRestoreError):
                    st.restore(uid)
        assert st.counters["quarantined"] == 2
        assert st.nvme_offline, (
            "repeated quarantines of NVMe-backed payloads must count "
            "toward the degraded-mode streak")
        st.close()


CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


def _serve(params, tiering, sizes):
    eng = RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=params, max_seqs=4,
        max_seq_len=128, prefill_chunk=16, page_size=16, num_pages=9,
        decode_block_size=4, kv_reserve="on_demand",
        kv_tiering=tiering, rng=jax.random.PRNGKey(11))
    r = np.random.default_rng(3)
    for s in sizes:
        eng.put_request(r.integers(1, 64, size=(s,), dtype=np.int32),
                        max_new_tokens=40)
    outs = {}
    while eng.has_work():
        eng.step()
        outs.update(eng.get_outputs())
        eng.allocator.audit()
        if eng.tiering is not None:
            eng.tiering.audit()
        eng.audit_kv_sharing()
    outs.update(eng.get_outputs())
    return outs, eng


SIZES = [12, 20, 9, 16, 14, 18]


class TestEngineDegradedMode:

    def test_tier_trip_mid_serve_keeps_greedy_parity(self, params,
                                                     tmp_path):
        off, _eoff = _serve(params, None, SIZES)
        tr = tracer_mod.trace
        prev = (tr.enabled, tr.buffer_size, tr.clock)
        tr.clear()
        tr.configure(enabled=True)
        try:
            with faults.FaultInjector(seed=7) as inj:
                # let one write-back land, then the device dies hard
                inj.io_error("kv.write", after=1, count=10_000)
                on, eon = _serve(
                    params,
                    {"host_pages": 2, "nvme_pages": 16,
                     "nvme_dir": str(tmp_path),
                     "nvme_fail_threshold": 2},
                    SIZES)
            st = eon.tiering.stats()
            assert st["tier_degraded"] == 1, st
            assert st["nvme_offline"] == 1
            # serving completed, bit-exact, audits clean at every step
            assert sorted(off) == sorted(on)
            for uid in off:
                np.testing.assert_array_equal(off[uid], on[uid])
            fin = eon.audit_kv_sharing()
            assert fin["referenced"] == 0
            assert eon.tiering.audit()["sessions"] == 0
            # the trip is a cat="resilience" instant that passes the
            # trace validator's schema gate
            import json

            tpath = str(tmp_path / "degraded_trace.json")
            tr.export(tpath)
            with open(tpath) as f:
                evs = json.load(f)["traceEvents"]
            res = [e for e in evs if e.get("cat") == "resilience"]
            assert any(e["name"] == "tier_degraded" for e in res), res
            assert validate_events(evs) == []
            eon.close()
        finally:
            tr.configure(enabled=prev[0], buffer_size=prev[1],
                         clock=prev[2])
            tr.clear()
