"""Tiered paged-KV store tests (the HBM -> host RAM -> NVMe tentpole).

The load-bearing contracts:

- **Restore is bit-identical to never having spilled**: a spilled
  sequence's pages come back exactly (greedy AND seeded sampling,
  pipeline on/off) — restore is a page upload, not a
  re-prefill, and tiering-on greedy output equals tiering-off output
  while ``evictions`` drops to zero.
- **Tiering off is byte-for-byte today's engine**: ``tiering is None``,
  destructive eviction, the old error messages.
- **Conservation**: ``PageAllocator.audit()`` and
  ``TieredKVStore.audit()`` both hold at every step of a pressured run
  (no page leaked between HBM and the spill tiers).
- **Verified restores**: every restored page passes its spill-time
  digest; a transient ``kv.read_page`` bitflip heals via re-read, a
  persistent one quarantines the payload and the session re-prefills
  loudly — output still exact.
- **Zero new steady-state compilations** across a full
  spill -> restore -> decode cycle (the fixed-shape gather/scatter
  programs compile once at warmup).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_tiering import KVRestoreError, TieredKVStore
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.resilience.sdc import DigestPool, digest as sdc_digest

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


def make(params, tiering, pipeline=True, **kw):
    # pool sized so four 40-token sequences cannot all stay resident:
    # growth stalls force the spill-vs-evict decision every run
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", 16)
    kw.setdefault("num_pages", 9)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("kv_reserve", "on_demand")
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=pipeline, kv_tiering=tiering,
                                   rng=jax.random.PRNGKey(11), **kw)


def _prompts(sizes, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


SIZES = [12, 20, 9, 16]


def _serve(params, tiering, pipeline=True, sizes=SIZES, eng_kw=None,
           **req_kw):
    eng = make(params, tiering, pipeline=pipeline, **(eng_kw or {}))
    req_kw.setdefault("max_new_tokens", 40)
    for p in _prompts(sizes):
        eng.put_request(p, **req_kw)
    outs = {}
    while eng.has_work():
        eng.step()
        outs.update(eng.get_outputs())
    outs.update(eng.get_outputs())
    return outs, eng


def _assert_same_outputs(a, b):
    assert sorted(a) == sorted(b), (sorted(a), sorted(b))
    for uid in a:
        np.testing.assert_array_equal(a[uid], b[uid],
                                      err_msg=f"uid {uid}")


# -- store-level unit tests (no engine, no model) ------------------------

PAGE_SHAPES = [(8, 4, 6), (8, 4)]           # e.g. kv_pages + kv_scales
PAGE_DTYPES = [np.float32, np.float32]


def _store(tmp_path=None, **kw):
    kw.setdefault("page_shapes", PAGE_SHAPES)
    kw.setdefault("page_dtypes", PAGE_DTYPES)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("host_pages", 4)
    if tmp_path is not None:
        kw.setdefault("nvme_pages", 8)
        kw.setdefault("nvme_dir", str(tmp_path))
    return TieredKVStore(**kw)


def _pages(n, seed=0):
    r = np.random.default_rng(seed)
    return [r.random((n,) + s).astype(d)
            for s, d in zip(PAGE_SHAPES, PAGE_DTYPES)]


class TestTieredStoreUnit:

    def test_spill_restore_roundtrip_host(self):
        st = _store()
        arrs = _pages(3, seed=1)
        st.spill(7, arrs, 3)
        assert st.holds(7)
        back = st.restore(7)
        for a, b in zip(arrs, back):
            np.testing.assert_array_equal(a, b)
        assert not st.holds(7)
        s = st.stats()
        assert s["pages_verified"] == s["pages_restored"] == 3
        assert st.audit()["sessions"] == 0
        st.close()

    def test_demotion_prefetch_and_nvme_roundtrip(self, tmp_path):
        st = _store(tmp_path, host_pages=3)
        a, b = _pages(3, seed=2), _pages(2, seed=3)
        st.spill(1, a, 3)
        st.spill(2, b, 2)                    # demotes uid 1 to NVMe
        assert st.counters["demotions"] == 1
        assert st.counters["nvme_spills"] == 1
        st._writes.drain()                   # write-back lands on disk
        assert st._entries[1].state == "nvme"
        assert st.prefetch([1]) == 1         # async NVMe -> staging
        back = st.restore(1)
        for x, y in zip(a, back):
            np.testing.assert_array_equal(x, y)
        assert st.counters["prefetch_hits"] == 1
        back2 = st.restore(2)
        for x, y in zip(b, back2):
            np.testing.assert_array_equal(x, y)
        assert st.audit()["sessions"] == 0
        st.close()

    def test_restore_while_write_in_flight(self, tmp_path):
        """Restoring before the NVMe write-back joins must read the
        authoritative in-memory bytes, not the half-written file."""
        st = _store(tmp_path, host_pages=2)
        arrs = _pages(4, seed=4)             # 4 > host_pages: straight NVMe
        st.spill(9, arrs, 4)
        assert st._entries[9].state == "writing"
        back = st.restore(9)
        for x, y in zip(arrs, back):
            np.testing.assert_array_equal(x, y)
        st.close()

    def test_capacity_rejection_counts_fallback(self):
        st = _store(host_pages=2)
        st.spill(1, _pages(2, seed=5), 2)
        with pytest.raises(RuntimeError, match="kv tiers full"):
            st.spill(2, _pages(2, seed=6), 2)
        assert st.counters["spill_fallbacks"] == 1
        assert not st.can_spill(1)
        st.close()

    def test_transient_bitflip_heals_via_reread(self):
        st = _store()
        arrs = _pages(2, seed=7)
        st.spill(3, arrs, 2)
        with faults.FaultInjector(seed=5) as inj:
            inj.bitflip("kv.read_page", bits=1, count=1)
            back = st.restore(3)
        for x, y in zip(arrs, back):
            np.testing.assert_array_equal(x, y)
        assert st.counters["reread_recovered"] == 1
        assert st.counters["quarantined"] == 0
        st.close()

    def test_persistent_corruption_quarantines(self, tmp_path):
        st = _store(tmp_path, host_pages=1, max_reread=2)
        arrs = _pages(2, seed=8)
        st.spill(4, arrs, 2)                 # oversized for host: NVMe
        st._writes.drain()
        path = st._entries[4].path
        with faults.FaultInjector(seed=6) as inj:
            inj.bitflip("kv.read_page", bits=1, count=10)
            with pytest.raises(KVRestoreError):
                st.restore(4)
        assert st.counters["quarantined"] == 1
        assert not st.holds(4)               # dropped: session re-prefills
        assert os.path.exists(path + ".quarantine")
        st.close()

    def test_digest_pool_inline_deferred_parity(self):
        """Satellite: the SDC digest side pool on the substrate —
        deferred digests bit-match inline ones."""
        buf = np.random.default_rng(0).integers(
            0, 255, size=(1 << 16,), dtype=np.uint8)
        pool = DigestPool(defer_min=0)       # everything defers
        assert pool.note("k", buf) is None
        assert pool.pop("k") == sdc_digest(buf, "sum64")
        inline = DigestPool(defer_min=1 << 30)
        assert inline.note("k", buf) == sdc_digest(buf, "sum64")
        assert not inline.spun, "small digests must not spin the pool"
        pool.close()
        inline.close()


# -- engine-level tests --------------------------------------------------

class TestEngineTiering:

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_greedy_parity_spill_replaces_evict(self, params, pipeline):
        off, eoff = _serve(params, None, pipeline=pipeline)
        on, eon = _serve(params, {"host_pages": 64}, pipeline=pipeline)
        assert eoff.evictions > 0, "pool sized to force pressure"
        assert eon.spills > 0 and eon.restores > 0
        assert eon.evictions == 0, "tiers absorb what eviction destroyed"
        _assert_same_outputs(off, on)
        st = eon.serving_stages()["kv_tiering"]
        assert st["pages_verified"] == st["pages_restored"] > 0
        eon.close()

    @pytest.mark.slow
    def test_seeded_sampling_deterministic_across_spill(self, params):
        kw = dict(do_sample=True, temperature=0.9, top_k=12,
                  max_new_tokens=30)
        a, ea = _serve(params, {"host_pages": 64}, **kw)
        b, eb = _serve(params, {"host_pages": 64}, **kw)
        assert ea.spills > 0
        _assert_same_outputs(a, b)
        ea.close()
        eb.close()

    def test_nvme_tier_parity(self, params, tmp_path):
        off, _ = _serve(params, None, sizes=[12, 20, 9, 16, 14, 18])
        tier = {"host_pages": 2, "nvme_pages": 16,
                "nvme_dir": str(tmp_path)}
        on, eon = _serve(params, tier, sizes=[12, 20, 9, 16, 14, 18])
        st = eon.tiering.stats()
        assert st["nvme_spills"] > 0, "host tier sized to overflow"
        _assert_same_outputs(off, on)
        eon.close()

    def test_tiering_off_control_unchanged(self, params):
        eng = make(params, None, num_pages=4)
        assert eng.tiering is None
        with pytest.raises(ValueError, match="raise num_pages$"):
            eng.put_request(np.ones(40, np.int32), max_new_tokens=60)

    def test_conservation_audits_under_pressure(self, params, tmp_path):
        eng = make(params, {"host_pages": 2, "nvme_pages": 16,
                            "nvme_dir": str(tmp_path)})
        for p in _prompts([12, 20, 9, 16, 14, 18]):
            eng.put_request(p, max_new_tokens=40)
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
            eng.allocator.audit()
            eng.tiering.audit()
            # refcount conservation: every page's refcount == number of
            # page-table rows (+ external holders) referencing it
            eng.audit_kv_sharing()
        assert eng.spills > 0
        a = eng.tiering.audit()
        assert a["sessions"] == 0, "drained run leaves no spilled payload"
        fin = eng.audit_kv_sharing()
        assert fin["referenced"] == 0, "drained run leaves no live refs"
        eng.close()

    def test_persistent_corruption_reprefills_exactly(self, params):
        off, _ = _serve(params, None)
        with faults.FaultInjector(seed=6) as inj:
            inj.bitflip("kv.read_page", bits=1, count=3)
            on, eon = _serve(params, {"host_pages": 64})
        st = eon.tiering.stats()
        assert st["quarantined"] >= 1, "fault must have fired"
        _assert_same_outputs(off, on)       # re-prefill is exact (greedy)
        eon.close()

    def test_zero_new_compiles_across_spill_restore(self, params):
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, {"host_pages": 64})
        prompts = _prompts(SIZES)
        eng.generate_all(prompts, max_new_tokens=40)
        assert eng.spills > 0, "warmup must exercise the spill path"
        with count_compiles() as compiles:
            eng.generate_all(prompts, max_new_tokens=40)
        assert eng.spills > 2, "steady-state run must spill too"
        assert compiles.count == 0, (
            f"{compiles.count} recompilations across the spill/restore "
            "cycle — the gather/scatter programs must be fixed-shape")
        eng.close()


class TestTierAwareSubmitValidation:
    """Satellite bugfix: put_request capacity math accounts for the
    spill tiers, and rejections name the tier budget that ran out."""

    def test_accepts_beyond_hbm_within_tiers(self, params):
        eng = make(params, {"host_pages": 64}, num_pages=4)
        # 100 tokens = 7 pages > 3 usable HBM pages, but within the
        # 3 + 64 combined capacity: admissible (max_new_tokens is a
        # budget, not a promise — tiering makes the overflow
        # non-destructive for every other session)
        uid = eng.put_request(np.ones(40, np.int32), max_new_tokens=60)
        assert uid >= 0
        eng.close()

    def test_rejection_names_tier_budgets(self, params):
        eng = make(params, {"host_pages": 2}, num_pages=4)
        with pytest.raises(ValueError, match=r"host \(2\) \+ NVMe \(0\)"):
            eng.put_request(np.ones(40, np.int32), max_new_tokens=60)
        eng.close()

    def test_admit_defense_names_hbm_tier(self, params):
        """A spilled-tier-admitted request whose WORKING SET cannot fit
        HBM fails loudly at admission, naming the HBM tier."""
        eng = make(params, {"host_pages": 64}, num_pages=4)
        eng.put_request(np.ones(60, np.int32), max_new_tokens=40)
        with pytest.raises(ValueError, match="HBM tier"):
            eng.step()
        assert not eng.waiting
        eng.close()

    def test_config_rejects_unknown_checksum(self):
        """A typo'd digest algo must die at config time, not at the
        first spill mid-serving."""
        from deepspeed_tpu.inference.config import KVTieringConfig

        with pytest.raises(ValueError, match="checksum"):
            KVTieringConfig(enabled=True, checksum="md5")
