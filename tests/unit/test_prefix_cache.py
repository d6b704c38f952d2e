"""Cross-request prefix cache tests (refcounted COW KV sharing).

The load-bearing contracts:

- **Sharing never changes output**: greedy outputs with the prefix
  cache on are bit-identical to cache-off (N sessions sharing a system
  prompt, both pipeline modes); seeded sampling is reproducible too
  (position-keyed RNG streams make the draw for token n of request u
  independent of co-batching and cache hits).
- **Copy-on-write**: a fully-matched admission COWs its last page
  before the one-token re-prefill; mid-stream divergence after a
  shared prefix never writes into a shared page.
- **Verification beats hashing**: a hash-colliding chunk with
  different token ids must never share a page — token ids are compared
  before attach, so a collision degrades to a miss.
- **Refcount conservation**: ``PageAllocator.audit`` (with the
  engine's external-holders map via ``audit_kv_sharing``) holds at
  every step under COW + spill pressure.
- **Composition**: tiering (shared pages are spill-exempt via
  spill-holds; demoted index pages revive once for all waiters)
  composes without output changes; steady state adds zero new
  compilations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.inference.prefix_cache as pfx_mod
from deepspeed_tpu.inference.paged import PageAllocator
from deepspeed_tpu.inference.prefix_cache import (ROOT_HASH,
                                                  PrefixCacheIndex,
                                                  _chunk_hash)
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.telemetry.requests import (RequestLatencyTracker,
                                              percentile)

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)

PAGE = 16


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


def make(params, prefix=True, tiering=None, pipeline=True, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("num_pages", 21)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("kv_reserve", "on_demand")
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=pipeline, kv_tiering=tiering,
                                   prefix_cache=prefix,
                                   rng=jax.random.PRNGKey(11), **kw)


def _shared_prompts(n, sys_pages=2, suffix=6, seed=3, repeat_of=None):
    """n prompts sharing a ``sys_pages``-page system prompt with
    distinct user suffixes; ``repeat_of`` maps indices to earlier
    indices to repeat verbatim (full-match/COW admissions)."""
    r = np.random.default_rng(seed)
    sys = r.integers(1, 64, size=(sys_pages * PAGE,), dtype=np.int32)
    out = []
    for i in range(n):
        if repeat_of and i in repeat_of:
            out.append(out[repeat_of[i]].copy())
        else:
            sfx = r.integers(1, 64, size=(suffix,), dtype=np.int32)
            out.append(np.concatenate([sys, sfx]))
    return out


def _serve(eng, prompts, audit=False, **req_kw):
    req_kw.setdefault("max_new_tokens", 20)
    for p in prompts:
        eng.put_request(p, **req_kw)
    outs = {}
    saw_spill_hold = False
    while eng.has_work():
        eng.step()
        outs.update(eng.get_outputs())
        if audit:
            eng.audit_kv_sharing()
            saw_spill_hold |= any(
                r.spilled is not None and r.spilled.get("shared_pages")
                for r in eng.waiting)
    outs.update(eng.get_outputs())
    return (outs, saw_spill_hold) if audit else outs


def _assert_same_outputs(a, b):
    assert sorted(a) == sorted(b), (sorted(a), sorted(b))
    for uid in a:
        np.testing.assert_array_equal(a[uid], b[uid],
                                      err_msg=f"uid {uid}")


# -- allocator refcounts (no model) --------------------------------------


class TestRefcountedAllocator:

    def test_incref_keeps_page_out_of_circulation(self):
        al = PageAllocator(num_pages=6, page_size=PAGE)
        al.allocate(0, 2 * PAGE)
        p = al.owned_pages(0)[0]
        al.incref(p)                       # e.g. a prefix-index entry
        al.free(0)                         # slot gone, page survives
        assert al.refcount(p) == 1
        assert p not in al.grow(1, 1), "held page must not be re-granted"
        al.audit(external={p: 1})
        al.decref(p)
        assert al.refcount(p) == 0
        al.audit(external={})

    def test_attach_then_cow_diverges(self):
        al = PageAllocator(num_pages=6, page_size=PAGE)
        al.allocate(0, PAGE)
        p = al.owned_pages(0)[0]
        al.attach(1, [p])
        assert al.refcount(p) == 2
        old, new = al.cow(1, 0)
        assert old == p and new != p
        assert al.owned_pages(1) == [new]
        assert al.refcount(p) == 1 and al.refcount(new) == 1
        # sole owner: cow is a no-op
        o2, n2 = al.cow(0, 0)
        assert o2 == n2 == p
        al.audit(external={})

    def test_audit_catches_leaked_external_ref(self):
        al = PageAllocator(num_pages=6, page_size=PAGE)
        al.allocate(0, PAGE)
        p = al.owned_pages(0)[0]
        al.incref(p)
        with pytest.raises(AssertionError, match="refcount"):
            al.audit(external={})          # the extra ref is unaccounted
        al.audit(external={p: 1})


# -- index unit tests (no model) -----------------------------------------


def _index(**kw):
    al = PageAllocator(num_pages=32, page_size=4)
    kw.setdefault("max_entries", 8)
    return PrefixCacheIndex(al, 4, **kw), al


class TestPrefixIndexUnit:

    def test_match_register_roundtrip(self):
        ix, al = _index()
        toks = np.arange(1, 13, dtype=np.int32)      # 3 full pages
        assert ix.match(toks) == []
        parent = ROOT_HASH
        pages = []
        for k in range(3):
            pg = al.grow(0, 1)[0] if al.owned(0) else al.allocate(0, 4)[0]
            parent = ix.register(parent, toks[k * 4:(k + 1) * 4], pg)
            pages.append(pg)
        got = ix.match(toks)
        assert [e.page for e in got] == pages
        # a longer query matches only its full-page prefix
        assert len(ix.match(np.concatenate([toks, [9, 9]]))) == 3
        # divergence in page 2 stops the walk after page 1
        q = toks.copy()
        q[5] ^= 1
        assert len(ix.match(q)) == 1

    def test_min_match_pages_floor(self):
        ix, al = _index(min_match_pages=2)
        toks = np.arange(1, 9, dtype=np.int32)       # 2 pages
        pg = al.allocate(0, 8)
        parent = ix.register(ROOT_HASH, toks[:4], pg[0])
        assert ix.match(toks[:4]) == []              # 1 page < floor
        ix.register(parent, toks[4:], pg[1])
        assert len(ix.match(toks)) == 2

    def test_hash_collision_never_shares(self, monkeypatch):
        """Token-id verification, not hash uniqueness, is the safety
        contract: with a constant (always-colliding) hash, different
        tokens must never attach to each other's pages."""
        monkeypatch.setattr(pfx_mod, "_chunk_hash",
                            lambda parent, tokens: 42)
        ix, al = _index()
        a = np.arange(1, 5, dtype=np.int32)
        b = np.arange(5, 9, dtype=np.int32)
        pg = al.allocate(0, 8)
        ix.register(ROOT_HASH, a, pg[0])
        assert ix.match(b) == [], "colliding key with different tokens"
        assert ix.collisions >= 1
        # registering b evicts a's entry (the key now means b)
        ix.register(ROOT_HASH, b, pg[1])
        assert ix.match(a) == []
        assert [e.page for e in ix.match(b)] == [pg[1]]
        al.audit(external={pg[1]: 1})

    def test_lru_overflow_and_reclaim(self):
        ix, al = _index(max_entries=2)
        slot_pages = al.allocate(0, 12)
        parents = []
        for k, pg in enumerate(slot_pages):
            toks = np.full((4,), 10 + k, np.int32)
            parents.append(ix.register(ROOT_HASH, toks, pg))
        assert len(ix) == 2 and ix.drops == 1        # LRU evicted
        al.free(0)                                   # only index refs left
        assert ix.reclaimable() == 2
        free0 = al.free_pages
        assert ix.reclaim(1) == 1
        assert al.free_pages == free0 + 1
        al.audit(external={e.page: 1 for e in ix._entries.values()
                           if e.state == "resident"})

    def test_exclude_protects_matched_entries(self):
        ix, al = _index()
        pg = al.allocate(0, 4)[0]
        toks = np.arange(1, 5, dtype=np.int32)
        key = ix.register(ROOT_HASH, toks, pg)
        al.free(0)
        assert ix.reclaimable() == 1
        assert ix.reclaimable(exclude={key}) == 0
        assert ix.reclaim(1, exclude={key}) == 0
        assert len(ix.match(toks)) == 1


# -- engine integration --------------------------------------------------


class TestPrefixServingParity:

    @pytest.mark.parametrize("pipeline", [True, False])
    def test_greedy_shared_system_prompt_parity(self, params, pipeline):
        prompts = _shared_prompts(8)
        off = _serve(make(params, prefix=False, pipeline=pipeline),
                     prompts)
        eng = make(params, prefix=True, pipeline=pipeline)
        on = _serve(eng, prompts)
        pc = eng.serving_stages()["prefix_cache"]
        assert pc["hit_requests"] > 0, "later waves must hit"
        assert pc["hit_tokens"] > 0
        _assert_same_outputs(off, on)
        # the cache must actually cut prefill compute
        rl = eng.request_latency.summary()
        assert rl["prefill_cached_tokens"] > 0
        assert (rl["prefill_computed_tokens"] + rl["prefill_cached_tokens"]
                == sum(p.size for p in prompts))
        eng.close()

    def test_full_match_cow_and_divergence(self, params):
        # 6th request repeats the 1st verbatim: full match -> COW +
        # one-token re-prefill; the rest diverge mid-page after the
        # shared prefix
        prompts = _shared_prompts(6, suffix=PAGE, repeat_of={5: 0})
        off = _serve(make(params, prefix=False), prompts)
        eng = make(params, prefix=True)
        on = _serve(eng, prompts)
        pc = eng.serving_stages()["prefix_cache"]
        assert pc["cow_copies"] >= 1, "full match must copy-on-write"
        _assert_same_outputs(off, on)
        eng.audit_kv_sharing()
        eng.close()

    def test_seeded_sampling_parity(self, params):
        kw = dict(do_sample=True, temperature=0.9, top_k=12,
                  max_new_tokens=16)
        prompts = _shared_prompts(8)
        off = _serve(make(params, prefix=False), prompts, **kw)
        eng = make(params, prefix=True)
        on = _serve(eng, prompts, **kw)
        assert eng.serving_stages()["prefix_cache"]["hit_requests"] > 0
        _assert_same_outputs(off, on)
        eng.close()

    def test_min_match_pages_gates_short_prefixes(self, params):
        prompts = _shared_prompts(6, sys_pages=1)     # 1 shared page
        eng = make(params, prefix={"min_match_pages": 2})
        _serve(eng, prompts)
        pc = eng.serving_stages()["prefix_cache"]
        assert pc["hit_requests"] == 0, "below the match floor"
        eng.close()

    def test_engine_hash_collision_never_shares(self, params,
                                                monkeypatch):
        monkeypatch.setattr(pfx_mod, "_chunk_hash",
                            lambda parent, tokens: 7)
        prompts = _shared_prompts(6)                  # distinct suffixes
        off = _serve(make(params, prefix=False), prompts)
        eng = make(params, prefix=True)
        on = _serve(eng, prompts)
        _assert_same_outputs(off, on)
        eng.audit_kv_sharing()
        eng.close()

    def test_audit_under_cow_pressure(self, params):
        prompts = _shared_prompts(10, suffix=PAGE,
                                  repeat_of={6: 0, 9: 2})
        eng = make(params, prefix=True, num_pages=17)
        outs, _ = _serve(eng, prompts, audit=True)
        assert len(outs) == 10
        fin = eng.audit_kv_sharing()
        # only the index's resident entries survive the drain
        assert fin["referenced"] == eng.kv.pfx.stats()["resident_entries"]
        eng.close()
        assert eng.allocator.audit(external={})["referenced"] == 0


class TestPrefixComposition:

    def test_composes_with_tiering_spill_restore(self, params):
        prompts = _shared_prompts(6, suffix=10)
        off = _serve(make(params, prefix=False, num_pages=21), prompts,
                     max_new_tokens=28)
        eng = make(params, prefix=True, num_pages=9,
                   tiering={"host_pages": 64})
        on, saw_hold = _serve(eng, prompts, audit=True,
                              max_new_tokens=28)
        assert eng.spills > 0, "pool sized to force spills"
        pc = eng.serving_stages()["prefix_cache"]
        assert pc["hit_requests"] > 0
        assert saw_hold, ("a spilled sequence with a shared prefix must "
                          "hold its shared pages in HBM (spill-exempt)")
        _assert_same_outputs(off, on)
        eng.close()

    def test_demoted_prefix_revives_once_for_all_waiters(self, params):
        eng = make(params, prefix=True, tiering={"host_pages": 64})
        sys_pages = 2
        first = _shared_prompts(3, sys_pages=sys_pages, seed=5)
        _serve(eng, first)
        ix = eng.kv.pfx
        assert ix.stats()["resident_entries"] >= sys_pages
        # pressure stand-in: demote every reclaimable index page to the
        # tier store (keyed by prefix hash, not uid)
        demoted = ix.reclaim(ix.reclaimable())
        assert demoted >= sys_pages
        assert ix.stats()["spilled_entries"] >= sys_pages
        assert any(eng.tiering.holds(PrefixCacheIndex.tier_key(k))
                   for k in ix._entries)
        eng.audit_kv_sharing()
        # two new waiters of the same system prompt: the first admission
        # revives each demoted page ONCE; both hit
        second = _shared_prompts(2, sys_pages=sys_pages, seed=5)
        off = _serve(make(params, prefix=False), second)
        on = _serve(eng, second)
        st = ix.stats()
        assert st["revivals"] >= sys_pages
        assert st["hits"] >= 2
        for a, b in zip([off[k] for k in sorted(off)],
                        [on[k] for k in sorted(on)]):
            np.testing.assert_array_equal(a, b)
        eng.audit_kv_sharing()
        eng.close()

    def test_zero_new_compiles_steady_state(self, params):
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, prefix=True)
        prompts = _shared_prompts(8, suffix=PAGE, repeat_of={5: 0})
        _serve(eng, prompts)
        st = eng.serving_stages()["prefix_cache"]
        assert st["hit_requests"] > 0 and st["cow_copies"] > 0, (
            "warmup must exercise attach AND the COW program")
        with count_compiles() as compiles:
            _serve(eng, _shared_prompts(8, suffix=PAGE,
                                        repeat_of={5: 0}, seed=9))
        assert compiles.count == 0, (
            f"{compiles.count} recompilations across steady-state prefix "
            "hits/COWs — attach and COW must be fixed-shape")
        eng.close()


# -- latency-tracker regression ------------------------------------------


class TestLatencyTrackerPrefixHit:

    def test_fully_skipped_prefill_records_sane_ttft(self):
        """A prefix-hit request whose prefill is fully skipped emits in
        the same tick it was admitted — TTFT must be >= 0 (clamped at
        submit), with a zero-length prefill span, never a missing or
        negative sample."""
        t = [10.0]
        rl = RequestLatencyTracker(clock=lambda: t[0])
        rl.on_submit(1)
        rl.on_admit(1)
        # full hit: 31 of 32 prompt tokens skipped, one re-prefilled
        rl.on_prefill_done(1, 1, 31)
        t[0] = 9.5                 # coarse clock went "backwards"
        rl.on_tokens(1, 1)
        t[0] = 12.0
        rl.on_tokens(1, 3)
        rl.on_finish(1)
        s = rl.summary()
        assert s["ttft_ms_p50"] == 0.0          # clamped, not negative
        assert s["prefill_ms_p50"] == 0.0    # zero-length span
        assert s["prefill_computed_tokens"] == 1
        assert s["prefill_cached_tokens"] == 31
        assert s["tpot_ms_p50"] == pytest.approx((12.0 - 10.0) / 2 * 1e3)

    def test_hand_computed_percentiles(self):
        t = [0.0]
        rl = RequestLatencyTracker(clock=lambda: t[0])
        # four requests with TTFTs of 10, 20, 30, 40 ms
        for uid, ttft_ms in enumerate([10.0, 20.0, 30.0, 40.0]):
            t[0] = 1.0
            rl.on_submit(uid)
            rl.on_admit(uid)
            t[0] = 1.0 + ttft_ms / 1e3
            rl.on_tokens(uid, 1)
            rl.on_finish(uid)
        s = rl.summary()
        # nearest-rank: p50 of [10,20,30,40] -> ceil(2)=2nd -> 20;
        # p90 -> ceil(3.6)=4th -> 40; p99 -> 4th -> 40
        assert s["ttft_ms_p50"] == pytest.approx(20.0)
        assert s["ttft_ms_p90"] == pytest.approx(40.0)
        assert s["ttft_ms_p99"] == pytest.approx(40.0)
        assert percentile([10.0, 20.0, 30.0, 40.0], 50) == 20.0
        assert percentile([], 50) is None
