"""Elastic serving tests (the serving half of the re-slicing tentpole).

World-size change as a recoverable event, behind the router:

- **Grow**: ``ReplicaSet.grow`` builds replicas from the retained
  factory (fresh, never-reused names); ``Router.add_replica`` admits
  one to the routed set, optionally replaying the donor's prefix-cache
  chains so sticky traffic re-pinned there starts warm.
- **Shrink**: ``Router.retire_replica`` drains a replica without
  dropping work — parked sessions travel to a survivor in SPILL FORMAT
  (packed pages + the donor's spill-time digests, so the receiver's
  restore verifies end-to-end), in-flight requests finish in place,
  affinity pins re-home — then ``ReplicaSet.shrink`` releases it.
- **Bit-parity**: a grow-then-shrink serving run produces greedy
  outputs identical to a static single engine; a handed-off spilled
  session decodes on the receiver from restored (verified) pages.

Router mechanics run against scripted fakes; the integration classes
at the bottom drive real engines.
"""
import itertools
import types
from collections import OrderedDict

import numpy as np
import pytest

from deepspeed_tpu.inference.kv_tiering import KVRestoreError, TieredKVStore
from deepspeed_tpu.inference.prefix_cache import ROOT_HASH, _chunk_hash
from deepspeed_tpu.serving import Router, RouterRejection
from deepspeed_tpu.serving.replica_set import ReplicaSet


# -- spill-format handoff at the store level -----------------------------

PAGE_SHAPES = [(8, 4, 6), (8, 4)]
PAGE_DTYPES = [np.float32, np.float32]


def _store(**kw):
    kw.setdefault("page_shapes", PAGE_SHAPES)
    kw.setdefault("page_dtypes", PAGE_DTYPES)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("host_pages", 8)
    return TieredKVStore(**kw)


def _pages(n, seed=0):
    return [np.random.default_rng(seed).random((n,) + s).astype(d)
            for s, d in zip(PAGE_SHAPES, PAGE_DTYPES)]


class TestSpillFormatHandoff:

    def test_export_import_roundtrip_bit_exact(self):
        a, b = _store(), _store()
        arrs = _pages(3, seed=1)
        a.spill(5, arrs, 3)
        blob = a.export_spilled(5)
        assert not a.holds(5), "export transfers ownership out"
        assert a.counters["exports"] == 1
        b.import_spilled(7, blob)          # receiver re-keys the uid
        back = b.restore(7)
        for x, y in zip(arrs, back):
            np.testing.assert_array_equal(x, y)
        s = b.stats()
        # donor digests travelled: restore VERIFIED against them
        assert s["pages_verified"] == s["pages_restored"] == 3
        assert b.counters["imports"] == 1
        a.close()
        b.close()

    def test_corruption_in_transit_caught_by_donor_digests(self):
        a, b = _store(), _store()
        a.spill(1, _pages(3, seed=2), 3)
        blob = a.export_spilled(1)
        raw = bytearray(blob["payload"])
        raw[100] ^= 0xFF                   # one flipped bit in transit
        blob["payload"] = bytes(raw)
        b.import_spilled(9, blob)
        with pytest.raises(KVRestoreError):
            b.restore(9)
        assert b.counters["quarantined"] == 1
        assert not b.holds(9)              # session re-prefills loudly
        a.close()
        b.close()

    def test_import_rejects_layout_mismatch(self):
        a = _store()
        # leaf widths past one 4096B alignment unit: stride 8192 != 4096
        b = _store(page_shapes=[(64, 4, 6), (64, 4)])
        a.spill(1, _pages(2, seed=3), 2)
        blob = a.export_spilled(1)
        with pytest.raises(ValueError, match="page_stride"):
            b.import_spilled(1, blob)
        a.close()
        b.close()

    def test_import_rejects_when_tiers_full(self):
        a, b = _store(), _store(host_pages=1, nvme_pages=0)
        a.spill(1, _pages(3, seed=4), 3)
        blob = a.export_spilled(1)
        with pytest.raises(RuntimeError, match="kv tiers full"):
            b.import_spilled(1, blob)
        assert b.counters["spill_fallbacks"] == 1
        assert not b.holds(1)
        a.close()
        b.close()


# -- ReplicaSet grow / shrink --------------------------------------------

class _DummyEngine:
    max_seqs = 2
    page_size = 4

    def __init__(self):
        self.closed = False

    def set_replica(self, name):
        self.replica = name

    def close(self):
        self.closed = True


class TestReplicaSetElastic:

    def test_grow_uses_fresh_never_reused_names(self):
        rs = ReplicaSet(lambda i: _DummyEngine(), 2)
        try:
            (h2,) = rs.grow(1)
            assert h2.name == "r2" and len(rs) == 3
            rs.shrink("r2")
            (h3,) = rs.grow(1)                 # r2 is NOT resurrected
            assert h3.name == "r3"
            assert [h.name for h in rs] == ["r0", "r1", "r3"]
        finally:
            rs.close()

    def test_shrink_removes_and_closes(self):
        rs = ReplicaSet(lambda i: _DummyEngine(), 3)
        try:
            (dropped,) = rs.shrink("r1")
            assert not dropped.alive and dropped.engine.closed
            assert [h.name for h in rs] == ["r0", "r2"]
        finally:
            rs.close()

    def test_shrink_refuses_unknown_and_empty(self):
        rs = ReplicaSet(lambda i: _DummyEngine(), 2)
        try:
            with pytest.raises(ValueError, match="unknown replicas"):
                rs.shrink("nope")
            with pytest.raises(ValueError, match="empty replica set"):
                rs.shrink(["r0", "r1"])
            assert len(rs) == 2                # refusal changed nothing
        finally:
            rs.close()


# -- Router grow / retire against scripted fakes -------------------------

class FakeElasticReplica:
    """Handle-protocol fake with the elastic extensions: synchronous
    ops, scripted finish latency, parked-session export/import."""

    def __init__(self, idx, max_seqs=3, page_size=4, latency=1,
                 exportable=True):
        self.idx = idx
        self.name = f"f{idx}"
        self.alive = True
        self.max_seqs = max_seqs
        self.page_size = page_size
        self.in_flight = 0
        self.latency = latency
        self.exportable = exportable
        self._uid = itertools.count(1000 * idx)
        self.admitted = []            # [uid, steps_left, prompt]
        self.puts = []
        self.imported = []
        self.closed = False
        self.engine = types.SimpleNamespace()   # no prefix cache

    def validate(self, prompt, max_new):
        if np.asarray(prompt).size + int(max_new) > 64:
            raise ValueError("prompt + max_new_tokens > max_seq_len 64")

    def put_async(self, prompt, kw, accept_t, on_done=None):
        uid = next(self._uid)
        p = np.asarray(prompt, np.int32)
        self.puts.append((uid, p.tolist()))
        self.admitted.append([uid, self.latency, p])
        if on_done is not None:
            on_done(uid)

    def step_async(self, on_done):
        outs, keep = [], []
        for ent in self.admitted:
            ent[1] -= 1
            if ent[1] <= 0:
                outs.append((ent[0], np.concatenate(
                    [ent[2], np.array([7, 8, 9], np.int32)])))
            else:
                keep.append(ent)
        self.admitted = keep
        on_done((outs, {"pressure": float(len(self.admitted))}))

    def drain_async(self, on_done=None):
        outs = [(e[0], np.concatenate([e[2],
                                       np.array([7, 8, 9], np.int32)]))
                for e in self.admitted]
        self.admitted = []
        if on_done is not None:
            on_done((outs, {"pressure": 0.0}))

    def export_parked_async(self, on_done):
        sessions = []
        if self.exportable:
            sessions = [{"uid": e[0], "prompt": e[2]}
                        for e in self.admitted]
            self.admitted = []
        on_done(sessions)

    def import_parked_async(self, sessions, on_done):
        uids = []
        for s in sessions:
            uid = next(self._uid)
            self.admitted.append([uid, self.latency,
                                  np.asarray(s["prompt"], np.int32)])
            self.imported.append(uid)
            uids.append(uid)
        on_done(uids)

    def join_all(self):
        pass

    def close(self):
        self.alive = False
        self.closed = True


def _prompt(n, base=1):
    return np.arange(base, base + n, dtype=np.int32)


class TestRouterElastic:

    def test_add_replica_joins_rotation(self):
        fakes = [FakeElasticReplica(0)]
        router = Router(fakes, policy="rr", sticky=False)
        router.add_replica(FakeElasticReplica(1))
        rids = [router.submit(_prompt(3, base=10 * i), max_new_tokens=4)
                for i in range(4)]
        outs = router.drain()
        assert set(outs) == set(rids)
        s = router.stats()
        assert s["replicas_added"] == 1
        assert s["routed_f0"] == 2 and s["routed_f1"] == 2, s

    def test_add_replica_rejects_duplicate_name(self):
        router = Router([FakeElasticReplica(0)], sticky=False)
        with pytest.raises(ValueError, match="already routed"):
            router.add_replica(FakeElasticReplica(0))

    def test_add_replica_warms_donor_prefix_chains(self):
        donor = FakeElasticReplica(0)
        k1 = _chunk_hash(ROOT_HASH, (1, 2, 3, 4))
        k2 = _chunk_hash(k1, (5, 6, 7, 8))
        k3 = _chunk_hash(ROOT_HASH, (9, 9, 9, 9))
        donor.engine = types.SimpleNamespace(prefix_index=types.SimpleNamespace(
            _entries=OrderedDict([
                (k1, types.SimpleNamespace(parent=ROOT_HASH,
                                           tokens=(1, 2, 3, 4))),
                (k2, types.SimpleNamespace(parent=k1,
                                           tokens=(5, 6, 7, 8))),
                (k3, types.SimpleNamespace(parent=ROOT_HASH,
                                           tokens=(9, 9, 9, 9))),
            ])))
        router = Router([donor], sticky=True)
        newbie = FakeElasticReplica(1)
        router.add_replica(newbie, warm_from=donor, warm_limit=1)
        # only the LONGEST chain replays under warm_limit=1, and it is
        # the full leaf-to-root token sequence
        assert [p[1] for p in newbie.puts] == [[1, 2, 3, 4, 5, 6, 7, 8]]

    def test_retire_hands_off_parked_sessions(self):
        fakes = [FakeElasticReplica(0, latency=5),
                 FakeElasticReplica(1, latency=5)]
        router = Router(fakes, policy="rr", sticky=False)
        rids = [router.submit(_prompt(3, base=10 * i), max_new_tokens=4)
                for i in range(6)]
        router.pump()                          # 3 admitted on each
        summary = router.retire_replica("f0")
        assert summary["handed_off"] == 3
        assert fakes[0].closed
        assert [h.name for h in router.handles] == ["f1"]
        assert len(fakes[1].imported) == 3
        s = router.stats()
        assert s["replicas_retired"] == 1
        assert s["sessions_handed_off"] == 3
        # conservation: every accepted request still finishes, with the
        # handed-off uids re-keyed to the survivor
        outs = router.drain()
        assert set(outs) == set(rids)

    def test_retire_finishes_in_flight_before_close(self):
        # a replica whose engine cannot export (pre-elastic protocol):
        # retire degrades to drain-in-place, still conserving requests
        fakes = [FakeElasticReplica(0, latency=3, exportable=False),
                 FakeElasticReplica(1, latency=1)]
        router = Router(fakes, policy="rr", sticky=False)
        rids = [router.submit(_prompt(3, base=10 * i), max_new_tokens=4)
                for i in range(4)]
        router.pump()
        summary = router.retire_replica("f0")
        assert summary["handed_off"] == 0
        assert fakes[0].closed and not fakes[0].admitted
        outs = router.drain()
        assert set(outs) == set(rids)
        assert router.stats()["sessions_handed_off"] == 0

    def test_retire_migrates_affinity_pins(self):
        fakes = [FakeElasticReplica(0, latency=1),
                 FakeElasticReplica(1, latency=1)]
        router = Router(fakes, policy="rr", sticky=True)
        shared = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
        router.submit(np.concatenate([shared, [11]]), max_new_tokens=4)
        router.drain()
        pinned = next(iter(router._affinity.values()))
        summary = router.retire_replica(pinned)
        assert summary["moved_pins"] == 1
        survivor = router.handles[0].name
        assert set(router._affinity.values()) == {survivor}
        # sticky traffic now lands on the survivor as an affinity hit
        router.submit(np.concatenate([shared, [22]]), max_new_tokens=4)
        router.drain()
        assert router.stats()["affinity_hits"] == 1

    def test_retire_refuses_last_replica(self):
        router = Router([FakeElasticReplica(0)], sticky=False)
        with pytest.raises(RouterRejection, match="no surviving"):
            router.retire_replica("f0")
        with pytest.raises(ValueError, match="unknown replica"):
            router.retire_replica("ghost")

    def test_retire_honours_named_target(self):
        fakes = [FakeElasticReplica(i, latency=5) for i in range(3)]
        router = Router(fakes, policy="rr", sticky=False)
        for i in range(3):
            router.submit(_prompt(3, base=10 * i), max_new_tokens=4)
        router.pump()
        summary = router.retire_replica("f0", target="f2")
        assert summary["target"] == "f2"
        assert len(fakes[2].imported) == summary["handed_off"] == 1
        assert not fakes[1].imported
        router.drain()


# -- integration against REAL engines ------------------------------------

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                     # noqa: E402

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.llama import (LlamaForCausalLM,       # noqa: E402
                                        get_config)

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


def _prompts(sizes, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


def _tiered_engine(params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", 16)
    kw.setdefault("num_pages", 9)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("kv_reserve", "on_demand")
    kw.setdefault("kv_tiering", {"host_pages": 64})
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=True,
                                   rng=jax.random.PRNGKey(11), **kw)


def _run_to_completion(eng, umap, outs):
    while eng.has_work():
        eng.step()
        outs.update({umap[u]: t for u, t in eng.get_outputs()})
    outs.update({umap[u]: t for u, t in eng.get_outputs()})


class TestEngineHandoffParity:

    def test_spilled_session_decodes_on_receiver_bit_exact(self, params):
        """A session parked with SPILLED private pages travels to a new
        engine in spill format and finishes there with greedy outputs
        identical to an uninterrupted run — restore on the receiver is
        a digest-verified page upload, not a re-prefill."""
        prompts = _prompts([12, 20, 9, 16])
        ref_eng = _tiered_engine(params)
        rmap = {ref_eng.put_request(p, max_new_tokens=40): i
                for i, p in enumerate(prompts)}
        ref = {}
        _run_to_completion(ref_eng, rmap, ref)
        ref_eng.close()

        a = _tiered_engine(params)
        amap = {a.put_request(p, max_new_tokens=40): i
                for i, p in enumerate(prompts)}
        while a.has_work():                 # run until a spilled session
            a.step()                        # is parked in the waiting q
            if any(r.spilled is not None for r in a.waiting):
                break
        else:
            pytest.fail("pool sized to force a parked spilled session")
        outs = {}
        outs.update({amap[u]: t for u, t in a.get_outputs()})
        sessions = a.export_parked()
        assert any(s["spill"] is not None for s in sessions), \
            "a spilled payload must travel in spill format"
        assert not a.waiting
        _run_to_completion(a, amap, outs)   # in-slot work finishes on A

        b = _tiered_engine(params)
        new_uids = b.import_parked(sessions)
        bmap = {nu: amap[int(s["uid"])]
                for s, nu in zip(sessions, new_uids)}
        _run_to_completion(b, bmap, outs)
        # the travelled payload was restored AND verified on B against
        # the donor's spill-time digests
        assert b.tiering.counters["imports"] >= 1
        st = b.tiering.stats()
        assert st["pages_verified"] == st["pages_restored"] > 0
        assert sorted(outs) == sorted(ref)
        for i in ref:
            np.testing.assert_array_equal(outs[i], ref[i],
                                          err_msg=f"prompt {i}")
        a.close()
        b.close()


def _engine(params):
    return RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=params, pipeline=True,
        rng=jax.random.PRNGKey(11), max_seqs=3, max_seq_len=128,
        prefill_chunk=8, decode_block_size=4, harvest_interval=3)


def _single_engine_reference(params, prompts, max_new):
    eng = _engine(params)
    order = {eng.put_request(p, max_new_tokens=max_new): i
             for i, p in enumerate(prompts)}
    outs = {}
    _run_to_completion(eng, order, outs)
    eng.close()
    return outs


class TestElasticServingParity:

    def test_grow_then_shrink_matches_static_engine(self, params):
        """One replica grows to two mid-traffic (prefix-warmed from the
        donor), then the original retires (parked sessions handed off,
        in-flight finished in place) — every request finishes and
        greedy outputs bit-match a static single engine."""
        prompts = _prompts((5, 9, 13, 7, 11, 6, 8, 10))
        ref = _single_engine_reference(params, prompts, max_new=12)
        rs = ReplicaSet(lambda i: _engine(params), 1)
        try:
            router = Router(rs, policy="least_tokens")
            rids = {router.submit(p, max_new_tokens=12): i
                    for i, p in enumerate(prompts[:5])}
            router.pump()                  # 3 into slots, 2 parked
            router.join()
            (h2,) = rs.grow(1)
            router.add_replica(h2, warm_from=rs.handles[0])
            for i, p in enumerate(prompts[5:], start=5):
                rids[router.submit(p, max_new_tokens=12)] = i
            summary = router.retire_replica("r0")
            rs.shrink("r0")
            outs = router.drain()
            assert sorted(rids[r] for r in outs) == sorted(ref)
            for rid, toks in outs.items():
                np.testing.assert_array_equal(toks, ref[rids[rid]])
            s = router.stats()
            assert s["replicas_added"] == 1
            assert s["replicas_retired"] == 1
            # anti-vacuity: the handoff actually moved parked sessions
            assert summary["handed_off"] >= 1
            assert s["sessions_handed_off"] == summary["handed_off"]
            assert [h.name for h in rs] == ["r1"]
        finally:
            rs.close()

    def test_grow_warms_from_a_real_prefix_cache_donor(self, params):
        """The donor is a real engine with the prefix cache on: the
        router finds its index under the engine's public name, replays
        the cached chains, and the new replica's own index holds them
        afterwards (the fake above cannot see a renamed member)."""
        rs = ReplicaSet(lambda i: RaggedInferenceEngineV2(
            LlamaForCausalLM(CFG), params=params, pipeline=True,
            rng=jax.random.PRNGKey(11), max_seqs=3, max_seq_len=128,
            prefill_chunk=16, page_size=16, decode_block_size=4,
            prefix_cache=True), 1)
        try:
            router = Router(rs, policy="least_tokens")
            shared = _prompts((48,), seed=5)[0]
            for tail in _prompts((5, 9), seed=6):
                router.submit(np.concatenate([shared, tail]),
                              max_new_tokens=4)
            router.drain()
            donor = rs.handles[0]
            assert len(donor.engine.prefix_index) > 0
            (h2,) = rs.grow(1)
            assert len(h2.engine.prefix_index) == 0
            router.add_replica(h2, warm_from=donor)
            assert len(h2.engine.prefix_index) > 0
            # the shared 48-token chain now hits on the new replica
            hit = h2.engine.prefix_index.match(
                np.concatenate([shared, _prompts((7,), seed=8)[0]]),
                touch=False)
            assert len(hit) == 3
        finally:
            rs.close()
