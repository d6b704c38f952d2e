"""Engine-level request cancellation (the front door's disconnect path).

``cancel(uid)`` must release EVERY resource a request holds at ANY
lifecycle stage — queued, spilled to the tiers, mid-prefill,
mid-decode (inside a pipelined carry), or finished-but-uncollected —
and the conservation audits must stay clean after each:
``PageAllocator.audit()`` via ``audit_kv_sharing()`` (slot rows +
prefix entries + spill-holds cover every refcount) and
``TieredKVStore.audit()`` (no orphaned spill payloads).  Survivors of
a cancel must finish with greedy outputs bit-identical to a run that
never saw the cancelled request's neighbours torn down.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config

pytestmark = pytest.mark.faults

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=256, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


def make(params, tiering=None, prefix=None, pipeline=False, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", 16)
    kw.setdefault("num_pages", 9)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("kv_reserve", "on_demand")
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=pipeline, kv_tiering=tiering,
                                   prefix_cache=prefix,
                                   rng=jax.random.PRNGKey(11), **kw)


def _prompts(sizes, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


def _finish(eng):
    outs = {}
    while eng.has_work():
        eng.step()
        outs.update(eng.get_outputs())
        eng.audit_kv_sharing()
    eng.sync()
    outs.update(eng.get_outputs())
    return outs


def _reference(params, prompts, max_new, **mk):
    eng = make(params, **mk)
    uids = [eng.put_request(p, max_new_tokens=max_new) for p in prompts]
    outs = _finish(eng)
    eng.close()
    return {u: outs[u] for u in uids}


class TestCancelStages:

    def test_cancel_queued(self, params):
        eng = make(params, max_seqs=2)
        prompts = _prompts((8, 8, 8))
        uids = [eng.put_request(p, max_new_tokens=8) for p in prompts]
        # nothing stepped yet: all three are queued
        assert eng.cancel(uids[2]) == "queued"
        eng.audit_kv_sharing()
        outs = _finish(eng)
        assert sorted(outs) == sorted(uids[:2])
        assert eng.cancels == 1
        assert eng.request_latency.summary()["cancelled"] == 1
        eng.close()

    def test_cancel_prefill(self, params):
        # 40-token prompt, prefill_chunk 16: after one step the slot is
        # mid-prefill (prefill_done < ctx_len)
        eng = make(params)
        (p,) = _prompts((40,))
        uid = eng.put_request(p, max_new_tokens=8)
        eng.step()
        r = next(s for s in eng.slots if s is not None and s.uid == uid)
        assert r.prefill_done < r.ctx_len, "stage setup: not mid-prefill"
        free0 = eng.allocator.free_pages
        assert eng.cancel(uid) == "prefill"
        eng.audit_kv_sharing()
        assert eng.allocator.free_pages > free0, "pages not reclaimed"
        assert not eng.has_work()
        eng.close()

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_cancel_mid_decode(self, params, pipeline):
        # the survivor's greedy output must be bit-identical to a solo
        # run — tearing a neighbour out of the fused batch mid-decode
        # must not perturb anyone else
        prompts = _prompts((12, 9))
        ref = _reference(params, prompts[:1], max_new=16,
                         pipeline=pipeline)
        eng = make(params, pipeline=pipeline)
        keep = eng.put_request(prompts[0], max_new_tokens=16)
        kill = eng.put_request(prompts[1], max_new_tokens=16)
        for _ in range(6):                       # both into decode
            eng.step()
        stage = eng.cancel(kill)
        assert stage in ("decode", "prefill", "finished"), stage
        eng.audit_kv_sharing()
        outs = _finish(eng)
        assert kill not in outs
        np.testing.assert_array_equal(outs[keep], list(ref.values())[0])
        eng.close()

    def test_cancel_spilled_releases_tier_and_holds(self, params):
        # pressured pool + tiers: step until some waiting request has a
        # spilled payload, cancel it, and require both audits clean and
        # the tier entry gone
        eng = make(params, tiering={"host_pages": 64})
        prompts = _prompts((12, 20, 9, 16, 14))
        uids = [eng.put_request(p, max_new_tokens=40) for p in prompts]
        victim = None
        for _ in range(200):
            eng.step()
            spilled = [r for r in eng.waiting if r.spilled is not None]
            if spilled:
                victim = spilled[0]
                break
        assert victim is not None, "pressure never spilled a request"
        assert eng.tiering.holds(victim.uid)
        assert eng.cancel(victim.uid) == "spilled"
        assert not eng.tiering.holds(victim.uid)
        eng.audit_kv_sharing()
        eng.tiering.audit()
        outs = _finish(eng)
        assert sorted(outs) == sorted(u for u in uids if u != victim.uid)
        eng.tiering.audit()
        eng.close()

    def test_cancel_finished_uncollected(self, params):
        eng = make(params)
        (p,) = _prompts((8,))
        uid = eng.put_request(p, max_new_tokens=4)
        while eng.has_work():
            eng.step()
        eng.sync()
        assert any(r.uid == uid for r in eng.finished)
        assert eng.cancel(uid) == "finished"
        assert eng.get_outputs() == []
        eng.audit_kv_sharing()
        eng.close()

    def test_cancel_unknown_uid_is_none(self, params):
        eng = make(params)
        assert eng.cancel(12345) is None
        assert eng.cancels == 0
        eng.close()


class TestCancelUnderPrefixSharing:

    def test_audit_clean_under_cow_pressure(self, params):
        # two requests share a 2-page prefix through the prefix cache
        # (COW refcounts > 1 on the shared pages); cancelling the
        # second mid-decode must decref, not free, the shared pages —
        # audit_kv_sharing() proves each refcount is covered, and the
        # survivor's output stays bit-identical to serving alone
        shared = _prompts((32,), seed=5)[0]
        tail_a = np.array([11, 12, 13], np.int32)
        tail_b = np.array([21, 22, 23, 24], np.int32)
        pa = np.concatenate([shared, tail_a])
        pb = np.concatenate([shared, tail_b])
        ref = _reference(params, [pa], max_new=12, prefix=True,
                         num_pages=12)
        eng = make(params, prefix=True, num_pages=12)
        keep = eng.put_request(pa, max_new_tokens=12)
        kill = eng.put_request(pb, max_new_tokens=12)
        for _ in range(5):
            eng.step()
            eng.audit_kv_sharing()
        stage = eng.cancel(kill)
        assert stage is not None
        eng.audit_kv_sharing()
        outs = _finish(eng)
        assert kill not in outs
        np.testing.assert_array_equal(outs[keep], list(ref.values())[0])
        # the cancelled request's resources are fully reclaimed: a
        # fresh identical request must be admittable and finish clean
        redo = eng.put_request(pb, max_new_tokens=12)
        outs2 = _finish(eng)
        assert redo in outs2
        eng.audit_kv_sharing()
        eng.close()

    def test_cancel_every_waiting_and_resident_request(self, params):
        # sweep: cancel EVERYTHING at whatever stage it happens to be
        # in after a few pressured steps; the pool must return to its
        # baseline free-page count (nothing leaked anywhere)
        eng = make(params, tiering={"host_pages": 64}, prefix=True)
        free0 = eng.allocator.free_pages
        prompts = _prompts((12, 20, 9, 16, 14, 18))
        uids = [eng.put_request(p, max_new_tokens=40) for p in prompts]
        for _ in range(4):
            eng.step()
        stages = {}
        for u in uids:
            stages[u] = eng.cancel(u)
            eng.audit_kv_sharing()
            eng.tiering.audit()
        # a request may have finished+collected already (stage None);
        # everything else must have been found somewhere
        assert all(s is not None for s in stages.values()), stages
        assert not eng.has_work()
        eng.sync()
        # pages still out are exactly the prefix cache's resident
        # entries (published chains outlive their requests by design);
        # nothing else may hold a page
        pfx_held = sum(1 for e in eng.kv.pfx._entries.values()
                       if e.state == "resident")
        assert eng.allocator.free_pages == free0 - pfx_held, (
            f"leak: {free0 - pfx_held - eng.allocator.free_pages} pages "
            f"missing after cancelling at stages {stages} "
            f"({pfx_held} prefix-held)")
        eng.close()
