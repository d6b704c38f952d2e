"""Seven contracts of the serving engine's loops, by each tiny serving model:
parity of the pipelined and unpipelined loops under greedy and under seeded
sampling, pre-emption under a dry pool, an early finish, a steady state that
compiles nothing, a cancel mid-decode, a ``sync()`` mid-run.

The loops are the three a cell runs: the fused tick (``_step``), the
unpipelined decode block (``_step_decode_block``) and the queue of dispatched
work (``_pipeline_start`` / ``_pipeline_step``).  This file is no test file:
``test_loop_contracts*.py`` each name a group of the ten models
(``rig = rig_fixture([...])``) and import the seven tests from here, because
``--dist loadfile`` gives a file to one worker and all ten are 280 s cold.
A rig is one model's three engines (a pipelined one, an unpipelined one, and
a pipelined one over a pool too small for its mix), each built once a module.
"""
import importlib
import itertools

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import paged
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.telemetry.compiles import count_compiles
from tests.unit.inference.test_cache_kind_seam import F32
from tests.unit.inference.test_cache_kind_seam import MODELS as SEAM_MODELS

__all__ = [
    "test_greedy_parity_with_midrun_admissions",
    "test_seeded_parity_with_midrun_admissions",
    "test_a_dry_pool_preempts_and_every_request_finishes",
    "test_an_early_eos_seats_a_waiting_request",
    "test_a_second_mix_compiles_nothing",
    "test_a_cancel_mid_decode_leaves_the_others_alone",
    "test_a_sync_mid_run_flushes_and_the_run_goes_on"]

MODELS = {**SEAM_MODELS, "tinytrinity": ("trinity", "TrinityForCausalLM", {})}
ENGINE = dict(max_seqs=3, max_seq_len=128, prefill_chunk=16, page_size=16,
              decode_block_size=4, harvest_interval=3)
ROOMY = 1 + 3 * 8               # every slot can reach max_seq_len
TIGHT = 9                       # three rows of HEAVY want twelve pages
SEEDED = dict(do_sample=True, temperature=0.8, top_k=8, top_p=0.9)
# prompt sizes, then {step: sizes admitted at that step}
MIX = ([21, 5, 13], {4: [7], 9: [13]})
SECOND = ([9, 17, 4], {3: [11]})
HEAVY = ([12, 20, 9, 16], {})


def prompts(sizes, seed):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


def rewind(eng):
    """An idle engine back at uid 0 with its counters cleared: a drawn
    token's key is ``(engine seed, uid, position)``, so two runs that are
    compared under sampling give their requests the same uids."""
    assert not eng.has_work() and not eng.get_outputs()
    audit_empty(eng)
    eng._uid = itertools.count()
    eng.host_stats.reset()


def audit_empty(eng):
    eng.audit_kv_sharing()                  # raises on a leaked page
    assert eng.allocator.free_pages == eng.num_pages - 1
    own = eng.kv.window
    assert own is None or own.allocator.free_pages == own.num_pages - 1


def serve(eng, mix, seed=3, each=None, **req_kw):
    """Run ``mix`` to its end on ``eng``; returns each request's tokens in
    the order the requests were put.  ``each(i)``: request ``i``'s own
    keywords beside ``req_kw``."""
    rewind(eng)
    sizes, mid = mix
    mid = {step: prompts(s, seed + step) for step, s in mid.items()}
    order, outs, step = [], {}, 0

    def put(p):
        kw = dict(req_kw, **(each(len(order)) if each else {}))
        order.append(eng.put_request(p, **kw))

    for p in prompts(sizes, seed):
        put(p)
    while eng.has_work() or mid:
        for p in mid.pop(step, []):
            put(p)
        if eng.has_work():
            eng.step()
            outs.update(eng.get_outputs())
        step += 1
    outs.update(eng.get_outputs())
    assert sorted(outs) == sorted(order)
    return [outs[uid] for uid in order]


def same(got, want):
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


class Rig:
    """A tiny model's three engines, and the unpipelined loop's greedy
    answers to each mix (every contract's reference), run once."""

    def __init__(self, preset):
        module, cls, more = MODELS[preset]
        mod = importlib.import_module(f"deepspeed_tpu.models.{module}")
        self.model = getattr(mod, cls)(mod.get_config(preset, **F32, **more))
        self.params = jax.jit(self.model.init)(
            jax.random.PRNGKey(7), np.zeros((1, 8), np.int32))["params"]
        self._engines, self._refs = {}, {}

    def engine(self, pipeline, num_pages=ROOMY):
        key = (pipeline, num_pages)
        if key not in self._engines:
            cfg = self.model.config
            # a model whose window layers keep a pool of their own sizes it:
            # roomy in every rig, the pool that runs dry is the first
            own = ({"window_pool_bytes": ROOMY * programs.paged_kv_page_bytes(
                self.model, cfg, ENGINE["page_size"], "none", window=True)}
                if paged.window_pool_layers(cfg) else {})
            self._engines[key] = RaggedInferenceEngineV2(
                self.model, params=self.params, pipeline=pipeline,
                num_pages=num_pages, rng=jax.random.PRNGKey(11), **ENGINE,
                **own)
        return self._engines[key]

    on = property(lambda self: self.engine(True))
    off = property(lambda self: self.engine(False))
    tight = property(lambda self: self.engine(True, TIGHT))

    def ref(self, name, mix, **req_kw):
        if name not in self._refs:
            self._refs[name] = serve(self.off, mix, **req_kw)
        return self._refs[name]

    def close(self):
        for eng in self._engines.values():
            eng.close()


def rig_fixture(presets):
    """The module's ``rig`` fixture over ``presets``: pytest runs a module's
    tests grouped by a module-scoped parameter, so a model's engines are
    built, serve its seven contracts and are closed."""
    @pytest.fixture(scope="module", params=presets)
    def rig(request):
        r = Rig(request.param)
        yield r
        r.close()
    return rig


# -- the contracts ------------------------------------------------------------

def test_greedy_parity_with_midrun_admissions(rig):
    """(a) each request's greedy tokens under ``pipeline=True`` are the
    ``pipeline=False`` run's, with requests admitted mid-run."""
    off = rig.ref("mix", MIX, max_new_tokens=10)
    on = serve(rig.on, MIX, max_new_tokens=10)
    assert len(on) == 5
    same(on, off)
    st = rig.on.host_stats
    # the pipelined run really went ahead of its fetches, and on EOS-free
    # traffic it waited only where a finish in flight had to seat a request
    assert st.decode_blocks_ahead > 0 and st.admissions_ahead > 0
    assert st.blocking_gets < st.dispatches
    assert set(st.drain_reasons) <= {"pages"}, st.drain_reasons


def test_seeded_parity_with_midrun_admissions(rig):
    """(b) the same under seeded sampling."""
    kw = dict(max_new_tokens=9, **SEEDED)
    on = serve(rig.on, MIX, **kw)
    off = serve(rig.off, MIX, **kw)
    same(on, off)
    greedy = rig.ref("mix", MIX, max_new_tokens=10)
    assert any(not np.array_equal(a, b[:a.size])
               for a, b in zip(on, greedy)), "nothing was drawn"


def test_a_dry_pool_preempts_and_every_request_finishes(rig):
    """(c) a pool too small for the mix pre-empts, every request finishes
    with the roomy pool's tokens and ``audit()`` is clean."""
    roomy = rig.ref("heavy", HEAVY, max_new_tokens=40)
    before = rig.tight.evictions
    got = serve(rig.tight, HEAVY, max_new_tokens=40)
    assert rig.tight.evictions > before, (
        "pool sized to pre-empt; nothing was")
    same(got, roomy)
    audit_empty(rig.tight)


def test_an_early_eos_seats_a_waiting_request(rig):
    """(d) an early EOS gives its slot and pages to a waiting request in
    the same run."""
    sizes, new = [8, 14, 6, 10], [20, 24, 24, 6]
    plain = rig.ref("eos", (sizes, {}), each=lambda i: dict(
        max_new_tokens=new[i]))
    eos = int(plain[0][sizes[0] + 1])       # request 0's second token
    for eng in (rig.on, rig.off):
        rewind(eng)
        uids = [eng.put_request(p, max_new_tokens=n,
                                **({"eos_token_id": eos} if i == 0 else {}))
                for i, (p, n) in enumerate(zip(prompts(sizes, 3), new))]
        outs, seated_beside = {}, False
        while eng.has_work():
            eng.step()
            live = {r.uid for r in eng.slots if r is not None}
            seated_beside |= {uids[1], uids[2], uids[3]} <= live
            outs.update(eng.get_outputs())
        assert seated_beside, "the fourth request never sat beside the two"
        stop = sizes[0] + 1 + int(np.argmax(plain[0][sizes[0]:] == eos))
        np.testing.assert_array_equal(outs[uids[0]], plain[0][:stop])
        assert stop < plain[0].size
        same([outs[u] for u in uids[1:]], plain[1:])
        audit_empty(eng)


def test_a_second_mix_compiles_nothing(rig):
    """(e) a second mix compiles nothing."""
    for eng in (rig.on, rig.off):
        serve(eng, MIX, max_new_tokens=10)
        serve(eng, MIX, max_new_tokens=9, **SEEDED)
        with count_compiles() as compiles:
            serve(eng, SECOND, seed=8, each=lambda i: dict(
                max_new_tokens=7 + 3 * i, **(SEEDED if i % 2 else {})))
        assert compiles.count == 0, (
            f"{compiles.count} compilations in a second mix")


def test_a_cancel_mid_decode_leaves_the_others_alone(rig):
    """(f) ``cancel`` mid-decode leaves ``audit()`` clean and the other
    requests' tokens as they were."""
    want = rig.ref("mix", MIX, max_new_tokens=10)
    for eng in (rig.on, rig.off):
        rewind(eng)
        uids = [eng.put_request(p, max_new_tokens=10)
                for p in prompts(MIX[0], 3)]
        outs = {}

        def decoding():
            return any(r is not None and r.uid == uids[1]
                       and len(r.generated) + r.pending >= 3
                       for r in eng.slots)
        while not decoding():
            eng.step()
            outs.update(eng.get_outputs())
        assert eng.cancel(uids[1]) == "decode"
        eng.audit_kv_sharing()
        while eng.has_work():
            eng.step()
            outs.update(eng.get_outputs())
        assert sorted(outs) == [uids[0], uids[2]]
        same([outs[uids[0]], outs[uids[2]]], [want[0], want[2]])
        audit_empty(eng)


def test_a_sync_mid_run_flushes_and_the_run_goes_on(rig):
    """(g) a ``sync()`` mid-run flushes the deferred tokens and the run
    goes on to the same tokens."""
    want = rig.ref("mix", MIX, max_new_tokens=10)
    eng = rig.on
    rewind(eng)
    uids = [eng.put_request(p, max_new_tokens=10) for p in prompts(MIX[0], 3)]
    seen = lambda: sum(len(r.generated) for r in itertools.chain(
        (r for r in eng.slots if r is not None), eng.finished))
    while not any(r is not None and r.pending for r in eng.slots):
        eng.step()
    before = seen()
    flushed = eng.sync()
    assert flushed > 0 and seen() - before == flushed
    assert not any(r.pending for r in eng.slots if r is not None)
    assert eng.sync() == 0
    outs = dict(eng.get_outputs())
    while eng.has_work():
        eng.step()
        outs.update(eng.get_outputs())
    same([outs[u] for u in uids], want[:3])
    audit_empty(eng)
