"""``KVState`` — the one owner of a sequence's KV state — driven with NO
model: the cache is a tree of small arrays whose rows are recognisable, a
request is the engine's plain dataclass.  Then the seam from the scheduler's
side: the seven places that used to give a slot back by hand each leave the
same state through ``_unseat`` + ``KVState.release``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.inference.config import (KVTieringConfig,
                                            PrefixCacheConfig)
from deepspeed_tpu.inference.v2.kv_state import KVState
from deepspeed_tpu.inference.v2.ragged_engine import (
    RaggedInferenceEngineV2, Request)
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.telemetry import RequestLatencyTracker

PAGE, PAGES, SEQS, MAX_LEN = 4, 12, 3, 32
TIERS = {"host_pages": 32}


# per-slot state beside the pool, by shape: a few rows a layer (a short
# convolution's), a matrix a head (linear attention's), a matrix whose heads
# lie along the lanes (the gated delta rule's)
STATE_SHAPES = {"rows": (2, 8), "matrix": (2, 4, 4), "wide-matrix": (4, 12)}


# a layer's pool leaves, by what a cache kind keeps for a page (each leads
# with the page dim, which is all ``KVState`` knows of it): per-head K/V with
# its scale rows; K/V heads first beside compressed keys (block selection);
# one latent row a token; K/V a token a row beside one index key a token
LEAF_SETS = {
    "kv": {"kv_pages": (PAGE, 2, 4), "kv_scales": (PAGE,)},
    "kv+compressed": {"sparse_kv_pages": (4, PAGE, 4), "ckey_pages": (2, 4)},
    "latent": {"latent_pages": (PAGE, 8)},
    "kv+index": {"tokensel_kv_pages": (PAGE, 16),
                 "index_key_pages": (PAGE // 2, 8)},
}


def make_kv(tiering=None, prefix=False, num_pages=PAGES, state=None,
            leaves="kv", conv_rows=False):
    """A pool whose page ``p`` holds ``100 * p`` plus a small count in
    every leaf of ``LEAF_SETS[leaves]`` (negated in every second leaf), so
    a moved page is told by its content; with ``state`` (a key of
    ``STATE_SHAPES``) a per-slot state leaf beside it whose slot ``s`` holds
    ``7 + s`` and counts up; with ``conv_rows`` a SECOND state leaf of the
    same layer, in a module of its own (a gated-delta-rule layer's
    convolution rows, bf16), whose slot ``s`` holds ``50 + s``."""
    layer = {}
    for i, (name, shape) in enumerate(LEAF_SETS[leaves].items()):
        within = np.arange(int(np.prod(shape)), dtype=np.float32).reshape(
            shape) % PAGE
        layer[name] = jnp.asarray((-1) ** i * (
            100 * np.arange(num_pages, dtype=np.float32).reshape(
                (num_pages,) + (1,) * len(shape)) + within))
    cache = {"model": {"layers_0": layer}}
    if state is not None:
        shape = STATE_SHAPES[state]
        cache["model"]["layers_1"] = {"slot_state": jnp.asarray(
            (7 + np.arange(SEQS)).reshape((SEQS,) + (1,) * len(shape))
            + np.arange(np.prod(shape), dtype=np.float32).reshape(shape))}
        if conv_rows:
            cache["model"]["layers_1"]["conv"] = {"slot_state": jnp.asarray(
                50.0 + np.arange(SEQS)[:, None, None] + np.zeros((3, 6)),
                jnp.bfloat16)}
    return KVState(cache, num_pages=num_pages, page_size=PAGE,
                   max_seqs=SEQS, max_seq_len=MAX_LEN,
                   kv_reserve="on_demand",
                   tier_cfg=KVTieringConfig.coerce(tiering),
                   pfx_cfg=PrefixCacheConfig.coerce(prefix),
                   host_stats=HostStageStats(),
                   request_latency=RequestLatencyTracker())


def make_req(uid, n_ctx, slot, generated=(), seed=0):
    prompt = np.random.default_rng(seed).integers(
        1, 50, size=n_ctx).astype(np.int32)
    r = Request(uid=uid, prompt=prompt, max_new_tokens=8)
    r.ctx, r.slot, r.generated = prompt, slot, list(generated)
    return r


def seat(kv, r, block=4):
    assert kv.attach(r, kv.need(r, block))
    return [int(p) for p in kv.page_table[r.slot] if p >= 0]


def page_rows(kv, pages):
    """What each of ``pages`` holds in every pool leaf, side by side."""
    return np.concatenate(
        [np.asarray(leaf)[np.asarray(pages)].reshape(len(pages), -1)
         for _, leaf in sorted(kv.cache["model"]["layers_0"].items())],
        axis=1)


def state_of(kv, slot):
    return np.asarray(kv.cache["model"]["layers_1"]["slot_state"])[slot]


def slots_of(*reqs):
    out = [None] * SEQS
    for r in reqs:
        out[r.slot] = r
    return out


def decoding(r, new_tokens=1):
    """Mark ``r`` as past prefill with ``new_tokens`` sampled."""
    r.prefill_done = r.ctx_len
    r.generated = list(range(3, 3 + new_tokens))
    return r


@pytest.mark.parametrize("after", ["attach", "ensure", "spill",
                                   "failed_restore"])
def test_release_leaves_the_pool_whole(after):
    kv = make_kv(tiering=TIERS)
    r = make_req(1, 10, slot=0)
    seat(kv, r)
    parked = []
    if after == "ensure":
        assert kv.ensure(0, 27)
        assert kv.allocator.owned(0) == 7
    if after in ("spill", "failed_restore"):
        assert kv.spill(decoding(r), last_tok=5)
        parked = [r]
    if after == "failed_restore":
        kv.release(0)
        r.slot = 1
        seat(kv, r)
        with faults.FaultInjector(seed=6) as inj:
            inj.bitflip("kv.read_page", bits=1, count=10)
            assert kv.restore(r) is None
        assert r.spilled is None and kv.restores == 0
        parked = []
    slot = r.slot
    kv.release(slot)
    assert (kv.page_table[slot] == -1).all()
    assert kv.allocator.owned(slot) == 0
    counts = kv.audit([None] * SEQS, parked)
    assert counts["referenced"] == 0
    assert kv.allocator.free_pages == PAGES - 1
    kv.close(parked)


@pytest.mark.parametrize("leaves", sorted(LEAF_SETS))
def test_spill_then_restore_brings_back_the_rows_and_keeps_the_shared_prefix(
        leaves):
    kv = make_kv(tiering=TIERS, prefix=True, leaves=leaves)
    r = make_req(1, 13, slot=0)
    pages = seat(kv, r)
    decoding(r)
    kv.publish(r, r.ctx, 13)            # three full pages into the index
    want = page_rows(kv, pages[:4])
    assert kv.spill(r, last_tok=9)
    shared = r.spilled["shared_pages"]
    assert shared == pages[:3] and r.spilled["n_pages"] == 1
    kv.release(0)
    # index + spill-hold: the shared pages never left the pool
    assert all(kv.allocator.refcount(p) == 2 for p in shared)
    kv.audit([None] * SEQS, [r])
    # another tenant takes the private page's place and scribbles on it
    other = make_req(2, 20, slot=2, seed=1)
    seat(kv, other)
    kv.cache = jax.tree_util.tree_map(
        lambda l: l.at[np.asarray(pages[3:])].set(0), kv.cache)
    r.slot = 1
    now = seat(kv, r)
    assert now[:3] == shared and now[3] != pages[3]
    assert kv.restore(r) == 9 and r.spilled is None and kv.restores == 1
    np.testing.assert_array_equal(page_rows(kv, now[:4]), want)
    # the holds went with the restore: index + slot hold each shared page
    assert all(kv.allocator.refcount(p) == 2 for p in shared)
    kv.audit(slots_of(r, other))
    kv.close()


@pytest.mark.parametrize("leaves", sorted(LEAF_SETS))
def test_a_full_prefix_match_copies_its_last_page_before_the_write(leaves):
    kv = make_kv(prefix=True, leaves=leaves)
    first = make_req(1, 8, slot=0)
    pages = seat(kv, first)
    first.prefill_done = 8
    kv.publish(first, first.ctx, 8)
    second = make_req(2, 8, slot=1)             # same seed: same tokens
    mine = seat(kv, second)
    assert second.prefill_done == 7 and second.pc_cached == 7
    assert mine[0] == pages[0], "the first page is shared"
    assert mine[1] != pages[1], "the page the re-prefill writes is private"
    np.testing.assert_array_equal(page_rows(kv, [mine[1]]),
                                  page_rows(kv, [pages[1]]))
    assert kv.host_stats.prefix_cow_copies == 1
    assert kv.host_stats.prefix_hits == 1
    kv.audit(slots_of(first, second))


@pytest.mark.parametrize("state,leaves", [
    (None, "kv"), ("rows", "kv"), ("matrix", "kv"), ("matrix", "kv+compressed"),
    (None, "latent"), (None, "kv+index")])
def test_export_then_adopt_round_trips_on_a_second_pool(state, leaves):
    """The pages, whatever their leaves hold, and the per-slot state,
    whatever its shape, of a spilled sequence reach a second pool's slot
    through ``export`` and ``adopt``."""
    kv, kv2 = (make_kv(tiering=TIERS, state=state, leaves=leaves)
               for _ in range(2))
    r = decoding(make_req(1, 10, slot=0), new_tokens=2)
    pages = seat(kv, r)
    want = page_rows(kv, pages[:3])             # 11 live tokens: 3 pages
    if state:
        # ``attach`` zeroed the slot; what the sequence then left there
        kv.cache["model"]["layers_1"]["slot_state"] = \
            kv.cache["model"]["layers_1"]["slot_state"].at[0].add(5.0)
        held = state_of(kv, 0).copy()
        assert held.shape == STATE_SHAPES[state] and (held == 5.0).all()
    assert kv.spill(r, last_tok=4)
    kv.release(0)
    blob = kv.export(r)
    assert blob["spill"]["payload"]["n_pages"] == 3
    assert blob["generated"] == r.generated and blob["prefill_done"] == 10
    twin = make_req(7, 10, slot=2)
    twin.generated, twin.prefill_done = blob["generated"], 10
    kv2.cache = jax.tree_util.tree_map(jnp.zeros_like, kv2.cache)
    assert kv2.adopt(blob["spill"], twin)
    assert twin.spilled["n_pages"] == 3 and not twin.spilled["shared_pages"]
    now = seat(kv2, twin)
    assert kv2.restore(twin) == 4
    np.testing.assert_array_equal(page_rows(kv2, now[:3]), want)
    if state:
        np.testing.assert_array_equal(state_of(kv2, 2), held)
        assert (state_of(kv2, 0) == 0).all()
    # a pool with no tiers cannot take the payload: the caller folds
    assert not make_kv().adopt(blob["spill"], make_req(8, 10, slot=0))
    kv.close()
    kv2.close()


@pytest.mark.parametrize("state", ["rows", "matrix", "wide-matrix"])
def test_attach_zeroes_spill_carries_and_release_ends_the_state(state):
    """The state's life is the pages': a slot's next tenant reads zeros,
    ``spill`` -> ``restore`` brings back what the sequence left, into
    another slot, and no other slot's state moves."""
    kv = make_kv(tiering=TIERS, state=state)
    before = state_of(kv, 1).copy()
    r = decoding(make_req(1, 10, slot=0))
    seat(kv, r)
    assert (state_of(kv, 0) == 0).all()
    assert kv.state_bytes_per_seq == 4 * int(np.prod(STATE_SHAPES[state]))
    kv.cache["model"]["layers_1"]["slot_state"] = \
        kv.cache["model"]["layers_1"]["slot_state"].at[0].set(3.5)
    assert kv.spill(r, last_tok=9)
    kv.release(0)
    r.slot = 2
    seat(kv, r)
    assert (state_of(kv, 2) == 0).all()
    assert kv.restore(r) == 9
    assert (state_of(kv, 2) == 3.5).all()
    np.testing.assert_array_equal(state_of(kv, 1), before)
    kv.release(2)
    kv.close()


def test_ensure_past_the_pool_says_no_and_leaks_nothing():
    kv = make_kv(num_pages=6)
    a, b = make_req(1, 6, slot=0), make_req(2, 4, slot=1)
    seat(kv, a)
    seat(kv, b)
    free = kv.allocator.free_pages
    assert free == 0
    assert not kv.ensure(0, 24)
    assert kv.allocator.free_pages == free and kv.allocator.owned(0) == 3
    assert not kv.can_admit(make_req(3, 4, slot=-1), 8)
    kv.audit(slots_of(a, b))
    kv.release(1)
    assert kv.ensure(0, 16) and kv.allocator.owned(0) == 4


# -- the scheduler's side of the seam ---------------------------------------

CFG = get_config("tinyllama", dtype=jnp.float32, param_dtype=jnp.float32,
                 scan_layers=False, remat=False, use_flash_attention=False)


@pytest.fixture(scope="module")
def params():
    return jax.jit(LlamaForCausalLM(CFG).init)(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32))


def make_engine(params, **kw):
    return RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=params, pipeline=False, max_seqs=2,
        max_seq_len=64, prefill_chunk=16, page_size=8, num_pages=12,
        decode_block_size=2, **kw)


def _seated(eng, n=12, steps=2, **kw):
    uid = eng.put_request(np.arange(1, n + 1, dtype=np.int32),
                          **{"max_new_tokens": 24, **kw})
    for _ in range(steps):
        eng.step()
    (r,) = [s for s in eng.slots if s is not None]
    assert r.uid == uid and r.generated
    return r


def _cancel(eng):
    r = _seated(eng)
    assert eng.cancel(r.uid) == "decode"
    return r, None


def _admit_undo(eng):
    uid = eng.put_request(np.arange(1, 13, dtype=np.int32),
                          max_new_tokens=24)
    attach = eng.kv.attach
    eng.kv.attach = lambda req, need: attach(req, need) and False
    eng._admit()                        # pages granted, then "failed"
    eng.kv.attach = attach
    (r,) = eng.waiting
    assert r.uid == uid
    return r, "head"


def _handoff_fold(eng):
    eng.put_request(np.arange(1, 13, dtype=np.int32), max_new_tokens=24,
                    handoff=True)
    eng.step()                          # prefill + first token, then parks
    (r,) = eng._handoff_ready
    assert eng.handoff_folds == 1       # no tiers: the degraded leg
    return r, "folded"


def _evict(eng):
    r = _seated(eng)
    eng._evict(r)
    assert eng.evictions == 1 and eng.waiting[-1] is r
    return r, "folded"


def _spill(eng):
    r = _seated(eng)
    done = r.prefill_done
    assert eng._spill(r) and eng.spills == 1
    assert r.spilled["n_pages"] > 0 and r.prefill_done == done
    return r, "parked"


def _restore_failure(eng):
    r = _seated(eng)
    assert eng._spill(r)
    # what ``_admit`` does for the head of the queue, one step at a time
    # (the whole of it would re-admit the folded request at once)
    eng.waiting.remove(r)
    r.slot, eng.slots[1] = 1, r
    assert eng.kv.attach(r, eng.kv.need(r, eng.decode_block_size))
    with faults.FaultInjector(seed=6) as inj:
        inj.bitflip("kv.read_page", bits=1, count=10)
        eng._restore(r)
    assert eng.restores == 0 and r.spilled is None
    assert eng.waiting[0] is r
    return r, "folded"


def _reap(eng):
    eng.put_request(np.arange(1, 13, dtype=np.int32), max_new_tokens=3)
    while not eng.finished:
        eng.step()
    (r,) = eng.finished
    assert r.done and len(r.generated) == 3
    return r, None


@pytest.mark.parametrize("site,tiers", [
    (_cancel, False), (_admit_undo, False), (_handoff_fold, False),
    (_evict, False), (_spill, True), (_restore_failure, True),
    (_reap, False)], ids=lambda v: getattr(v, "__name__", None))
def test_every_way_out_of_a_slot_leaves_the_same_state(params, site, tiers):
    eng = make_engine(params, kv_tiering=TIERS if tiers else None)
    r, left_as = site(eng)
    assert eng.slots == [None, None] and r.slot == -1
    assert (eng.page_table == -1).all()
    assert all(eng.allocator.owned(s) == 0 for s in range(2))
    counts = eng.audit_kv_sharing()
    assert counts["referenced"] == 0
    assert eng.allocator.free_pages == eng.num_pages - 1
    if left_as == "folded":             # a re-prefill continuation
        assert r.prefill_done == 0 and r.pc_pages == 0
        np.testing.assert_array_equal(
            r.ctx, np.concatenate([r.prompt, r.generated]).astype(np.int32))
    # and the session still finishes with the tokens it would have had
    if left_as is not None:
        if r in eng._handoff_ready:
            eng._handoff_ready.remove(r)
            r.handoff = False
            eng.waiting.append(r)
        want = make_engine(params).generate_all(
            [r.prompt], max_new_tokens=r.max_new_tokens)
        got = eng.drain()
        np.testing.assert_array_equal(got[r.uid], want[0])
    eng.close()


@pytest.mark.parametrize("verb", ["spill-restore", "export-adopt"])
def test_two_state_leaves_a_layer_travel_together(verb):
    """A gated-delta-rule layer keeps TWO per-slot leaves (its convolution's
    rows in a module of their own beside the matrix state): ``attach`` zeroes
    both, ``spill`` -> ``restore`` and ``export`` -> ``adopt`` carry both into
    another slot (of another pool), and no other slot's state moves."""
    two = dict(tiering=TIERS, state="wide-matrix", conv_rows=True)
    leaves = lambda kv, slot: [
        np.asarray(kv.cache["model"]["layers_1"]["slot_state"])[slot],
        np.asarray(kv.cache["model"]["layers_1"]["conv"]["slot_state"]
                   .astype(jnp.float32))[slot]]
    kv = make_kv(**two)
    assert kv.state_bytes_per_seq == 4 * 4 * 12 + 2 * 3 * 6
    other = [a.copy() for a in leaves(kv, 1)]
    r = decoding(make_req(1, 10, slot=0))
    seat(kv, r)
    assert all((a == 0).all() for a in leaves(kv, 0))
    layer = kv.cache["model"]["layers_1"]
    layer["slot_state"] = layer["slot_state"].at[0].set(3.5)
    layer["conv"]["slot_state"] = layer["conv"]["slot_state"].at[0].set(-2.0)
    assert kv.spill(r, last_tok=9)
    kv.release(0)
    if verb == "spill-restore":
        into = kv
    else:
        blob = kv.export(r)
        into = make_kv(**two)
        twin = make_req(7, 10, slot=2)
        twin.generated, twin.prefill_done = blob["generated"], 10
        assert into.adopt(blob["spill"], twin)
        r = twin
    r.slot = 2
    seat(into, r)
    assert all((a == 0).all() for a in leaves(into, 2))
    assert into.restore(r) == 9
    matrix, conv = leaves(into, 2)
    assert (matrix == 3.5).all() and (conv == -2.0).all()
    for got, want in zip(leaves(kv, 1), other):
        np.testing.assert_array_equal(got, want)
    into.release(2)
    if into is not kv:
        into.close()
    kv.close()
