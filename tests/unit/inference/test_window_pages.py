"""The window layers' own pool (``inference/paged/window_pages.py``) under the
engine: a tiny Trinity (window 12, pages of 4, one dense layer and one period)
served through ``RaggedInferenceEngineV2`` with BOTH pools.

- a sequence never holds more than ``pages_bound(window, tick rows, page)``
  window pages, whatever its length, and gives its pages back WHILE it lives;
- a released column is never read: with every free page of the window pool
  poisoned after every tick the logits are the unpoisoned run's to the bit;
- a finish, a cancel and a preemption return both pools to empty, and
  ``audit()`` covers both allocators after every step of a mixed run;
- admission waits where either pool is dry, and a pool that runs dry
  mid-flight preempts as the one pool does;
- the two sizes are two keywords: ``window_pool_bytes`` is required for such
  a model and refused for any other; what cannot know two cursors a sequence
  is refused by name.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.common import HostStageStats  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.inference.v2.ragged_engine import \
    RaggedInferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import mistral  # noqa: E402
from deepspeed_tpu.models import trinity as tr  # noqa: E402

CFG = tr.get_config("tinytrinity", dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False,
                    use_flash_attention=False)
MODEL = tr.TrinityForCausalLM(CFG)
PAGE, CHUNK, WINDOW = 4, 16, 12
FULL_PAGE = programs.paged_kv_page_bytes(MODEL, CFG, PAGE, "none")
OWN_PAGE = programs.paged_kv_page_bytes(MODEL, CFG, PAGE, "none",
                                        window=True)


@pytest.fixture(scope="module")
def params():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(3),
                               np.zeros((1, 8), np.int32))["params"]


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 96, size=n).astype(
        np.int32)


def _engine(params, *, max_seqs=2, full_pages=70, own_pages=40, **kw):
    return RaggedInferenceEngineV2(
        MODEL, params=params, max_seqs=max_seqs, max_seq_len=160,
        prefill_chunk=CHUNK, page_size=PAGE, num_pages=full_pages,
        window_pool_bytes=own_pages * OWN_PAGE, decode_block_size=2, **kw)


_PADDED = 176


@jax.jit
def _forward(params, ids):
    return MODEL.apply({"params": params}, ids)


def _greedy(params, prompt, n):
    """The plain forward pass's greedy continuation (one padded shape: what
    follows a position does not reach it)."""
    toks = list(int(t) for t in prompt)
    for _ in range(n):
        ids = np.zeros((1, _PADDED), np.int32)
        ids[0, :len(toks)] = toks
        toks.append(int(jnp.argmax(_forward(params, ids)[0, len(toks) - 1])))
    return toks


def _empty(eng):
    own = eng.kv.window
    return (eng.allocator.free_pages == eng.num_pages - 1
            and own.allocator.free_pages == own.num_pages - 1
            and (own.page_table == -1).all()
            and (eng.page_table == -1).all())


def test_the_description_has_a_part_for_each_pool():
    kind = paged.describe(CFG)
    assert [(type(p).__name__, p.layers) for p in kind.parts] == [
        ("KVPages", 1), ("WindowPages", 4)]
    assert kind.window.window == WINDOW and kind.kv_layers == 5
    assert paged.window_pool_layers(CFG) == 4
    # one window for the whole model stays on the one pool
    plain = mistral.get_config("tinymistral", sliding_window=8)
    assert paged.window_pool_layers(plain) == 0
    assert paged.describe(plain).window is None
    assert paged.layer_window(plain) == 8
    assert paged.layer_window(CFG, "sliding_attention") == WINDOW
    assert paged.layer_window(CFG, "full_attention") is None
    assert OWN_PAGE == 4 * FULL_PAGE


def test_a_sequence_never_holds_more_than_its_bound(params):
    """130 tokens are ten windows deep: the window pool's peak is the bound
    whatever the length, while the full layer's row grows to the end; every
    dispatch span says what the pools hold."""
    from deepspeed_tpu.telemetry import trace

    eng = _engine(params, max_seqs=1)
    bound = paged.pages_bound(WINDOW, CHUNK + 1, PAGE)
    assert eng.kv.window.bound == bound == 9
    prompt = _tokens(130, 1)
    trace.configure(enabled=True)
    try:
        trace.clear()
        eng.put_request(prompt, max_new_tokens=6)
        most = 0
        while eng.has_work():
            eng.step()
            most = max(most, eng.kv.window.allocator.owned(0))
            eng.audit_kv_sharing()
        spans = [e["args"] for e in trace.snapshot()
                 if e["name"] == "engine.dispatch"]
    finally:
        trace.configure(enabled=False)
        trace.clear()
    (_, out), = eng.get_outputs()
    assert out.tolist() == _greedy(params, prompt, 6)
    assert 0 < most <= bound
    stages = eng.serving_stages()["window_pool"]
    assert stages["pages_peak"] <= bound and stages["layers"] == 4
    assert stages["pages_bound_per_seq"] == bound
    # 136 positions are 34 columns; all but the last window's went back
    assert stages["pages_released"] >= 34 - bound
    assert stages["full_pages_peak"] == 34
    assert all(s["window_layers"] == 4 for s in spans)
    assert sum(s["window_pages_released"] for s in spans) \
        <= stages["pages_released"]
    assert max(s["window_pages_held"] for s in spans) <= bound
    assert max(s["full_pages_held"] for s in spans) == 34
    assert _empty(eng)
    eng.close()


def _poisoned(eng):
    """Every page of the window pool that no sequence owns, the trash page
    aside, filled with a value no key or value could have."""
    own = eng.kv.window
    free = np.asarray(sorted(own.allocator._free), np.int32)

    def fill(path, leaf):
        if not any(getattr(k, "key", None) == paged.WINDOW_POOL
                   for k in path):
            return leaf
        return leaf.at[free].set(1e4)

    eng.kv.cache = jax.tree_util.tree_map_with_path(fill, eng.kv.cache)


def test_a_released_column_is_never_read(params):
    """The same three requests with and without every free window page
    poisoned after every step: the same tokens, which are the plain
    forward pass's."""
    prompts = [_tokens(n, 10 + i) for i, n in enumerate((100, 7, 61))]
    outs = []
    for poison in (False, True):
        eng = _engine(params, pipeline=False)
        uids = [eng.put_request(p, max_new_tokens=5) for p in prompts]
        got = {}
        while eng.has_work():
            eng.step()
            if poison:
                _poisoned(eng)
            got.update(dict(eng.get_outputs()))
        assert eng.kv.window.stats.window_pages_released > 20
        outs.append([got[u].tolist() for u in uids])
        eng.close()
    assert outs[0] == outs[1]
    assert outs[0] == [_greedy(params, p, 5) for p in prompts]


def test_a_mixed_run_audits_both_allocators_and_ends_empty(params):
    """Short and long requests through two slots, pipelined: the audit of
    both allocators holds after every step, every answer is the plain
    forward pass's, and a finish returns both pools to empty."""
    eng = _engine(params)
    prompts = [_tokens(n, 30 + i) for i, n in enumerate((5, 90, 40, 120, 3))]
    uids = [eng.put_request(p, max_new_tokens=6) for p in prompts]
    got = {}
    while eng.has_work():
        eng.step()
        counts = eng.audit_kv_sharing()
        assert counts["window"]["shared"] == 0
        for s in range(2):
            assert eng.kv.window.allocator.owned(s) <= eng.kv.window.bound
        got.update(dict(eng.get_outputs()))
    assert [got[u].tolist() for u in uids] == [
        _greedy(params, p, 6) for p in prompts]
    assert _empty(eng)
    assert eng.evictions == 0
    eng.close()


@pytest.mark.parametrize("after", [2, 9], ids=["in-prefill", "in-decode"])
def test_a_cancel_frees_both_pools(params, after):
    eng = _engine(params)
    uid = eng.put_request(_tokens(100, 4), max_new_tokens=8)
    for _ in range(after):
        eng.step()
    assert not _empty(eng)
    assert eng.cancel(uid) in ("prefill", "decode")
    assert _empty(eng)
    eng.audit_kv_sharing()
    eng.close()


@pytest.mark.parametrize("dry", ["window", "full"])
def test_admission_waits_where_either_pool_is_dry(params, dry):
    """Two requests of 60 tokens, two free slots, and ONE pool too small
    for both: the second is admitted only when the first has given its
    pages back, and both answers are right."""
    sizes = (dict(full_pages=70, own_pages=13) if dry == "window"
             else dict(full_pages=22, own_pages=40))
    eng = _engine(params, **sizes)
    prompts = [_tokens(60, 50), _tokens(60, 51)]
    uids = [eng.put_request(p, max_new_tokens=4) for p in prompts]
    together, got = 0, {}
    while eng.has_work():
        eng.step()
        together = max(together, sum(r is not None for r in eng.slots))
        eng.audit_kv_sharing()
        got.update(dict(eng.get_outputs()))
    assert together == 1 and eng.evictions == 0
    assert [got[u].tolist() for u in uids] == [
        _greedy(params, p, 4) for p in prompts]
    assert _empty(eng)
    eng.close()


def test_a_window_pool_that_runs_dry_preempts(params):
    """Three short prompts take the nine window pages at admission (three
    each), and each wants a fourth at its thirteenth token: every live
    sequence is stalled, the youngest is preempted and re-prefilled from its
    own tokens behind the others, and all three answers are right.  (Short
    of that a dry window pool degrades a decode block to ticks, which want
    a page less.)"""
    eng = _engine(params, max_seqs=3, own_pages=10)
    prompts = [_tokens(7, 60 + i) for i in range(3)]
    uids = [eng.put_request(p, max_new_tokens=30) for p in prompts]
    got = {}
    while eng.has_work():
        eng.step()
        eng.audit_kv_sharing()
        got.update(dict(eng.get_outputs()))
    assert eng.evictions >= 1
    assert [got[u].tolist() for u in uids] == [
        _greedy(params, p, 30) for p in prompts]
    assert _empty(eng)
    eng.close()


def test_the_window_pool_has_no_default_size(params):
    sizes = dict(max_seqs=1, max_seq_len=64, prefill_chunk=8, page_size=4,
                 num_pages=20)
    with pytest.raises(ValueError, match="pool of their own, which has no "
                       "default size"):
        RaggedInferenceEngineV2(MODEL, params=params, **sizes)
    plain = mistral.MistralForCausalLM(mistral.get_config(
        "tinymistral", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=False, remat=False, use_flash_attention=False))
    with pytest.raises(ValueError, match="behind one table"):
        RaggedInferenceEngineV2(plain, window_pool_bytes=1 << 20, **sizes)
    # a request that could never be scheduled is refused at the door
    eng = RaggedInferenceEngineV2(MODEL, params=params,
                                  window_pool_bytes=4 * OWN_PAGE, **sizes)
    with pytest.raises(ValueError, match="window layers' pool"):
        eng.put_request(_tokens(40, 1), max_new_tokens=4)
    eng.close()


ASK = {"prefix_cache": dict(prefix_cache={"enabled": True}),
       "tiering": dict(kv_tiering={"host_pages": 32}),
       "quantized": dict(kv_cache_dtype="int8"),
       "tensor_parallel": dict(tensor_parallel=2)}
_IS = ("TrinityForCausalLM keeps the keys and values of 4 of its 5 layers in "
       "a pool of their own, whose pages a sequence gives back as its window "
       "of 12 leaves them behind")
NAMED = {"prefix_cache": "prefix_cache: ", "tiering": "kv_tiering: ",
         "quantized": "kv_cache_dtype='int8': ",
         "tensor_parallel": "tensor-parallel serving: "}


@pytest.mark.parametrize("feature", sorted(ASK))
def test_what_knows_one_cursor_a_sequence_is_refused_by_name(feature):
    import deepspeed_tpu.comm as dist

    kw = dict(ASK[feature])
    if kw.pop("tensor_parallel", None):
        kw["topology"] = dist.initialize_mesh(tp=2)
    try:
        with pytest.raises(NotImplementedError) as refused:
            RaggedInferenceEngineV2(
                MODEL, max_seqs=2, max_seq_len=64, prefill_chunk=8,
                page_size=16, num_pages=10, window_pool_bytes=1 << 20, **kw)
    finally:
        dist.set_topology(None)
    _, refuses = paged.describe(CFG).refusals("TrinityForCausalLM")[0]
    assert str(refused.value) == NAMED[feature] + _IS + refuses[feature]
    assert set(refuses) == set(ASK)


def test_the_host_pool_alone():
    """``WindowPool`` without an engine: growth by column, release behind
    the window, the bound, conservation."""
    st = HostStageStats()
    pool = paged.WindowPool(40, PAGE, 2, 48, WINDOW, CHUNK + 2, st)
    assert pool.bound == paged.pages_bound(WINDOW, CHUNK + 2, PAGE) == 9
    assert pool.attach(0, 100) and pool.allocator.owned(0) == 9
    assert pool.attach(1, 6) and pool.allocator.owned(1) == 2
    at = 0
    while at < 150:
        assert pool.ensure(0, at + 18, at)
        assert pool.allocator.owned(0) <= pool.bound
        low, top = pool.span(0)
        # nothing a row of this tick can reach is gone
        assert low * PAGE <= max(at - WINDOW + 1, 0)
        assert top * PAGE >= at + 18
        assert (pool.page_table[0, low:top] > 0).all()
        assert (pool.page_table[0, :low] == -1).all()
        at += 18
    assert st.window_pages_released == pool.span(0)[0]
    assert st.window_pages_held == pool.allocator.owned(0) + 2
    pool.audit([object(), object()])
    pool.release(0)
    pool.release(1)
    assert pool.allocator.free_pages == 39 and st.window_pages_held == 0
