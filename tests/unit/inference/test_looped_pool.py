"""The paged pool of a model whose layers run several times a token
(``models/ouro.py``): a page holds a K/V slot for every pass, so its bytes,
the pages a byte budget buys, and everything that copies whole pages
(prefix copy-on-write, spill, restore) follow without a change of their
own; the compiled programs hold the block stack once, inside the pass
loop; and what reads a layer's pool as one slot is refused by name."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.models import ouro

U, LAYERS, PAGE = 3, 2, 8
CFG = ouro.get_config("tinyouro", dtype=jnp.float32, param_dtype=jnp.float32,
                      scan_layers=False, remat=False,
                      use_flash_attention=False)
MODEL = ouro.OuroForCausalLM(CFG)
# one page of one layer: U slots x 8 tokens x (K and V of 4 heads) x 8 x 4 B
LAYER_PAGE_BYTES = U * PAGE * 2 * 4 * 8 * 4
PROMPT = np.arange(1, 20, dtype=np.int32)           # 19 tokens: 3 pages


@pytest.fixture(scope="module")
def params():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))["params"]


def make_engine(params, **kw):
    return RaggedInferenceEngineV2(
        MODEL, params=params, **{**dict(
            pipeline=False, max_seqs=2, max_seq_len=64, prefill_chunk=16,
            page_size=PAGE, num_pages=12, decode_block_size=2), **kw})


def _run(eng):
    while eng.has_work():
        eng.step()
    return {uid: list(toks) for uid, toks in eng.get_outputs()}


def test_a_page_costs_a_slot_for_every_pass_and_layer(params):
    assert programs.kv_slots_per_layer(CFG) == U
    page_bytes = programs.paged_kv_page_bytes(MODEL, CFG, PAGE, "none")
    assert page_bytes == LAYERS * LAYER_PAGE_BYTES
    eng = make_engine(params, num_pages=None,
                      kv_pool_bytes=10 * page_bytes + page_bytes // 2)
    assert eng.num_pages == 10
    leaves = jax.tree_util.tree_leaves(eng.kv.cache)
    assert [l.shape for l in leaves] == [(10, U, PAGE, 8, 8)] * LAYERS
    assert eng.cache_bytes() == 10 * page_bytes
    # the published size, by shapes alone: 96 MiB a page of 64 tokens,
    # 85 pages in 8 GiB
    big = ouro.get_config("ouro-2.6b", dtype=jnp.bfloat16,
                          param_dtype=jnp.bfloat16, scan_layers=False)
    big_page = programs.paged_kv_page_bytes(
        ouro.OuroForCausalLM(big), big, 64, "none")
    assert big_page == 4 * 48 * 64 * 8192 == 96 * 2 ** 20
    assert 8 * 2 ** 30 // big_page == 85


def test_a_spilled_and_restored_sequence_carries_every_slot(params):
    """Spill a sequence mid-decode, let another tenant scribble over the
    pool, restore: the same tokens as a run that never spilled, which
    every pass's keys and values of every page must have come back for."""
    want = make_engine(params)
    want.put_request(PROMPT, max_new_tokens=12)
    want = _run(want)[0]

    eng = make_engine(params, kv_tiering={"host_pages": 32})
    eng.put_request(PROMPT, max_new_tokens=12)
    for _ in range(3):
        eng.step()
    (r,) = [s for s in eng.slots if s is not None]
    assert 0 < len(r.generated) < 12
    pages = [int(p) for p in eng.page_table[r.slot] if p >= 0]
    before = [np.asarray(l)[pages] for l in
              jax.tree_util.tree_leaves(eng.kv.cache)]
    # every slot of the first page holds keys: no pass left zeros
    assert all((np.abs(b[0]).reshape(U, -1).max(axis=1) > 0).all()
               for b in before)
    assert eng._spill(r) and eng.spills == 1
    eng.kv.cache = jax.tree_util.tree_map(jnp.zeros_like, eng.kv.cache)
    got = _run(eng)[0]
    assert eng.restores == 1 and got == want


def test_copy_on_write_copies_every_slot_of_the_page(params):
    """A second request with the same 16-token prompt (two full pages):
    the prefix cache shares the first page and copies the second before
    the re-prefill of its last token writes there; the copy holds all
    ``U`` slots, so the second request's tokens are the first's."""
    eng = make_engine(params, prefix_cache=True, max_seqs=2)
    prompt = PROMPT[:16]
    eng.put_request(prompt, max_new_tokens=6)
    first = _run(eng)[0]
    eng.put_request(prompt, max_new_tokens=6)
    eng._admit()
    assert eng.host_stats.prefix_cow_copies == 1
    (r,) = [s for s in eng.slots if s is not None]
    assert r.pc_cached == 15
    mine = [int(p) for p in eng.page_table[r.slot] if p >= 0]
    shared = [p for p in range(1, eng.num_pages)
              if eng.allocator.refcount(p) > 0 and p not in mine]
    assert len(shared) == 1, "the first request's second page, in the index"
    for leaf in jax.tree_util.tree_leaves(eng.kv.cache):
        leaf = np.asarray(leaf)
        # the private copy: all U slots hold the 8 rows of the original
        assert (np.abs(leaf[mine[1]]).reshape(U, -1).max(axis=1) > 0).all()
        np.testing.assert_array_equal(leaf[mine[1]], leaf[shared[0]])
    assert _run(eng)[1] == first


def _decode_text(model, params):
    eng = RaggedInferenceEngineV2(model, params=params, max_seqs=4,
                                  max_seq_len=64, prefill_chunk=8,
                                  page_size=PAGE, decode_block_size=4)
    z = np.zeros((4,), np.int32)
    f32, flag = np.zeros((4,), np.float32), np.zeros((4,), bool)
    return eng.decode_block(False).lower(
        eng.params, eng.kv.cache, z, z, flag, z,
        np.zeros((4, eng.pages_per_seq), np.int32), z, flag, f32, z, f32,
        np.zeros((4,), np.uint32), jax.random.PRNGKey(0)).as_text()


def test_the_decode_program_holds_the_block_stack_once(params):
    """One K/V scatter and one softmax a LAYER in the lowered text, not
    one a pass a layer: the pass loop is a loop in the program (a
    ``while`` inside the ticks' ``while``), as deep for 3 passes as for
    1."""
    count = lambda text, op: len(re.findall(rf'stablehlo\.{op}"?[ (]', text))
    looped = _decode_text(MODEL, params)
    once_cfg = ouro.get_config(
        "tinyouro", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=False, remat=False, use_flash_attention=False,
        total_ut_steps=1)
    once = _decode_text(ouro.OuroForCausalLM(once_cfg), params)
    assert count(looped, "scatter") == count(once, "scatter") == LAYERS
    # 7 matmuls and 2 attention products a layer, the head, the gate; the
    # looped program has the gate's distribution beside them, not 3 x 19
    assert 19 <= count(once, "dot_general") <= count(
        looped, "dot_general") <= count(once, "dot_general") + 2
    assert count(looped, "while") == count(once, "while") + 1


@pytest.mark.parametrize("kw,named", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype")], ids=["quantized-pool"])
def test_what_knows_one_slot_a_layer_is_refused_by_name(params, kw, named):
    with pytest.raises(NotImplementedError, match=named):
        make_engine(params, **kw)
