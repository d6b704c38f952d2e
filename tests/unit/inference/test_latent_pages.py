"""A latent page pool under the one ``KVState`` (the latent attention of
``models/glm4_moe_lite.py``): ONE row a token a layer, ``[c | k_r]`` padded
to whole lane tiles, and nothing per head; served by
``RaggedInferenceEngineV2`` through the same fused tick and decode block as
every other model; moved by the page verbs as any pool leaf is (spill and
restore, a handoff, the prefix cache's copy-on-write); what reads a pool as
heads of keys and values is refused by name; and the spans and
``serving_stages()`` say what ran.

Float32, the tiny preset: the engine's greedy tokens are held to the plain
reference's logits within ``test_glm4_moe_lite.py``'s ``TOL`` (2e-4, its
reason there).
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

from benchmark.reference import glm4_moe_lite as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.inference.v2.ragged_engine import \
    RaggedInferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import glm4_moe_lite as glm  # noqa: E402
from deepspeed_tpu.models import llama  # noqa: E402
from deepspeed_tpu.telemetry import trace  # noqa: E402
from tests.unit.models.test_glm4_moe_lite import CFG as REF_CFG  # noqa: E402
from tests.unit.models.test_glm4_moe_lite import PLAIN, TOL  # noqa: E402

PAGE, LAYERS = 8, 3
CFG = glm.get_config("tinyglm", **PLAIN)
MODEL = glm.Glm4MoeLiteForCausalLM(CFG)
# the tiny preset's row: 32 + 8 values, stored padded to one lane tile
W = 128
PROMPT = np.arange(1, 20, dtype=np.int32)           # 19 tokens: 3 pages


@pytest.fixture(scope="module")
def params():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))["params"]


def make_engine(params, **kw):
    return RaggedInferenceEngineV2(
        MODEL, params=params, **{**dict(
            pipeline=False, max_seqs=2, max_seq_len=64, prefill_chunk=8,
            page_size=PAGE, num_pages=14, decode_block_size=2), **kw})


def _run(eng):
    while eng.has_work():
        eng.step()
    return {uid: list(toks) for uid, toks in eng.get_outputs()}


def _is_the_references(params, prompt, out):
    gaps = ref.generated_gaps(params, REF_CFG, list(prompt),
                              out[len(prompt):])
    return gaps.max() < TOL


def test_a_token_costs_the_pool_one_row_a_layer_and_nothing_per_head(params):
    """``W * itemsize`` bytes a token a layer, from the leaf's own shape;
    at the published widths 640 x 2 (576 padded to whole lane tiles), where
    the same attention held per head would cost 20 x 512 x 2; and a K/V
    model's page costs what it did."""
    assert paged.latent_width(CFG) == W
    page_bytes = programs.paged_kv_page_bytes(MODEL, CFG, PAGE, "none")
    assert page_bytes == PAGE * LAYERS * W * 4
    eng = make_engine(params, num_pages=None,
                      kv_pool_bytes=10 * page_bytes + page_bytes // 2)
    assert eng.num_pages == 10
    pools, states = paged.split_cache(eng.kv.cache)
    assert [l.shape for l in pools] == [(10, PAGE, W)] * LAYERS and not states
    assert eng.cache_bytes() == 10 * page_bytes
    assert eng.serving_stages()["latent"] == {
        "width": W, "layers": LAYERS, "bytes_per_token": LAYERS * W * 4,
        "bytes_per_token_layer": W * 4, "pages_peak": 0}
    big = glm.get_config("glm-4.7-flash", num_hidden_layers=8,
                         dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert paged.latent_width(big) == 640
    assert programs.paged_kv_page_bytes(
        glm.Glm4MoeLiteForCausalLM(big), big, 64, "none") == 64 * 8 * 1280
    # per-head keys and values, unchanged: 2 x Hkv x Dh a token a layer
    tiny = llama.get_config("tinyllama", dtype=jnp.float32)
    assert paged.latent_width(tiny) == 0
    assert programs.paged_kv_page_bytes(
        llama.LlamaForCausalLM(tiny), tiny, PAGE, "none") == (
            PAGE * 2 * (2 * 2 * 16) * 4)


@pytest.mark.parametrize("n_prompt,pipeline", [(43, False), (43, True),
                                               (12, False)],
                         ids=["crosses-chunks-and-pages",
                              "dispatched-one-ahead", "twelve-tokens"])
def test_the_engine_serves_the_references_tokens(params, n_prompt, pipeline):
    """Prefill in chunks of 8 over pages of 8, then decode blocks, through
    the paged latent cache: a 43-token prompt crosses six chunks and six
    pages, a 12-token prompt is where a wrong mask shows."""
    eng = make_engine(params, pipeline=pipeline)
    prompt = np.random.default_rng(n_prompt).integers(
        1, 96, size=n_prompt).astype(np.int32)
    uid = eng.put_request(prompt, max_new_tokens=14)
    out = _run(eng)[uid]
    assert len(out) == n_prompt + 14
    assert _is_the_references(params, prompt, out)
    st = eng.serving_stages()
    # the pages its tokens fill, and at most the one a block grew ahead
    need = -(-(n_prompt + 14 - 1) // PAGE)
    assert need <= st["latent"]["pages_peak"] <= need + 1
    # its own two routes (the twin, on the CPU) beside the expert layer's,
    # and none of the per-head kernels'
    assert {k: v for k, v in st["kernels"].items() if "attention" in k} == {
        "latent_attention_prefill": "xla-dense-latent",
        "latent_attention_decode": "xla-dense-latent"}


def test_four_requests_share_two_slots(params):
    prompts = [np.arange(1 + 7 * i, 1 + 7 * i + n, dtype=np.int32)
               for i, n in enumerate((19, 5, 26, 11))]
    eng = make_engine(params, pipeline=True, decode_block_size=4,
                      num_pages=20)
    uids = [eng.put_request(p, max_new_tokens=9) for p in prompts]
    out = _run(eng)
    assert all(_is_the_references(params, p, out[u])
               for p, u in zip(prompts, uids))
    eng.audit_kv_sharing()


def test_a_spilled_and_restored_sequence_reads_its_latent_rows(params):
    """Spill a sequence mid-decode, scribble over the pool, restore: the
    tokens of a run that never spilled."""
    want = make_engine(params)
    want.put_request(PROMPT, max_new_tokens=12)
    want = _run(want)[0]
    assert _is_the_references(params, PROMPT, want)
    eng = make_engine(params, kv_tiering={"host_pages": 32})
    eng.put_request(PROMPT, max_new_tokens=12)
    for _ in range(4):
        eng.step()
    (r,) = [s for s in eng.slots if s is not None]
    assert 0 < len(r.generated) < 12
    assert eng._spill(r) and eng.spills == 1
    eng.kv.cache = jax.tree_util.tree_map(
        lambda l: jnp.full_like(l, 3.0), eng.kv.cache)
    assert eng.restores == 0 and _run(eng)[0] == want and eng.restores == 1


def test_a_handoff_carries_the_latent_rows_to_another_engine(params):
    want = make_engine(params)
    want.put_request(PROMPT, max_new_tokens=10)
    want = _run(want)[0]
    a = make_engine(params, kv_tiering={"host_pages": 32})
    a.put_request(PROMPT, max_new_tokens=10)
    for _ in range(4):
        a.step()
    (r,) = [s for s in a.slots if s is not None]
    assert a._spill(r)
    (blob,) = a.export_parked()
    b = make_engine(params, kv_tiering={"host_pages": 32})
    (uid,) = b.import_parked([blob])
    assert _run(b)[uid] == want


def test_copy_on_write_copies_a_latent_page(params):
    """A second request with the same 16-token prompt (two full pages): the
    prefix cache shares the first page and copies the second before the
    re-prefill of its last token writes there; the copy holds the rows of
    every layer, so the second request's tokens are the first's, and the
    reference's."""
    eng = make_engine(params, prefix_cache=True)
    prompt = PROMPT[:16]
    eng.put_request(prompt, max_new_tokens=6)
    first = _run(eng)[0]
    assert _is_the_references(params, prompt, first)
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
        assert np.abs(leaf[mine[1]]).max() > 0
        np.testing.assert_array_equal(leaf[mine[1]], leaf[shared[0]])
    assert _run(eng)[1] == first
    eng.audit_kv_sharing()


@pytest.mark.parametrize("kw,named", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype")], ids=["quantized-pool"])
def test_what_reads_a_pool_as_heads_is_refused_by_name(params, kw, named):
    with pytest.raises(NotImplementedError, match=named):
        make_engine(params, **kw)


def test_tensor_parallel_serving_is_refused_by_name(params):
    import deepspeed_tpu.comm as dist

    topo = dist.initialize_mesh(tp=2)
    try:
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            make_engine(params, topology=topo)
    finally:
        dist.set_topology(None)


def test_the_spans_and_the_stages_say_what_ran(params):
    """``engine.dispatch`` carries what a cached token costs the pool and
    the pages in use, ``engine.moe`` the counts of the two expert layers
    with their ``bias_swaps``."""
    trace.configure(enabled=True)
    try:
        trace.clear()
        eng = make_engine(params)
        eng.put_request(PROMPT, max_new_tokens=8)
        _run(eng)
        events = trace.snapshot()
    finally:
        trace.configure(enabled=False)
        trace.clear()
    dispatches = [e["args"] for e in events if e["name"] == "engine.dispatch"]
    assert {d["program"] for d in dispatches} == {"fused", "decode_block"}
    assert all(d["latent_bytes_per_token"] == LAYERS * W * 4
               for d in dispatches)
    assert max(d["pages_in_use"] for d in dispatches) == \
        eng.serving_stages()["latent"]["pages_peak"] >= 4
    moe = [e["args"] for e in events if e["name"] == "engine.moe"]
    # 2 expert layers x 2 experts a token x (19 prompt + 7 fed-back tokens)
    assert sum(m["moe_rows"] for m in moe) == 2 * 2 * (19 + 7)
    assert all(0 <= m["bias_swaps"] <= m["moe_rows"] // 2 for m in moe)
    # a K/V model's spans carry no such count
    assert "latent" not in RaggedInferenceEngineV2(
        llama.LlamaForCausalLM(llama.get_config(
            "tinyllama", dtype=jnp.float32, scan_layers=False)),
        max_seqs=2, max_seq_len=32, prefill_chunk=8,
        page_size=PAGE).serving_stages()
