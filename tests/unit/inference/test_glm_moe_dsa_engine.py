"""A tiny GLM-5 (``index_topk`` 24, pages of 4, a dense layer and two expert
layers that hold 4 of their router's 8 experts) through
``RaggedInferenceEngineV2`` over its two pool leaves: sequences on both
sides of ``index_topk`` in ONE queue, the short ones crossing it WHILE
DECODING, so a decode block's rows change from "every key" to "the chosen"
between two ticks.

- every sequence's tokens are the plain forward pass's greedy continuation;
- the ``engine.dispatch`` spans carry both leaves' bytes a token and the
  visible and chosen (query, key) pairs of prompt rows and of decode rows;
  ``engine.moe`` carries the rows the router made and the rows held;
- pages are shared out and given back: the allocator audits after every
  step and the pool ends empty; a second queue on the same engine, over the
  pages the first gave back, answers the same.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import paged
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.models import glm_moe_dsa as glm5

CFG = glm5.get_config("tinyglm5", dtype=jnp.float32, param_dtype=jnp.float32,
                      remat=False, use_flash_attention=False)
MODEL = glm5.GlmMoeDsaForCausalLM(CFG)
PAGE, CHUNK, BLOCK, TOPK = 4, 16, 4, 24
# (prompt tokens, new tokens): two start under index_topk and cross it while
# decoding, one starts past it, one never reaches it
REQUESTS = ((5, 26), (17, 14), (40, 10), (3, 6))
_PADDED = 64


@pytest.fixture(scope="module")
def params():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(3),
                               np.zeros((1, 8), np.int32))["params"]


@jax.jit
def _forward(params, ids):
    return MODEL.apply({"params": params}, ids)


def _greedy(params, prompt, n):
    toks = [int(t) for t in prompt]
    for _ in range(n):
        ids = np.zeros((1, _PADDED), np.int32)
        ids[0, :len(toks)] = toks
        toks.append(int(jnp.argmax(_forward(params, ids)[0, len(toks) - 1])))
    return toks


def test_the_description_is_one_part_of_two_leaves():
    kind = paged.describe(CFG)
    assert [(type(p).__name__, p.layers) for p in kind.parts] == [
        ("LatentSelect", 3)]
    assert kind.latent_width == 128 and kind.kv_layers == 3
    assert kind.parts[0].indexer.topk == TOPK
    # a latent row of 32 + 8 stored as 128 and an index key of 16, float32
    assert programs.paged_kv_page_bytes(MODEL, CFG, PAGE, "none") == (
        PAGE * 3 * (128 + 16) * 4)


@pytest.mark.parametrize("feature,named", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    (dict(tensor_parallel=2), "tensor-parallel serving")])
def test_what_cannot_carry_the_two_leaves_is_refused_by_name(params, feature,
                                                             named):
    import deepspeed_tpu.comm as dist

    feature = dict(feature)
    if feature.pop("tensor_parallel", None):
        feature["topology"] = dist.initialize_mesh(tp=2)
    try:
        with pytest.raises(NotImplementedError) as e:
            RaggedInferenceEngineV2(MODEL, params=params, max_seqs=2,
                                    max_seq_len=64, prefill_chunk=CHUNK,
                                    page_size=PAGE, num_pages=20, **feature)
    finally:
        dist.set_topology(None)
    assert named in str(e.value) and "one latent row of 128" in str(e.value)
    assert "an indexer of 2 heads chooses" in str(e.value)


def test_a_mixed_queue_crosses_index_topk_while_decoding(params):
    from deepspeed_tpu.telemetry import trace

    eng = RaggedInferenceEngineV2(
        MODEL, params=params, max_seqs=4, max_seq_len=64,
        prefill_chunk=CHUNK, page_size=PAGE, num_pages=60,
        decode_block_size=BLOCK)
    prompts = [np.random.default_rng(i).integers(1, 96, size=n).astype(
        np.int32) for i, (n, _) in enumerate(REQUESTS)]

    def serve():
        uids = [eng.put_request(p, max_new_tokens=new)
                for p, (_, new) in zip(prompts, REQUESTS)]
        while eng.has_work():
            eng.step()
            eng.audit_kv_sharing()
        got = dict(eng.get_outputs())
        return [got[u].tolist() for u in uids]

    trace.configure(enabled=True)
    try:
        trace.clear()
        first = serve()
        events = trace.snapshot()
    finally:
        trace.configure(enabled=False)
        trace.clear()
    want = [_greedy(params, p, new) for p, (_, new) in zip(prompts, REQUESTS)]
    assert first == want
    spans = [e["args"] for e in events if e["name"] == "engine.dispatch"]
    blocks = [a for a in spans if a.get("program") == "decode_block"]
    ticks = [a for a in spans if a.get("program") == "fused"]
    assert blocks and ticks
    assert all(a["latent_bytes_per_token"] == 3 * 128 * 4
               and a["index_bytes_per_token"] == 3 * 16 * 4 for a in spans)
    keys = ("index_rows", "index_visible_tokens", "index_prefill_tokens",
            "index_decode_tokens", "attend_prefill_tokens",
            "attend_decode_tokens", "sel_rows", "sel_key_tokens",
            "sel_visible_tokens")
    assert all(k in a for a in spans for k in keys)
    total = {k: sum(a[k] for a in spans) for k in keys}
    # every query of every request, prompt rows and decode rows alike (a
    # decode block counts its rows by whole blocks: a sequence that ends
    # inside one is counted to the block's end)
    served = sum(n + new - 1 for n, new in REQUESTS)
    assert served <= total["index_rows"] <= served + len(REQUESTS) * BLOCK
    visible = sum(t + 1 for n, new in REQUESTS for t in range(n + new - 1))
    assert visible <= total["index_visible_tokens"] == (
        total["index_prefill_tokens"] + total["index_decode_tokens"])
    chosen = (total["attend_prefill_tokens"] + total["attend_decode_tokens"])
    assert sum(min(t + 1, TOPK) for n, new in REQUESTS
               for t in range(n + new - 1)) <= chosen < visible
    # a prompt's rows are a fused tick's: the 40-token prompt's alone see
    # 820 keys and attend 576 + 16 * 24 of them
    assert total["index_prefill_tokens"] >= 820
    assert total["attend_prefill_tokens"] >= 300 + 16 * TOPK
    # decode blocks carried rows past index_topk: the choice ran there
    assert sum(a["sel_rows"] for a in blocks) > 0
    assert all(a["attend_prefill_tokens"] == 0 for a in blocks)
    moe = [e["args"] for e in events if e["name"] == "engine.moe"]
    assert moe and all(0 < a["moe_rows"] <= a["moe_rows_routed"]
                       for a in moe)
    assert eng.allocator.free_pages == eng.num_pages - 1
    stages = eng.serving_stages()
    block = stages["latent_select"]
    assert block["layers"] == 3 and block["topk"] == TOPK
    assert block["index_rows"] == total["index_rows"]
    assert block["bytes_per_token_layer"] == (128 + 16) * 4
    assert {"latent_select_prefill", "latent_select_decode",
            "token_select_index", "token_select_threshold",
            "token_select_row_index",
            "moe_grouped_matmul"} <= set(stages["kernels"])
    assert stages["kernels"]["latent_select_decode"] == (
        f"xla-latent-token-gather-{TOPK}")
    # the pages the first queue gave back, shared out again
    assert serve() == want
    assert eng.allocator.free_pages == eng.num_pages - 1
    eng.close()
