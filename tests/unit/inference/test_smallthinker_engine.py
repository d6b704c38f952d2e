"""A tiny SmallThinker (window 12, pages of 4, two periods [full, window,
window, window], four KV heads, the router before attention, ReGLU experts)
through ``RaggedInferenceEngineV2`` with BOTH pools: sequences on both sides
of the window in ONE queue, the short ones crossing it WHILE DECODING, so the
window pool's pages go back at decode-block plans and a decode block reads a
table with holes in it.

- every sequence's tokens are the plain forward pass's greedy continuation;
- the ``engine.dispatch`` spans of this family carry the ``window_*`` counts,
  the release under ``program: decode_block`` too; ``engine.moe`` carries
  ``moe_rows`` and ``moe_experts_hit`` of eight expert layers;
- both allocators audit after every step and both pools end empty.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import paged
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.models import smallthinker as st

CFG = st.get_config("tinysmallthinker", dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False,
                    use_flash_attention=False)
MODEL = st.SmallThinkerForCausalLM(CFG)
PAGE, CHUNK, WINDOW, BLOCK = 4, 16, 12, 4
OWN_PAGE = programs.paged_kv_page_bytes(MODEL, CFG, PAGE, "none",
                                        window=True)
# (prompt tokens, new tokens): two start inside the window and cross it while
# decoding, one starts past it, one never reaches it
REQUESTS = ((5, 22), (9, 18), (40, 10), (3, 6))
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


def test_the_description_has_a_part_for_each_pool():
    kind = paged.describe(CFG)
    assert [(type(p).__name__, p.layers) for p in kind.parts] == [
        ("KVPages", 2), ("WindowPages", 6)]
    assert kind.window.window == WINDOW and kind.kv_layers == 8
    assert OWN_PAGE == 3 * programs.paged_kv_page_bytes(MODEL, CFG, PAGE,
                                                        "none")


def test_a_mixed_queue_crosses_the_window_while_decoding(params):
    from deepspeed_tpu.telemetry import trace

    eng = RaggedInferenceEngineV2(
        MODEL, params=params, max_seqs=4, max_seq_len=64,
        prefill_chunk=CHUNK, page_size=PAGE, num_pages=70,
        window_pool_bytes=40 * OWN_PAGE, decode_block_size=BLOCK)
    prompts = [np.random.default_rng(i).integers(1, 96, size=n).astype(
        np.int32) for i, (n, _) in enumerate(REQUESTS)]
    trace.configure(enabled=True)
    try:
        trace.clear()
        uids = [eng.put_request(p, max_new_tokens=new)
                for p, (_, new) in zip(prompts, REQUESTS)]
        while eng.has_work():
            eng.step()
            eng.audit_kv_sharing()
        events = trace.snapshot()
    finally:
        trace.configure(enabled=False)
        trace.clear()
    got = dict(eng.get_outputs())
    for uid, prompt, (_, new) in zip(uids, prompts, REQUESTS):
        assert got[uid].tolist() == _greedy(params, prompt, new)
    spans = [e["args"] for e in events if e["name"] == "engine.dispatch"]
    blocks = [a for a in spans if a.get("program") == "decode_block"]
    assert blocks and all(a["window_layers"] == 6 for a in spans)
    assert all(k in a for a in spans for k in (
        "window_pages_held", "window_pages_released", "full_pages_held",
        "window_decode_key_tokens"))
    # the release happens where the crossing does: at decode-block plans
    assert sum(a["window_pages_released"] for a in blocks) >= 4
    moe = [e["args"] for e in events if e["name"] == "engine.moe"]
    assert moe and all(0 < a["moe_experts_hit"] <= 8 * 8 * a.get("ticks", 1)
                       and a["moe_rows"] > 0 for a in moe)
    own = eng.kv.window
    assert eng.allocator.free_pages == eng.num_pages - 1
    assert own.allocator.free_pages == own.num_pages - 1
    assert (own.page_table == -1).all()
    stages = eng.serving_stages()
    assert stages["window_pool"]["layers"] == 6
    assert stages["window_pool"]["pages_released"] >= 8
    assert {"paged_attention_prefill", "paged_attention_decode",
            "moe_grouped_matmul"} <= set(stages["kernels"])
    eng.close()
