"""Blocked/paged KV cache tests (reference blocked_allocator.py +
ragged/kv_cache.py semantics): memory scales with allocated pages, the
allocator recycles pages, and the fused SplitFuse step admits multiple
prefilling requests into one tick."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import PageAllocator
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config

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


def _engine(params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("prefill_chunk", 16)
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   **kw)


class TestAllocator:
    def test_reserves_trash_page(self):
        a = PageAllocator(num_pages=8, page_size=16)
        assert a.free_pages == 7
        pages = a.allocate(0, 16 * 7)
        assert 0 not in pages and len(pages) == 7

    def test_free_recycles(self):
        a = PageAllocator(num_pages=5, page_size=16)
        a.allocate(0, 40)                        # 3 pages
        assert not a.can_allocate(40)
        a.free(0)
        assert a.can_allocate(64)                # all 4 again

    def test_pages_for_rounds_up(self):
        a = PageAllocator(num_pages=4, page_size=16)
        assert a.pages_for(1) == 1
        assert a.pages_for(16) == 1
        assert a.pages_for(17) == 2


class TestPagedMemory:
    def test_cache_bytes_scale_with_pages(self, params):
        """THE blocked-KV contract: device cache bytes are proportional to
        num_pages, independent of max_seqs * max_seq_len worst case."""
        small = _engine(params, num_pages=5, page_size=16)
        big = _engine(params, num_pages=17, page_size=16)
        full = _engine(params)                   # full provisioning
        assert small.cache_bytes() * 17 == big.cache_bytes() * 5
        # shrunk engine holds far less than the worst-case slot-row layout
        assert small.cache_bytes() < full.cache_bytes() / 10

    def test_shrunk_pages_still_serve_correctly(self, params):
        """With only enough pages for ~1.5 sequences, admission
        backpressure serializes — outputs must still match the fully
        provisioned engine."""
        r = np.random.default_rng(5)
        prompts = [r.integers(1, 64, size=(s,), dtype=np.int32)
                   for s in (7, 12, 5)]
        full = _engine(params)
        ref = {i: toks for i, (u, toks) in enumerate(sorted(
            full.generate_all(prompts, max_new_tokens=4).items()))}
        # pages_for(7+4)=1, (12+4)=1, (5+4)=1 at page=16... use page=4:
        tight = _engine(params, page_size=4, num_pages=6)
        outs = tight.generate_all(prompts, max_new_tokens=4)
        got = {i: toks for i, (u, toks) in enumerate(sorted(outs.items()))}
        for i in ref:
            np.testing.assert_array_equal(got[i], ref[i])

    def test_admission_blocks_when_out_of_pages(self, params):
        eng = _engine(params, page_size=4, num_pages=4)  # 3 usable pages
        r = np.random.default_rng(6)
        # each request needs pages_for(6+6)=3 pages -> only one in flight
        u1 = eng.put_request(r.integers(1, 64, 6, dtype=np.int32),
                             max_new_tokens=6)
        u2 = eng.put_request(r.integers(1, 64, 6, dtype=np.int32),
                             max_new_tokens=6)
        eng.step()
        active = [rq.uid for rq in eng.slots if rq is not None]
        assert active == [u1], "second request must wait for pages"
        while eng.has_work():
            eng.step()
        outs = dict(eng.get_outputs())
        assert set(outs) == {u1, u2}

    def test_request_larger_than_pool_rejected(self, params):
        eng = _engine(params, page_size=4, num_pages=4)
        with pytest.raises(ValueError, match="never be scheduled"):
            eng.put_request(np.arange(1, 60, dtype=np.int32),
                            max_new_tokens=60)


class TestFusedStep:
    def test_multiple_requests_prefill_in_one_tick(self, params):
        """SplitFuse: the tick's chunk budget spans several prefilling
        requests (the round-2 engine prefilled exactly one per step)."""
        r = np.random.default_rng(7)
        eng = _engine(params, prefill_chunk=16)
        for s in (5, 6, 4):
            eng.put_request(r.integers(1, 64, s, dtype=np.int32),
                            max_new_tokens=3)
        eng.step()
        done_prefill = [rq.prefill_done for rq in eng.slots
                        if rq is not None]
        assert done_prefill == [5, 6, 4], (
            f"one tick should prefill all three prompts, got {done_prefill}")

    def test_single_compiled_program(self, params):
        """Every tick reuses ONE jitted step — no per-chunk-size
        recompilation (the fused batch is statically shaped)."""
        r = np.random.default_rng(8)
        eng = _engine(params)
        prompts = [r.integers(1, 64, size=(s,), dtype=np.int32)
                   for s in (3, 17, 29, 9, 23)]
        eng.generate_all(prompts, max_new_tokens=4)
        fn = eng.fused_sample_step(False)
        assert fn._cache_size() == 1, (
            f"expected 1 compiled variant, got {fn._cache_size()}")


# -- which attention a program takes (inference/paged/kv_pages.py:attend) ----

# the narrowest model the decode kernel's layout holds for: head_dim 128,
# 8 KV heads, a bf16 pool
WIDE = get_config("tinyllama", vocab_size=64, hidden_size=1024,
                  intermediate_size=64, num_hidden_layers=1,
                  num_attention_heads=8, num_key_value_heads=8,
                  max_position_embeddings=128, dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16, scan_layers=True, remat=False,
                  use_flash_attention=False)


@pytest.fixture(scope="module")
def wide_params():
    model = LlamaForCausalLM(WIDE)
    return jax.jit(model.init)(jax.random.PRNGKey(3),
                               np.zeros((1, 8), np.int32))


def _wide_engine(params, **kw):
    return RaggedInferenceEngineV2(
        LlamaForCausalLM(WIDE), params=params, max_seqs=4, max_seq_len=128,
        prefill_chunk=16, page_size=16, **kw)


class TestDecodeKernelSelection:
    """A decode block says it has one query a slot and takes the decode
    kernel where its layout holds; every other program keeps the call it
    had, whatever its shapes."""

    @pytest.fixture
    def interpreter(self, monkeypatch):
        """The decode kernel through the Pallas interpreter, and a clean
        record of routes."""
        import functools

        from deepspeed_tpu.inference import paged
        from deepspeed_tpu.ops import paged_decode, routes

        monkeypatch.setattr(
            paged.kv_pages, "paged_decode_attention", functools.partial(
                paged_decode.paged_decode_attention, interpret=True))
        monkeypatch.setattr(routes, "_taken", {})
        return lambda: routes.taken().get(paged_decode.KERNEL)

    def _prompts(self):
        r = np.random.default_rng(5)
        return [r.integers(1, 64, size=(n,), dtype=np.int32)
                for n in (5, 21, 9)]

    def test_decode_block_takes_the_kernel_and_gives_the_twins_tokens(
            self, wide_params, interpreter, monkeypatch):
        eng = _wide_engine(wide_params, decode_block_size=4)
        got = eng.generate_all(self._prompts(), max_new_tokens=9)
        assert interpreter() == "pallas-decode-16x8-interpret"
        kernels = eng.serving_stages()["kernels"]
        assert kernels == {"paged_attention": "xla-dense",
                           "paged_attention_prefill": "xla-dense",
                           "paged_attention_decode":
                               "pallas-decode-16x8-interpret"}
        from deepspeed_tpu.inference import paged
        from deepspeed_tpu.ops import paged_decode

        # the same engine on the route a CPU gives it
        monkeypatch.setattr(paged.kv_pages, "paged_decode_attention",
                            paged_decode.paged_decode_attention)
        twin = _wide_engine(wide_params, decode_block_size=4)
        want = twin.generate_all(self._prompts(), max_new_tokens=9)
        assert interpreter() == "pallas-decode-16x8-interpret+xla-dense"
        for a, b in zip(sorted(got), sorted(want)):
            np.testing.assert_array_equal(got[a], want[b])

    def test_fused_ticks_alone_never_ask(self, wide_params, interpreter):
        eng = _wide_engine(wide_params, decode_block_size=1)
        eng.generate_all(self._prompts(), max_new_tokens=3)
        assert interpreter() is None
        assert "paged_attention_decode" not in eng.serving_stages()["kernels"]
