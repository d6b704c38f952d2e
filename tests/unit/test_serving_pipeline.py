"""Serving host-path pipeline tests (the async serving tentpole).

The load-bearing contracts:

- **Bit-identical parity**: pipelined serving (``pipeline=True``,
  deferred harvest, device-resident metadata) and the unpipelined host
  loop (``pipeline=False``) produce the same ``(uid, tokens)`` outputs
  on mixed prompt-length workloads — greedy AND seeded sampling,
  including mid-run admissions and eviction backpressure.  The pipeline
  forces a harvest at every point where the unpipelined engine could
  have reaped/admitted/evicted, so the dispatch sequence (programs,
  metadata, rng splits) is identical by construction.
- **Steady state is sync-free**: across a decode window the engine
  performs no per-block metadata uploads and no per-block blocking
  ``device_get`` — the ``host_stats`` counters assert it.
- **No recompiles**: after warmup, a full mixed ragged run triggers
  zero new XLA compilations (JAX's compilation-cache miss counter) —
  per-tick shapes stay stable across the buffer-reuse path.
- **Loud submit-time rejection**: a request that could never be
  scheduled raises ``ValueError`` from ``put_request`` (and from
  ``_admit``, defense in depth) instead of deadlocking the queue.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.config import load_inference_config
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2, Request
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config

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


def make(params, pipeline, **kw):
    kw.setdefault("max_seqs", 3)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("harvest_interval", 3)
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=pipeline,
                                   rng=jax.random.PRNGKey(11), **kw)


def _prompts(sizes, seed=0):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


def _serve(params, pipeline, sizes, mid=None, eng_kw=None, **req_kw):
    """Run a workload to completion; ``mid`` maps a step index to prompt
    arrays admitted mid-run (same order in both modes -> same uids).
    Returns ({uid: tokens}, engine)."""
    eng = make(params, pipeline, **(eng_kw or {}))
    for p in _prompts(sizes, seed=3):
        eng.put_request(p, **req_kw)
    mid = dict(mid or {})
    outs = {}
    step_i = 0
    while eng.has_work() or mid:
        for p in mid.pop(step_i, []):
            eng.put_request(p, **req_kw)
        if eng.has_work():
            eng.step()
            outs.update(eng.get_outputs())
        step_i += 1
    outs.update(eng.get_outputs())
    return outs, eng


def _assert_same_outputs(a, b):
    assert sorted(a) == sorted(b), (sorted(a), sorted(b))
    for uid in a:
        np.testing.assert_array_equal(a[uid], b[uid],
                                      err_msg=f"uid {uid}")


class TestPipelineParity:
    """Pipelined vs pipeline=False: bit-identical (uid, tokens)."""

    def test_greedy_mixed_with_midrun_admissions(self, params):
        mid = {4: _prompts([7], seed=9), 9: _prompts([13], seed=10)}
        on, eng_on = _serve(params, True, [5, 11, 3], mid=mid,
                            max_new_tokens=10)
        off, eng_off = _serve(params, False, [5, 11, 3], mid=mid,
                              max_new_tokens=10)
        assert len(on) == 5
        _assert_same_outputs(on, off)
        # the pipelined run must actually defer: fewer blocking fetches
        # than the per-dispatch unpipelined loop
        assert (eng_on.host_stats.blocking_gets <
                eng_off.host_stats.blocking_gets)

    def test_seeded_sampling_mixed(self, params):
        kw = dict(max_new_tokens=9, do_sample=True, temperature=0.8,
                  top_k=8, top_p=0.9)
        mid = {5: _prompts([6], seed=8)}
        on, _ = _serve(params, True, [4, 12, 3], mid=mid, **kw)
        off, _ = _serve(params, False, [4, 12, 3], mid=mid, **kw)
        _assert_same_outputs(on, off)

    @pytest.mark.parametrize("sample", [False, True])
    @pytest.mark.slow
    def test_eviction_backpressure(self, params, sample):
        """Tight pool: growth stalls force mid-flight eviction/requeue;
        the pipeline reconciles at exactly the same blocks, so even
        seeded-sampled continuations match bit-for-bit."""
        eng_kw = dict(max_seqs=4, max_seq_len=128, prefill_chunk=16,
                      page_size=16, num_pages=9, decode_block_size=4,
                      kv_reserve="on_demand")
        kw = dict(max_new_tokens=40)
        if sample:
            kw.update(do_sample=True, temperature=0.9, top_k=12)
        on, eng_on = _serve(params, True, [12, 20, 9, 16],
                            eng_kw=eng_kw, **kw)
        off, eng_off = _serve(params, False, [12, 20, 9, 16],
                              eng_kw=eng_kw, **kw)
        assert eng_on.evictions > 0 and eng_off.evictions > 0, (
            "pool sized to force eviction; none happened")
        assert eng_on.evictions == eng_off.evictions
        _assert_same_outputs(on, off)

    @pytest.mark.slow
    def test_eos_early_finish(self, params):
        """EOS-bearing sequences force per-block harvests (device-side
        finish detection can't be projected) — outputs still match."""
        probe = _prompts([5, 9], seed=3)[0]   # _serve's first prompt
        out = make(params, True).generate_all([probe], max_new_tokens=2)
        eos = int(next(iter(out.values()))[-2])   # first generated token
        kw = dict(max_new_tokens=30, eos_token_id=eos)
        on, _ = _serve(params, True, [5, 9], **kw)
        off, _ = _serve(params, False, [5, 9], **kw)
        _assert_same_outputs(on, off)
        assert any(toks[-1] == eos and
                   toks.size < 5 + 30 for toks in on.values()), \
            "eos should have stopped at least the probe prompt early"


class TestSteadyStateSyncFree:
    """Acceptance: per-tick metadata uploads and blocking device_get
    calls are GONE from the steady-state decode loop."""

    def _decode_phase(self, params, pipeline):
        eng = make(params, pipeline, max_seqs=2, decode_block_size=4,
                   harvest_interval=4, kv_reserve="worst_case")
        for p in _prompts([4, 6], seed=5):
            eng.put_request(p, max_new_tokens=24)
        # drive through prefill; stats then cover ONLY the decode loop
        eng.step()
        while eng.has_work() and any(
                s is not None and s.prefill_done < s.ctx_len
                for s in eng.slots):
            eng.step()
        eng.host_stats.reset()
        while eng.has_work():
            eng.step()
        return eng

    def test_pipelined_decode_has_no_per_block_sync(self, params):
        eng = self._decode_phase(params, pipeline=True)
        st = eng.host_stats
        # 23 tokens remain per seq after prefill -> 6 blocks of 4
        assert st.dispatches >= 5
        # metadata uploaded ONCE at pipeline entry (11 arrays); the
        # worst_case reserve means zero page-table re-uploads
        assert st.meta_uploads <= 11, st.meta_uploads
        # harvests: one at harvest_interval=4, one at the projected
        # finish — NOT one per block
        assert st.blocking_gets <= 3, st.blocking_gets
        assert st.blocking_gets < st.dispatches
        assert st.harvests == st.blocking_gets

    def test_unpipelined_decode_syncs_per_block(self, params):
        """The control: pipeline=False pays one blocking fetch and a
        fresh metadata upload set per dispatch."""
        eng = self._decode_phase(params, pipeline=False)
        st = eng.host_stats
        assert st.blocking_gets == st.dispatches
        assert st.meta_uploads == 11 * st.dispatches

    def test_sync_flushes_deferred_tokens(self, params):
        eng = make(params, True, max_seqs=2, decode_block_size=4,
                   harvest_interval=8, kv_reserve="worst_case")
        (p,) = _prompts([4], seed=6)
        eng.put_request(p, max_new_tokens=20)
        eng.step()
        while eng.has_work() and any(
                s is not None and s.prefill_done < s.ctx_len
                for s in eng.slots):
            eng.step()
        generated_before = len(eng.slots[0].generated)
        eng.step()                       # one pipelined block, deferred
        assert len(eng.slots[0].generated) == generated_before
        flushed = eng.sync()
        assert flushed == 4              # the deferred block's tokens
        assert len(eng.slots[0].generated) == generated_before + 4
        stages = eng.serving_stages()
        for key in ("plan_ms", "upload_ms", "dispatch_ms", "device_ms",
                    "harvest_ms", "host_bound_fraction"):
            assert key in stages, stages


class TestNoRecompileAfterWarmup:
    def test_full_mixed_run_compiles_nothing_new(self, params):
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, True, max_seqs=3)
        sizes = [5, 11, 3, 7]
        eng.generate_all(_prompts(sizes, seed=3), max_new_tokens=8)
        with count_compiles() as compiles:
            eng.generate_all(_prompts(sizes, seed=3), max_new_tokens=8)
        assert compiles.count == 0, (
            f"{compiles.count} recompilations in the steady-state run — "
            "per-tick shapes must stay stable across the buffer-reuse "
            "path")


class TestSubmitTimeValidation:
    """Satellite bugfix: never-schedulable requests fail LOUDLY at
    submit (ValueError survives python -O; the old asserts did not)."""

    def test_empty_prompt(self, params):
        with pytest.raises(ValueError, match="empty prompt"):
            make(params, True).put_request(np.zeros(0, np.int32))

    def test_zero_max_new_tokens(self, params):
        with pytest.raises(ValueError, match="max_new_tokens"):
            make(params, True).put_request(np.ones(4, np.int32),
                                           max_new_tokens=0)

    def test_prompt_beyond_token_budget(self, params):
        eng = make(params, True, max_seq_len=32)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.put_request(np.ones(30, np.int32), max_new_tokens=8)

    def test_prompt_beyond_page_capacity_even_after_eviction(self, params):
        eng = make(params, True, max_seq_len=128, page_size=16,
                   num_pages=4)
        with pytest.raises(ValueError, match="never be scheduled"):
            eng.put_request(np.ones(40, np.int32), max_new_tokens=60)

    def test_admit_rejects_unschedulable_head(self, params):
        """Defense in depth: a request smuggled past put_request (here:
        appended directly) must not deadlock the FIFO queue."""
        eng = make(params, True, max_seq_len=256, page_size=16,
                   num_pages=4, kv_reserve="worst_case")
        eng.waiting.append(Request(uid=999,
                                   prompt=np.ones(16, np.int32),
                                   max_new_tokens=100))
        with pytest.raises(ValueError, match="never be scheduled"):
            eng.step()
        assert not eng.waiting           # poison head was dropped


class TestV1DeferredHarvest:
    """The v1 fused decode loop's deferred-harvest treatment."""

    @pytest.fixture(scope="class")
    def v1(self, params):
        return deepspeed_tpu.init_inference(
            model=LlamaForCausalLM(CFG), params=params,
            max_out_tokens=64, dtype="float32")

    def test_generate_async_matches_generate(self, v1):
        prompt = _prompts([6], seed=12)[0][None]
        ref = v1.generate(prompt, max_new_tokens=5)
        v1.host_stats.reset()
        handles = [v1.generate_async(prompt, max_new_tokens=5)
                   for _ in range(3)]
        # dispatching 3 generations cost ZERO blocking fetches...
        assert v1.host_stats.blocking_gets == 0
        assert v1.host_stats.dispatches == 3
        for h in handles:
            np.testing.assert_array_equal(h.result(), ref)
        # ...and each harvest paid exactly one
        assert v1.host_stats.blocking_gets == 3
        stages = v1.serving_stages()
        assert stages["host_bound_fraction"] is not None

    def test_result_is_cached(self, v1):
        prompt = _prompts([4], seed=13)[0][None]
        h = v1.generate_async(prompt, max_new_tokens=4)
        a, b = h.result(), h.result()
        assert a is b
        assert h.ready()

    def test_v1_reads_v2_config_subtree(self, params):
        eng = deepspeed_tpu.init_inference(
            model=LlamaForCausalLM(CFG), params=params,
            config={"dtype": "float32", "max_out_tokens": 64,
                    "v2": {"pipeline": False, "harvest_interval": 7}})
        assert eng.v2.pipeline is False
        assert eng.v2.harvest_interval == 7


class TestConfigKnobs:
    def test_defaults(self):
        cfg = load_inference_config(None)
        assert cfg.v2.pipeline is True
        assert cfg.v2.async_depth == 2
        assert cfg.v2.harvest_interval == 4

    def test_validation(self):
        with pytest.raises(Exception):
            load_inference_config({"v2": {"async_depth": 0}})

    def test_ragged_engine_consumes_config(self, params):
        eng = RaggedInferenceEngineV2(
            LlamaForCausalLM(CFG), params=params, max_seqs=2,
            max_seq_len=64, prefill_chunk=8,
            config={"v2": {"pipeline": False, "async_depth": 3,
                           "harvest_interval": 6}})
        assert eng.pipeline is False
        assert eng.async_depth == 3 and eng.harvest_interval == 6
        # explicit kwarg wins over the config subtree
        eng2 = RaggedInferenceEngineV2(
            LlamaForCausalLM(CFG), params=params, max_seqs=2,
            max_seq_len=64, prefill_chunk=8, pipeline=True,
            config={"v2": {"pipeline": False}})
        assert eng2.pipeline is True


class TestControlPlane:
    """Closed-loop controller on the live engine (pure-policy tests
    live in test_control.py — these cover the engine attach points)."""

    def _ctl(self):
        # tick every step, judge after one settle tick: the controller
        # exercises real knob changes within a short run
        return {"interval": 1, "settle": 1, "cooldown": 0}

    def test_armed_controller_compiles_nothing_new(self, params):
        """The online policy only touches knobs that are NOT baked into
        compiled shapes, so a warm engine with the controller actively
        probing must trigger zero new XLA compilations."""
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, True, max_seqs=3, control=self._ctl())
        assert eng._controller is not None
        sizes = [5, 11, 3, 7]
        eng.generate_all(_prompts(sizes, seed=3), max_new_tokens=8)
        with count_compiles() as compiles:
            eng.generate_all(_prompts(sizes, seed=3), max_new_tokens=8)
        assert eng._controller.counts["ticks"] > 0
        assert eng._controller.counts["probes"] > 0, (
            "controller never probed — the zero-recompile claim was "
            "not exercised")
        assert compiles.count == 0, (
            f"{compiles.count} recompilations with the controller armed — "
            "an online knob leaked into a compiled shape")

    def test_greedy_parity_across_midrun_knob_change(self, params):
        """harvest_interval / async_depth only move work between host
        and device timelines: flipping them mid-run through the knob
        registry must leave greedy outputs bit-identical."""
        base, _ = _serve(params, True, [5, 11, 3], max_new_tokens=20)
        eng = make(params, True)
        for p in _prompts([5, 11, 3], seed=3):
            eng.put_request(p, max_new_tokens=20)
        reg = eng.knob_registry()
        outs = {}
        step_i = 0
        while eng.has_work():
            if step_i == 2:
                reg.set("engine.harvest_interval", 1)
                reg.set("engine.async_depth", 4)
            elif step_i == 4:
                reg.set("engine.harvest_interval", 6)
                reg.set("engine.async_depth", 1)
            eng.step()
            outs.update(eng.get_outputs())
            step_i += 1
        outs.update(eng.get_outputs())
        assert step_i > 4, "run too short to exercise both changes"
        _assert_same_outputs(base, outs)

    def test_stages_expose_decisions_and_kill_switch(self, params,
                                                     monkeypatch):
        monkeypatch.delenv("DSTPU_CONTROL", raising=False)
        eng = make(params, True, control=self._ctl())
        eng.generate_all(_prompts([5, 3], seed=3), max_new_tokens=6)
        st = eng.serving_stages()["control"]
        assert st["ticks"] > 0
        assert st["knobs"]["engine.harvest_interval"] >= 1
        assert len(eng._controller.decision_log) == st["decisions"]
        # DSTPU_CONTROL=0: structurally the pre-control engine
        monkeypatch.setenv("DSTPU_CONTROL", "0")
        off = make(params, True, control=self._ctl())
        assert off._controller is None
        assert "control" not in off.serving_stages()

    def test_profile_seeds_construction(self, params, tmp_path):
        """A saved host profile seeds knob values at engine build —
        including recompile-class knobs, which are pre-warmup there."""
        from deepspeed_tpu.control import HostProfile, save_profile
        save_profile(HostProfile(knobs={"engine.harvest_interval": 9,
                                        "engine.async_depth": 1,
                                        "engine.decode_block_size": 8,
                                        "not.a.knob": 3}),
                     str(tmp_path))
        eng = make(params, True,
                   control={"profile": str(tmp_path)})
        assert eng.harvest_interval == 9
        assert eng.async_depth == 1
        assert eng.decode_block_size == 8
