"""Serving host-path pipeline tests (the async serving tentpole).

The load-bearing contracts:

- **Bit-identical parity, request by request**: pipelined serving
  (``pipeline=True``: one queue of dispatched, un-folded ticks and decode
  blocks, planned from counts) and the unpipelined host loop
  (``pipeline=False``) produce the same ``(uid, tokens)`` outputs on
  mixed prompt-length workloads, greedy AND seeded sampling, under
  mid-run admissions, finishes, EOS and eviction backpressure.  The
  SCHEDULES differ (an admission's tick runs behind the blocks in flight,
  a finish does not restart the blocks): a row's tokens are its own.
- **The loop waits only where it must**: on EOS-free traffic nothing
  drains, every block and every admission goes out with work in flight;
  what needs a token's value drains and says why.
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
        eng.sync()      # the last prefill tick may still be in flight
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
        eng.sync()      # the prefill tick's token may still be in flight
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


# prompts of several 8-token chunks beside decoding rows, with arrivals in
# mid-run: the fused path runs for many ticks on end
LONG_MIX = dict(sizes=[30, 5, 44],
                mid={3: _prompts([21], seed=9), 7: _prompts([37], seed=10),
                     12: _prompts([9], seed=11)})
SEEDED = dict(do_sample=True, temperature=0.8, top_k=8, top_p=0.9)


def _tiny(family):
    """A tiny OLMoE (dropless experts) or Ouro (a looped model) and its
    weights, in float32."""
    from deepspeed_tpu.models import olmoe, ouro

    plain = dict(dtype=jnp.float32, param_dtype=jnp.float32,
                 scan_layers=False, remat=False, use_flash_attention=False)
    model = (olmoe.OlmoeForCausalLM(olmoe.get_config(
        "tinyolmoe", drop_tokens=False, **plain)) if family == "olmoe"
        else ouro.OuroForCausalLM(ouro.get_config("tinyouro", **plain)))
    return model, jax.jit(model.init)(
        jax.random.PRNGKey(5), np.zeros((1, 8), np.int32))["params"]


def _log_calls(eng):
    """Record the order of the fused ticks' dispatches ("D") and of the
    blocking fetches ("F") of ``eng`` from here on."""
    log = []
    build, fetch = eng.fused_sample_step, eng.fetch

    def fused_sample_step(sampled):
        fn = build(sampled)

        def dispatch(*a):
            log.append("D")
            return fn(*a)
        return dispatch

    def logged_fetch(tree):
        log.append("F")
        return fetch(tree)

    eng.fused_sample_step, eng.fetch = fused_sample_step, logged_fetch
    return log


def _queued(eng):
    """The queued (dispatched, un-folded) work of ``eng``, as "T" for a
    fused tick and "B" for a decode block, oldest first."""
    return "".join("B" if w.block else "T" for w in eng._queue)


class TestFusedTicksAhead:
    """The SplitFuse loop dispatches tick k+1 before it waits for tick k's
    tokens wherever the plan of k+1 is exact without them, and nowhere
    else; the tokens are ``pipeline=False``'s bit for bit."""

    # (that the two engines run the same NUMBER of ticks is no contract:
    # an admission's tick runs behind the blocks in flight)

    @pytest.mark.parametrize("kw", [{}, SEEDED], ids=["greedy", "seeded"])
    def test_parity_on_a_mix_of_chunked_prompts_and_decoding_rows(
            self, params, kw):
        on, eng_on = _serve(params, True, LONG_MIX["sizes"],
                            mid=LONG_MIX["mid"], max_new_tokens=14, **kw)
        off, eng_off = _serve(params, False, LONG_MIX["sizes"],
                              mid=LONG_MIX["mid"], max_new_tokens=14, **kw)
        assert len(on) == 6
        _assert_same_outputs(on, off)
        st_on, st_off = eng_on.host_stats, eng_off.host_stats
        assert st_on.fused_ticks > 10 and st_off.fused_ticks > 10
        assert st_on.fused_ticks_ahead > st_on.fused_ticks // 2
        # three slots, six requests: a finish in flight is waited for
        # only while a request waits that nothing free can seat
        assert set(st_on.drain_reasons) <= {"pages"}
        assert st_off.fused_ticks_ahead == 0
        stages = eng_on.serving_stages()
        assert stages["fused_ticks_ahead"] == st_on.fused_ticks_ahead
        assert stages["fused_ticks"] == st_on.fused_ticks

    def test_tick_k_plus_1_is_dispatched_before_tick_k_is_fetched(
            self, params):
        eng = make(params, True)
        log = _log_calls(eng)
        eng.put_request(_prompts([5], seed=1)[0], max_new_tokens=12)
        eng.put_request(_prompts([40], seed=2)[0], max_new_tokens=4)
        while eng.slots[1] is None or (eng.slots[1].prefill_done <
                                       eng.slots[1].ctx_len):
            eng.step()
        # five chunk ticks, a decode row of the short request in each
        # after the first: every dispatch but the first goes out with the
        # tick before un-fetched, and every fetch but the last comes
        # after the NEXT tick's dispatch
        assert "".join(log) == "D" + "DF" * 4
        assert _queued(eng) == "T" and eng.has_work()
        assert eng.host_stats.fused_ticks_ahead == 4
        # the token of the tick in flight is not the host's yet
        before = len(eng.slots[0].generated)
        assert eng.slots[0].pending == 1
        assert eng.sync() == 2       # a decode row and the prompt's end
        assert not eng._queue and eng.slots[0].pending == 0
        assert len(eng.slots[0].generated) == before + 1
        assert eng.host_stats.drain_reasons == {"api": 1}

    def test_a_prompts_first_token_streams_when_its_tick_ends(self, params):
        """The first decode block is built from counts and dispatched
        BEHIND the prompt's tick, its carry taking the first token from
        the device; then the tick is folded, in the same step: the token
        reaches ``stream_deltas`` when its tick ends, no block's time
        later, and the chip has the block to run meanwhile."""
        eng = make(params, True)
        uid = eng.put_request(_prompts([5], seed=1)[0], max_new_tokens=12)
        assert eng.step() == 0 and _queued(eng) == "T"
        assert eng.stream_deltas() == []
        uploads = eng.host_stats.meta_uploads
        assert eng.step() == 1 and _queued(eng) == "B"
        assert eng._dev is not None and eng.slots[0].pending == 4
        # the carry's last_tok was not uploaded: nine arrays and the seeds
        assert eng.host_stats.meta_uploads == uploads + 10
        ((got, toks, total, done),) = eng.stream_deltas()
        assert (got, len(toks), total, done) == (uid, 1, 1, False)
        st = eng.host_stats
        assert (st.decode_blocks, st.decode_blocks_ahead, st.drains) == \
            (1, 1, 0)
        ref = make(params, False).generate_all(_prompts([5], seed=1),
                                               max_new_tokens=12)
        np.testing.assert_array_equal(eng.drain()[uid], ref[0])

    def test_dispatch_spans_say_whether_they_went_ahead(self, params):
        from deepspeed_tpu.telemetry import trace
        prev = (trace.enabled, trace.buffer_size, trace.clock)
        trace.clear()
        trace.configure(enabled=True)
        try:
            _, eng = _serve(params, True, [5, 40], max_new_tokens=6)
            fused = [e["args"] for e in trace.snapshot()
                     if e["name"] == "engine.dispatch"
                     and e["args"].get("program") == "fused"]
        finally:
            trace.configure(enabled=prev[0], buffer_size=prev[1],
                            clock=prev[2])
            trace.clear()
        st = eng.host_stats
        assert len(fused) == st.fused_ticks
        assert sum(a["ahead"] for a in fused) == st.fused_ticks_ahead > 0
        assert {a["ahead"] for a in fused} == {0, 1}

    @pytest.mark.parametrize("case", ["eos", "pipeline_off"])
    def test_no_tick_goes_ahead_where_its_tokens_decide_the_plan(
            self, params, case):
        """A row with an EOS id may end on any token, so its tick is
        fetched before the next plan, as ``has_eos`` forces a harvest of
        the decode pipeline; ``pipeline=False`` is the switch."""
        kw = dict(max_new_tokens=14)
        if case == "eos":
            kw["eos_token_id"] = 1000     # no token of the vocabulary
        eng = make(params, case != "pipeline_off")
        for p in _prompts(LONG_MIX["sizes"], seed=3):
            eng.put_request(p, **kw)
        mid, outs, step_i = dict(LONG_MIX["mid"]), {}, 0
        while eng.has_work() or mid:
            for p in mid.pop(step_i, []):
                eng.put_request(p, **kw)
            if eng.has_work():
                eng.step()
                # no token is left on the device past its step (a tick
                # that samples nothing has none, and may stay queued)
                assert not any(w.rows for w in eng._queue)
                outs.update(eng.get_outputs())
            step_i += 1
        ref, _ = _serve(params, False, LONG_MIX["sizes"],
                        mid=LONG_MIX["mid"], max_new_tokens=14)
        _assert_same_outputs(outs, ref)
        st = eng.host_stats
        assert st.fused_ticks > 10
        if case == "eos":
            assert st.drain_reasons == {"eos": st.drains} and st.drains > 10
        else:
            assert st.fused_ticks_ahead == st.drains == 0

    def test_a_handoff_prefill_is_fetched_and_parked_in_its_own_step(
            self, params):
        """The first token of a handoff prefill parks the session the
        step it lands (``_reap(handoff_sweep=True)``): its tick is never
        left in flight, while the ticks around it are."""
        eng = make(params, True)
        eng.put_request(_prompts([5], seed=1)[0], max_new_tokens=30)
        uid = eng.put_request(_prompts([29], seed=2)[0], max_new_tokens=8,
                              handoff=True)
        parked_at = None
        for i in range(40):
            eng.step()
            assert not any(r.handoff for w in eng._queue
                           for r, _ in w.rows)
            if eng._handoff_ready and parked_at is None:
                parked_at = i
                assert eng.host_stats.fused_ticks_ahead > 0
                # parked with its first token, in the step of its tick
                (r,) = eng._handoff_ready
                assert r.uid == uid and len(r.generated) == 1
                assert r.pending == 0 and r.slot == -1
                break
        assert parked_at is not None
        # pulled with a tick of the other request in flight or not:
        # export flushes first
        assert eng.host_stats.drain_reasons == {"handoff": 1}
        (blob,) = eng.export_handoff()
        assert not eng._queue and len(blob["generated"]) == 1
        alone = make(params, False).generate_all(
            _prompts([29], seed=2), max_new_tokens=1)
        assert list(alone[0][29:]) == blob["generated"]
        eng.drain()
        eng.audit_kv_sharing()          # raises on a leaked page

    @pytest.mark.parametrize("op", ["cancel", "sync", "drain"])
    def test_an_op_that_needs_exact_state_flushes_the_tick_in_flight(
            self, params, op):
        """The same op at the same step on both engines: the pipelined
        one has a tick in flight then, and ends with the same tokens."""
        def run(pipeline):
            eng = make(params, pipeline)
            uids = [eng.put_request(p, max_new_tokens=12)
                    for p in _prompts([5, 40, 33], seed=4)]
            for _ in range(3):
                eng.step()
            in_flight = bool(eng._queue)
            outs = {}
            if op == "cancel":
                assert eng.cancel(uids[0]) == "decode"
                eng.audit_kv_sharing()          # raises on a leaked page
            elif op == "sync":
                # the short request's decode row of the tick in flight
                assert eng.sync() == (1 if in_flight else 0)
                assert all(r.pending == 0 for r in eng.slots
                           if r is not None)
            if op == "drain":
                outs.update(eng.drain())
            else:
                assert not eng._queue
                while eng.has_work():
                    eng.step()
                    outs.update(eng.get_outputs())
            assert not eng._queue and not eng.has_work()
            return outs, in_flight

        (on, flew), (off, never) = run(True), run(False)
        assert flew and not never
        assert len(on) == (2 if op == "cancel" else 3)
        _assert_same_outputs(on, off)

    def test_a_page_stall_flushes_the_tick_in_flight_and_evicts_alike(
            self, params):
        """A pool too small for its sequences: the plan that falls short
        of pages is made again after the work in flight is folded, and
        whoever is evicted re-prefills its own output: the tokens are
        ``pipeline=False``'s."""
        eng_kw = dict(max_seqs=3, prefill_chunk=8, page_size=8, num_pages=9,
                      decode_block_size=4, kv_reserve="on_demand")
        on, eng_on = _serve(params, True, [12, 20, 9], eng_kw=eng_kw,
                            max_new_tokens=24)
        off, eng_off = _serve(params, False, [12, 20, 9], eng_kw=eng_kw,
                              max_new_tokens=24)
        assert eng_on.evictions > 0 and eng_off.evictions > 0
        assert eng_on.host_stats.fused_ticks_ahead > 0
        assert eng_on.host_stats.drain_reasons["pages"] > 0
        _assert_same_outputs(on, off)
        eng_on.audit_kv_sharing()       # raises on a leaked page

    def test_no_count_of_sampling_rows_compiles_after_the_first_tick(
            self, params):
        """Everything the host hands the tick is a fixed-shape array: ticks
        with 1..max_seqs sampling rows run the program the first compiled."""
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, True, max_seqs=4)
        # one request alone: the fused tick and the decode block compile
        eng.generate_all(_prompts([11], seed=3), max_new_tokens=8)
        seen = set()
        pack = eng._pack_tick

        def counting_pack(takes):
            out = pack(takes)
            seen.add(len(out[2]))
            return out

        eng._pack_tick = counting_pack
        with count_compiles() as compiles:
            # long prompts arriving one behind the other: each prefills
            # beside the decode rows of those before it
            for p in _prompts([30, 30, 30, 30], seed=6):
                eng.put_request(p, max_new_tokens=40)
                for _ in range(5):
                    eng.step()
            eng.drain()
        assert {1, 2, 3, 4} <= seen, seen
        assert compiles.count == 0, compiles.count

    @pytest.mark.parametrize("family", ["olmoe", "ouro"])
    def test_parity_and_counts_on_an_expert_and_a_looped_model(self, family):
        """The tick's inputs gain ``last_tok`` and a row-source vector, its
        model call is the same: the counts of the expert layers and of the
        pass loop ride the fetches as they did, to the same sums."""
        model, weights = _tiny(family)
        prompts = [np.asarray(p, np.int32)
                   for p in _prompts([21, 5, 13], seed=3)]
        runs = {}
        for pipeline in (True, False):
            eng = RaggedInferenceEngineV2(
                model, params=weights, pipeline=pipeline, max_seqs=3,
                max_seq_len=64, prefill_chunk=8, page_size=8,
                decode_block_size=4)
            runs[pipeline] = (eng.generate_all(prompts, max_new_tokens=9),
                              eng.serving_stages(), eng)
        (on, st_on, eng_on), (off, st_off, _) = runs[True], runs[False]
        _assert_same_outputs(on, off)
        assert eng_on.host_stats.fused_ticks_ahead > 0
        assert not eng_on._counts_pending
        if family == "olmoe":
            assert st_on["moe"] == st_off["moe"]
            assert st_on["moe"]["rows"] == 2 * 2 * sum(
                p.size + 8 for p in prompts)
        else:
            assert st_on["loop"]["rows"] == st_off["loop"]["rows"] > 0
            np.testing.assert_allclose(st_on["loop"]["exit_cdf"],
                                       st_off["loop"]["exit_cdf"],
                                       rtol=1e-5)


CALLERS = 4


def _closed_loop(model, weights, pipeline, total=12, **req_kw):
    """``CALLERS`` callers that each send their next request when the last
    one's answer is collected, ``total`` requests in all, prompts of 3-19
    tokens and answers of 9-33: finishes and arrivals fall while decode
    blocks are in flight.  Returns ``({uid: tokens}, engine, stream)``,
    ``stream`` the ``(step, uid, new tokens, done)`` of every delta."""
    eng = RaggedInferenceEngineV2(
        model, params=weights, pipeline=pipeline, max_seqs=6,
        max_seq_len=64, prefill_chunk=8, page_size=8, decode_block_size=4,
        rng=jax.random.PRNGKey(11))
    rng = np.random.default_rng(5)
    work = [(rng.integers(1, 64, size=(int(rng.integers(3, 20)),),
                          dtype=np.int32), int(rng.integers(9, 34)))
            for _ in range(total)]
    queue = iter(work)

    def submit():
        prompt, new = next(queue)
        eng.put_request(prompt, max_new_tokens=new, **req_kw)

    for _ in range(CALLERS):
        submit()
    outs, stream, step_i, sent = {}, [], 0, CALLERS
    while eng.has_work():
        eng.step()
        step_i += 1
        stream += [(step_i, uid, len(toks), done)
                   for uid, toks, _, done in eng.stream_deltas()]
        for uid, toks in eng.get_outputs():
            outs[uid] = toks
            if sent < total:
                submit()
                sent += 1
    return outs, eng, stream


class TestOneQueueOfDispatchedWork:
    """Ticks and decode blocks in ONE in-order queue, planned from counts:
    a periodic harvest, a finish by count and an admission wait for
    nothing in flight; what needs a token's value drains, and says why."""

    @pytest.mark.parametrize("kw", [{}, SEEDED], ids=["greedy", "seeded"])
    def test_each_requests_tokens_are_the_unpipelined_runs_in_a_closed_loop(
            self, params, kw):
        """(By every other tiny serving model:
        ``tests/unit/inference/test_loop_contracts*.py``.)"""
        model = LlamaForCausalLM(CFG)
        on, eng, _ = _closed_loop(model, params, True, **kw)
        off, _, _ = _closed_loop(model, params, False, **kw)
        assert len(on) == 12
        _assert_same_outputs(on, off)
        st = eng.host_stats
        # the schedule really was the queue's: blocks and admissions went
        # out behind work in flight, and nothing was waited for
        assert st.decode_blocks_ahead > 10 and st.admissions_ahead >= 6
        assert st.drains == 0 and not eng._counts_pending
        eng.audit_kv_sharing()          # raises on a leaked page

    def test_eos_free_traffic_never_drains_and_everything_goes_ahead(
            self, params):
        from deepspeed_tpu.telemetry import trace
        prev = (trace.enabled, trace.buffer_size, trace.clock)
        trace.clear()
        trace.configure(enabled=True)
        try:
            _, eng, _ = _closed_loop(LlamaForCausalLM(CFG), params, True)
            spans = [e["args"] for e in trace.snapshot()
                     if e["name"] == "engine.dispatch"]
        finally:
            trace.configure(enabled=prev[0], buffer_size=prev[1],
                            clock=prev[2])
            trace.clear()
        st = eng.host_stats
        stages = eng.serving_stages()
        assert st.drains == 0 and "drain_reasons" not in stages
        # every block goes out with work in flight (the first behind the
        # last prompt's tick), every admission after the callers' first
        assert st.decode_blocks_ahead == st.decode_blocks > 10
        assert st.admissions == 12
        assert st.admissions_ahead == st.admissions - CALLERS
        for key in ("drains", "decode_blocks", "decode_blocks_ahead",
                    "admissions", "admissions_ahead"):
            assert stages[key] == getattr(st, key)
        # the spans say the same, and a tick that follows a block is ahead
        blocks = [a for a in spans if a["program"] == "decode_block"]
        assert len(blocks) == st.decode_blocks
        assert sum(a["ahead"] for a in blocks) == st.decode_blocks_ahead
        after_a_block = [b for a, b in zip(spans, spans[1:])
                         if a["program"] == "decode_block"
                         and b["program"] == "fused"]
        assert len(after_a_block) >= 3
        assert all(b["ahead"] == 1 for b in after_a_block)

    @pytest.mark.parametrize("case", ["eos", "pages", "handoff"])
    def test_what_needs_a_tokens_value_drains_and_says_why(self, params,
                                                           case):
        """The same traffic that never drains, with the one thing added
        that the loop cannot plan past from counts."""
        eng_kw, kw, extra = {}, dict(max_new_tokens=14), {}
        if case == "eos":
            kw["eos_token_id"] = 1000     # no token of the vocabulary
        elif case == "pages":
            eng_kw = dict(max_seqs=3, page_size=8, num_pages=9,
                          kv_reserve="on_demand")
            kw["max_new_tokens"] = 24
        else:
            extra = dict(handoff=True)
        eng = make(params, True, **eng_kw)
        for i, p in enumerate(_prompts([12, 20, 9], seed=3)):
            eng.put_request(p, **kw, **(extra if i == 1 else {}))
        while eng.has_work():
            eng.step()
            eng.export_handoff()
        reasons = dict(eng.host_stats.drain_reasons)
        reasons.pop("api", None)          # (export_handoff is one)
        assert set(reasons) == {case}, reasons
        assert eng.serving_stages()["drain_reasons"][case] == reasons[case]
        eng.audit_kv_sharing()

    def test_a_finish_streams_at_its_blocks_end_with_the_next_block_queued(
            self, params):
        """Two rows decode side by side and the short one ends, by its
        count, in block 3: block 4 goes out for the row that goes on, THEN
        block 3 is joined and folded, so the finish reaches
        ``stream_deltas`` as soon as block 3 ends (in the step that
        dispatches block 4, where a drain of block 3 used to deliver it),
        the long row is not restarted, and nothing is uploaded for it."""
        eng = make(params, True, max_seqs=2, harvest_interval=8)
        long_, short = (eng.put_request(p, max_new_tokens=n) for p, n in
                        zip(_prompts([5, 6], seed=5), (40, 10)))
        seen = {}
        while short not in seen:
            eng.step()
            for uid, _, total, done in eng.stream_deltas():
                if done:
                    seen[uid] = (eng.host_stats.decode_blocks, total)
        # 10 tokens: one of the prompt's tick, 4 + 4 + 1 of three blocks
        assert seen == {short: (4, 10)}
        assert _queued(eng) == "B" and eng._dev is not None
        assert [r.uid for r in eng._dev["reqs"] if not r.done] == [long_]
        # the long row has what two ticks and blocks 1-3 gave it; block 4 flies
        (row,) = [r for r in eng.slots if r is not None]
        assert (len(row.generated), row.pending) == (14, 4)
        uploads = eng.host_stats.meta_uploads
        outs = eng.drain()
        assert eng.host_stats.meta_uploads == uploads
        assert eng.host_stats.drain_reasons == {"api": 1}
        ref = make(params, False, max_seqs=2).generate_all(
            _prompts([5, 6], seed=5)[:1], max_new_tokens=40)
        np.testing.assert_array_equal(outs[long_], ref[0])

    def test_a_periodic_harvest_folds_what_is_joined_and_waits_for_nothing(
            self, params):
        """``harvest_interval`` 4, ``async_depth`` 2: at four un-folded
        blocks the two the window has joined are folded and the two in
        flight are left alone, so a stream sees 8 tokens every second
        block."""
        eng = make(params, True, max_seqs=2, harvest_interval=4,
                   kv_reserve="worst_case")
        uid = eng.put_request(_prompts([5], seed=1)[0], max_new_tokens=60)
        eng.step()
        eng.step()                       # the tick folded, block 1 queued
        eng.stream_deltas()
        got = []
        for _ in range(8):
            eng.step()
            got.append((sum(len(t) for _, t, _, _ in eng.stream_deltas()),
                        _queued(eng)))
        assert got == [(0, "BB"), (0, "BBB"), (8, "BB"), (0, "BBB"),
                       (8, "BB"), (0, "BBB"), (8, "BB"), (0, "BBB")]
        assert eng.host_stats.drains == 0
        ref = make(params, False, max_seqs=2).generate_all(
            _prompts([5], seed=1), max_new_tokens=60)
        np.testing.assert_array_equal(eng.drain()[uid], ref[0])


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
