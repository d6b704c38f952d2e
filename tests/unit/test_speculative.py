"""Speculative decoding tests (the round-6 serving perf tentpole).

The load-bearing contracts:

- **Greedy bit-identity**: speculation is a pure perf lever — greedy
  spec-on output equals spec-off output bit-for-bit for BOTH draft
  modes, including mid-run admissions, eviction backpressure, rollback
  spanning a deferred-harvest window, ``k`` longer than a sequence's
  remaining budget, and sequences that hit ``max_seq_len`` mid-chunk.
- **Sampled distribution preservation**: the accept/rollback core
  (``sampling.speculative_verify``) provably leaves the output
  distribution unchanged — verified by Monte-Carlo against the filtered
  target distribution for both point-mass (n-gram) and draft-model
  proposal distributions.  At the engine level, seeded sampled runs are
  bit-identical between ``pipeline=True`` and ``pipeline=False`` with
  speculation on (the PR-5 parity oracle extended to the speculative
  dispatch sequence).
- **KV bookkeeping exactness**: position rollback never leaks or
  double-grants pages (``PageAllocator.audit``).
- **Steady state stays pipelined**: speculative decode defers harvests
  and re-uses device-resident metadata; no per-block sync creep, zero
  new compilations after warmup.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import load_inference_config
from deepspeed_tpu.inference.sampling import (filter_logits_batched,
                                              speculative_verify)
from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineV2,
                                        SpeculationConfig)
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)
DCFG = get_config("tinyllama", vocab_size=64, hidden_size=16,
                  intermediate_size=32, num_hidden_layers=1,
                  num_attention_heads=2, num_key_value_heads=1,
                  max_position_embeddings=128, dtype=jnp.float32,
                  param_dtype=jnp.float32, scan_layers=False, remat=False,
                  use_flash_attention=False)


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


@pytest.fixture(scope="module")
def draft_params():
    model = LlamaForCausalLM(DCFG)
    return jax.jit(model.init)(jax.random.PRNGKey(9),
                               np.zeros((1, 8), np.int32))


def make(params, spec, pipeline=True, draft_params=None, **kw):
    kw.setdefault("max_seqs", 3)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("harvest_interval", 3)
    if spec == "draft" or (isinstance(spec, dict) and
                           spec.get("mode") == "draft"):
        kw.setdefault("draft_model", LlamaForCausalLM(DCFG))
        kw.setdefault("draft_params", draft_params)
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=pipeline, speculation=spec,
                                   rng=jax.random.PRNGKey(11), **kw)


def _prompts(sizes, seed=0):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


def _serve(params, spec, sizes, pipeline=True, mid=None, eng_kw=None,
           draft_params=None, **req_kw):
    eng = make(params, spec, pipeline=pipeline, draft_params=draft_params,
               **(eng_kw or {}))
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


class TestVerifyDistribution:
    """Monte-Carlo oracle: the accept/residual-resample core leaves the
    output distribution exactly the target's filtered distribution."""

    N = 40000
    V = 8
    K = 3

    def _first_token_freq(self, draft_probs, seed, target_logits,
                          temperature=0.7, top_k=0, top_p=1.0):
        """Rows are independent trials (independent uniforms/categorical
        draws per row) — one jit call is N trials."""
        N, V, K = self.N, self.V, self.K
        logits = jnp.broadcast_to(target_logits, (N, K + 1, V))
        r = np.random.default_rng(seed)
        if draft_probs is None:
            # point-mass draft: ANY fixed proposal is a sample of its
            # own delta distribution
            draft = jnp.asarray(
                np.broadcast_to(r.integers(0, V, size=(1, K)), (N, K)),
                jnp.int32)
        else:
            # the theorem needs d ~ q: sample the proposals from the
            # draft distribution per trial row
            draft = jax.random.categorical(
                jax.random.PRNGKey(seed + 100),
                jnp.log(jnp.maximum(jnp.broadcast_to(
                    draft_probs, (N, K, V)), 1e-30)),
                axis=-1).astype(jnp.int32)
        out, _ = jax.jit(speculative_verify, static_argnums=())(
            logits, draft,
            (jnp.broadcast_to(draft_probs, (N, K, V))
             if draft_probs is not None else None),
            jax.random.PRNGKey(seed),
            jnp.ones((N,), bool), jnp.full((N,), temperature, jnp.float32),
            jnp.full((N,), top_k, jnp.int32),
            jnp.full((N,), top_p, jnp.float32))
        first = np.asarray(out[:, 0])
        freq = np.bincount(first, minlength=V) / N
        flt = filter_logits_batched(
            target_logits[:1, :].astype(jnp.float32),
            jnp.asarray([temperature]), jnp.asarray([top_k]),
            jnp.asarray([top_p]))
        expect = np.asarray(jax.nn.softmax(flt, axis=-1))[0]
        return freq, expect

    def test_point_mass_draft_preserves_distribution(self):
        """n-gram drafts are delta distributions: accept w.p. p(d),
        else resample from p minus the drafted token."""
        r = np.random.default_rng(0)
        tlogits = jnp.asarray(r.normal(size=(self.K + 1, self.V)),
                              jnp.float32)
        freq, expect = self._first_token_freq(None, seed=1,
                                              target_logits=tlogits)
        np.testing.assert_allclose(freq, expect, atol=0.012)

    def test_draft_distribution_preserves_distribution(self):
        """Full rejection sampling against a non-degenerate q."""
        r = np.random.default_rng(2)
        tlogits = jnp.asarray(r.normal(size=(self.K + 1, self.V)),
                              jnp.float32)
        q = jax.nn.softmax(jnp.asarray(
            r.normal(size=(self.K, self.V)), jnp.float32), axis=-1)
        freq, expect = self._first_token_freq(q, seed=3,
                                              target_logits=tlogits)
        np.testing.assert_allclose(freq, expect, atol=0.012)

    def test_filtered_distribution_preserved_under_top_k_top_p(self):
        r = np.random.default_rng(4)
        tlogits = jnp.asarray(r.normal(size=(self.K + 1, self.V)),
                              jnp.float32)
        freq, expect = self._first_token_freq(
            None, seed=5, target_logits=tlogits, temperature=0.9,
            top_k=4, top_p=0.8)
        assert (freq[expect == 0] == 0).all(), \
            "sampled a token the filter removed"
        np.testing.assert_allclose(freq, expect, atol=0.012)

    def test_greedy_rows_emit_target_argmax(self):
        """Greedy verify emits the target argmax at every position —
        draft quality only moves the accept length."""
        r = np.random.default_rng(6)
        logits = jnp.asarray(r.normal(size=(5, self.K + 1, self.V)),
                             jnp.float32)
        draft = jnp.asarray(r.integers(0, self.V, size=(5, self.K)),
                            jnp.int32)
        out, acc = speculative_verify(
            logits, draft, None, None, jnp.zeros((5,), bool),
            jnp.ones((5,), jnp.float32), jnp.zeros((5,), jnp.int32),
            jnp.ones((5,), jnp.float32))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jnp.argmax(logits, -1)))
        g = np.asarray(jnp.argmax(logits, -1))[:, :self.K]
        expect_acc = [int(np.cumprod(np.asarray(draft)[i] == g[i]).sum())
                      for i in range(5)]
        np.testing.assert_array_equal(np.asarray(acc), expect_acc)


class TestGreedyParity:
    """Greedy spec-on == spec-off, bit-identical (both draft modes)."""

    def test_ngram_mixed_with_midrun_admissions(self, params):
        mid = {4: _prompts([7], seed=9), 9: _prompts([13], seed=10)}
        off, _ = _serve(params, "off", [5, 11, 3], mid=mid,
                        max_new_tokens=10)
        on, eng = _serve(params, "ngram", [5, 11, 3], mid=mid,
                         max_new_tokens=10)
        assert len(on) == 5
        _assert_same_outputs(on, off)
        assert eng.host_stats.spec_dispatches > 0
        eng.allocator.audit()

    def test_draft_model_mixed(self, params, draft_params):
        off, _ = _serve(params, "off", [5, 11, 3], max_new_tokens=10)
        on, eng = _serve(params, "draft", [5, 11, 3],
                         draft_params=draft_params, max_new_tokens=10)
        _assert_same_outputs(on, off)
        assert eng.host_stats.spec_dispatches > 0
        eng.allocator.audit()

    def test_self_draft_accepts_and_matches(self, params):
        """Draft == target: acceptance mechanics at the quality ceiling
        — still bit-identical, and acceptance must actually happen."""
        off, _ = _serve(params, "off", [5, 9], max_new_tokens=16)
        on, eng = _serve(
            params, "draft", [5, 9], max_new_tokens=16,
            eng_kw=dict(draft_model=LlamaForCausalLM(CFG)),
            draft_params=params)
        _assert_same_outputs(on, off)
        spec = eng.serving_stages()["speculation"]
        assert spec["acceptance_rate"] > 0.1, spec

    def test_eviction_backpressure(self, params):
        """Tight pool: speculative over-allocation for the k+1-wide
        write span forces stalls/evictions — greedy outputs still
        bit-identical, page accounting still exact."""
        eng_kw = dict(max_seqs=4, max_seq_len=128, prefill_chunk=16,
                      page_size=16, num_pages=9, decode_block_size=4,
                      kv_reserve="on_demand")
        off, eoff = _serve(params, "off", [12, 20, 9, 16],
                           eng_kw=eng_kw, max_new_tokens=40)
        on, eon = _serve(params, "ngram", [12, 20, 9, 16],
                         eng_kw=eng_kw, max_new_tokens=40)
        assert eon.evictions > 0, "pool sized to force eviction"
        _assert_same_outputs(on, off)
        eon.allocator.audit()

    @pytest.mark.slow
    def test_k_longer_than_remaining_budget(self, params):
        """max_new_tokens < k: the emission clamp caps the accepted
        prefix at the budget."""
        off, _ = _serve(params, "off", [5, 11, 3], max_new_tokens=2)
        on, _ = _serve(params, {"mode": "ngram", "k": 4}, [5, 11, 3],
                       max_new_tokens=2)
        _assert_same_outputs(on, off)

    def test_max_len_cap_mid_chunk(self, params):
        """A sequence that hits max_seq_len mid-verify-chunk: writes
        past the cap route to the trash page, emission clamps, outputs
        match."""
        eng_kw = dict(max_seqs=2, max_seq_len=32, prefill_chunk=8,
                      decode_block_size=4)
        off, _ = _serve(params, "off", [20, 9], eng_kw=eng_kw,
                        max_new_tokens=12)
        on, _ = _serve(params, "ngram", [20, 9], eng_kw=eng_kw,
                       max_new_tokens=12)
        _assert_same_outputs(on, off)
        assert any(v.size == 32 for v in on.values()), \
            "workload should reach the max_seq_len cap"

    @pytest.mark.slow
    def test_eos_early_finish(self, params):
        probe = _serve(params, "off", [5], max_new_tokens=2)[0]
        eos = int(next(iter(probe.values()))[-2])
        kw = dict(max_new_tokens=30, eos_token_id=eos)
        off, _ = _serve(params, "off", [5, 9], **kw)
        on, _ = _serve(params, "ngram", [5, 9], **kw)
        _assert_same_outputs(on, off)
        assert any(t[-1] == eos and t.size < 5 + 30 for t in on.values())

    @pytest.mark.slow
    def test_rollback_spanning_harvest_window(self, params):
        """Deferred harvests span several speculative blocks, each with
        data-dependent rollback — fold-back still reconstructs the
        exact sequence, and the pipelined run really defers."""
        eng_kw = dict(kv_reserve="worst_case", harvest_interval=4)
        off, _ = _serve(params, "off", [4, 6], eng_kw=eng_kw,
                        max_new_tokens=24)
        on, eng = _serve(params, "ngram", [4, 6], eng_kw=eng_kw,
                         max_new_tokens=24)
        _assert_same_outputs(on, off)
        st = eng.host_stats
        assert st.harvests < st.spec_dispatches, (
            f"harvests={st.harvests} should defer across "
            f"{st.spec_dispatches} speculative dispatches")


class TestSampledParity:
    """Seeded sampling with speculation on: pipelined and unpipelined
    dispatch sequences are identical (the PR-5 oracle), so outputs are
    bit-identical; vs spec-off the distribution (not the stream) is
    preserved — covered by TestVerifyDistribution."""

    def test_ngram_pipeline_on_off_bit_identical(self, params):
        kw = dict(max_new_tokens=9, do_sample=True, temperature=0.8,
                  top_k=8, top_p=0.9)
        on, _ = _serve(params, "ngram", [4, 12, 3], pipeline=True, **kw)
        off, _ = _serve(params, "ngram", [4, 12, 3], pipeline=False, **kw)
        _assert_same_outputs(on, off)

    @pytest.mark.slow
    def test_draft_pipeline_on_off_bit_identical(self, params,
                                                 draft_params):
        kw = dict(max_new_tokens=9, do_sample=True, temperature=0.9,
                  top_k=12)
        on, _ = _serve(params, "draft", [4, 12, 3], pipeline=True,
                       draft_params=draft_params, **kw)
        off, _ = _serve(params, "draft", [4, 12, 3], pipeline=False,
                        draft_params=draft_params, **kw)
        _assert_same_outputs(on, off)

    @pytest.mark.slow
    def test_mixed_greedy_and_sampled_slots(self, params):
        """One compiled program serves heterogeneous slots; greedy
        slots must still match spec-off exactly."""
        eng_on = make(params, "ngram")
        eng_off = make(params, "off")
        outs = {}
        for eng in (eng_on, eng_off):
            ps = _prompts([5, 8], seed=3)
            u1 = eng.put_request(ps[0], max_new_tokens=8)
            eng.put_request(ps[1], max_new_tokens=8, do_sample=True,
                            temperature=0.8)
            o = {}
            while eng.has_work():
                eng.step()
                o.update(eng.get_outputs())
            o.update(eng.get_outputs())
            outs[eng] = (o, u1)
        (o_on, u1), (o_off, _) = outs[eng_on], outs[eng_off]
        np.testing.assert_array_equal(o_on[u1], o_off[u1],
                                      err_msg="greedy slot diverged")


class TestSteadyState:
    def _decode_phase(self, params, spec, **mk):
        eng = make(params, spec, max_seqs=2, decode_block_size=4,
                   harvest_interval=4, kv_reserve="worst_case", **mk)
        for p in _prompts([4, 6], seed=5):
            eng.put_request(p, max_new_tokens=24)
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

    def test_spec_decode_stays_pipelined(self, params):
        eng = self._decode_phase(params, "ngram")
        st = eng.host_stats
        assert st.spec_dispatches >= 2
        # one carry upload set (10 arrays + hist) per pipeline ENTRY —
        # variable emission means a finish can tear the loop down and
        # re-enter (bounded by harvests), but steady state must never
        # regress to the unpipelined per-dispatch upload rate
        assert st.meta_uploads <= 11 * max(st.harvests, 1), (
            st.meta_uploads, st.harvests)
        assert st.meta_uploads < 10 * st.dispatches
        assert st.blocking_gets < st.dispatches
        assert st.harvests == st.blocking_gets

    def test_spec_stats_reported(self, params):
        eng = self._decode_phase(params, "ngram")
        stages = eng.serving_stages()
        spec = stages["speculation"]
        for key in ("spec_dispatches", "draft_ms", "verify_ms",
                    "proposed", "accepted", "acceptance_rate",
                    "mean_accepted_len", "effective_tokens_per_dispatch"):
            assert key in spec, spec
        assert spec["proposed"] > 0
        assert stages["verify_ms"] >= 0

    def test_no_recompile_after_warmup(self, params):
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, "ngram", max_seqs=3)
        sizes = [5, 11, 3, 7]
        eng.generate_all(_prompts(sizes, seed=3), max_new_tokens=8)
        with count_compiles() as compiles:
            eng.generate_all(_prompts(sizes, seed=3), max_new_tokens=8)
        assert compiles.count == 0, (
            f"{compiles.count} recompilations in the warmed speculative "
            "steady state")


class TestConfigAndValidation:
    def test_defaults(self):
        cfg = load_inference_config(None)
        assert cfg.v2.speculation.mode == "off"
        assert cfg.v2.speculation.k == 4
        assert cfg.v2.speculation.ngram == 3

    @pytest.mark.parametrize("bad", [{"mode": "nope"}, {"k": 0},
                                     {"ngram": 0}])
    def test_validation(self, bad):
        with pytest.raises(Exception):
            load_inference_config({"v2": {"speculation": bad}})

    def test_engine_consumes_config_subtree(self, params):
        eng = RaggedInferenceEngineV2(
            LlamaForCausalLM(CFG), params=params, max_seqs=2,
            max_seq_len=64, prefill_chunk=8,
            config={"v2": {"speculation": {"mode": "ngram", "k": 2,
                                           "ngram": 2}}})
        assert eng.spec_mode == "ngram"
        assert eng.spec_k == 2 and eng.spec_ngram == 2
        # explicit kwarg wins over the config subtree
        eng2 = RaggedInferenceEngineV2(
            LlamaForCausalLM(CFG), params=params, max_seqs=2,
            max_seq_len=64, prefill_chunk=8,
            speculation=SpeculationConfig(mode="off"),
            config={"v2": {"speculation": {"mode": "ngram"}}})
        assert eng2.spec_mode == "off"

    def test_draft_mode_requires_draft_model(self, params):
        with pytest.raises(ValueError, match="draft model"):
            make(params, "draft", draft_model=None, draft_params=None)

    def test_draft_vocab_mismatch_rejected(self, params):
        bad = get_config("tinyllama", vocab_size=32, hidden_size=16,
                         intermediate_size=32, num_hidden_layers=1,
                         num_attention_heads=2, num_key_value_heads=1,
                         dtype=jnp.float32, param_dtype=jnp.float32,
                         scan_layers=False, remat=False,
                         use_flash_attention=False)
        with pytest.raises(AssertionError, match="vocab"):
            make(params, "draft", draft_model=LlamaForCausalLM(bad),
                 draft_params=None)


class TestUlyssesCommBytes:
    """Uneven-head Ulysses a2a satellite: the byte accounting (the mesh
    parity test lives in sequence_parallelism/test_ulysses.py)."""

    def test_uneven_kv_bytes_at_kv_head_rate(self):
        from deepspeed_tpu.sequence import ulysses_comm_bytes

        plan = ulysses_comm_bytes((2, 8, 64, 16), (2, 2, 64, 16), sp=4)
        # replicate ships H/sp=2 kv-head-pairs/device; once ships the
        # single kv head each query block consumes
        assert plan["kv_bytes_once"] < plan["kv_bytes_replicate"]
        assert plan["kv_once_ratio"] == 0.5
        assert plan["total_once"] < plan["total_replicate"]

    def test_even_heads_unchanged(self):
        from deepspeed_tpu.sequence import ulysses_comm_bytes

        plan = ulysses_comm_bytes((2, 8, 64, 16), (2, 4, 64, 16), sp=4)
        assert "kv_bytes_even" in plan

    def test_uneven_plan_covers_every_query_head(self):
        from deepspeed_tpu.sequence.layer import _uneven_kv_plan

        for H, Hkv, sp in [(8, 2, 4), (16, 2, 8), (12, 3, 4),
                           (8, 2, 8)]:
            idx, lmap, m = _uneven_kv_plan(H, Hkv, sp)
            g, Hl = H // Hkv, H // sp
            assert idx.shape == (sp * m,)
            for r in range(sp):
                dev_heads = idx[r * m:(r + 1) * m]
                for j in range(Hl):
                    want = (r * Hl + j) // g
                    assert dev_heads[lmap[r, j]] == want, (H, Hkv, sp, r,
                                                          j)
