"""Million-token context: partial residency — the tiered KV store as
virtual memory for attention.

The long_context tentpole's contracts:

- **Parity**: a partially-resident decode (sinks + recent window in
  HBM, middle parked in the spill tiers, streamed back through the
  chunked attention scan) is BIT-IDENTICAL to the fully-resident
  control — greedy and seeded sampling, full-width and quantized pools
  (the flash-attention m/l/acc carry fold is exact, not approximate).
- **Capacity inversion**: a single sequence whose KV exceeds the HBM
  pool by >= 4x decodes end-to-end; admission asks only that the
  resident window fits HBM and the total fits the combined tiers.
- **Named rejections**: validate_request names the resident-window
  HBM bound and the combined-tier bound separately.
- **Conservation**: page/refcount audits stay clean every step while
  parked groups come and go, including under prefix-cache COW and
  concurrent normal traffic.
- **Integrity**: parked pages are digest-verified on every page-in; a
  transient bitflip heals by re-read with no output change.
- **Fixed shapes**: the chunked multi-dispatch scan compiles a bounded
  program set — steady state adds zero new compilations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.resilience import faults

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=512, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=False, remat=False,
                 use_flash_attention=False)

# sink 1 + window 2 + chunk 2 + 1 staging = 6 resident pages (96 tokens)
LC_TIER = {"host_pages": 256, "long_context": True,
           "sink_pages": 1, "window_pages": 2, "chunk_pages": 2}


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


def make(params, tiering=None, num_pages=24, fmt="none", prefix=None,
         **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 512)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", 16)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("kv_reserve", "on_demand")
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=False, num_pages=num_pages,
                                   kv_cache_dtype=fmt, kv_tiering=tiering,
                                   prefix_cache=prefix,
                                   rng=jax.random.PRNGKey(11), **kw)


def _prompt(size, seed=3):
    return np.random.default_rng(seed).integers(
        1, 64, size=(size,), dtype=np.int32)


def _serve(eng, prompts, audit=True, **req_kw):
    req_kw.setdefault("max_new_tokens", 40)
    for p in prompts:
        eng.put_request(p, **req_kw)
    outs, steps = {}, 0
    while eng.has_work():
        eng.step()
        outs.update(eng.get_outputs())
        if audit:
            eng.audit_kv_sharing()
        steps += 1
        assert steps < 8000, "engine made no progress"
    outs.update(eng.get_outputs())
    return outs


def _assert_same_outputs(a, b):
    assert sorted(a) == sorted(b), (sorted(a), sorted(b))
    for uid in a:
        np.testing.assert_array_equal(a[uid], b[uid],
                                      err_msg=f"uid {uid}")


# -- parity ---------------------------------------------------------------


class TestParity:

    def test_greedy_parity_vs_fully_resident(self, params):
        """200-token prompt + 48 new = 16 KV pages on a 7-usable-page
        HBM pool: the middle parks and streams back through the chunked
        scan; greedy output equals the fully-resident control exactly."""
        p = _prompt(200)
        ref = _serve(make(params, num_pages=24), [p], max_new_tokens=48)
        eng = make(params, tiering=dict(LC_TIER), num_pages=8)
        out = _serve(eng, [p], max_new_tokens=48)
        _assert_same_outputs(ref, out)
        st = eng.serving_stages()["kv_tiering"]
        assert st["pageins"] > 0, "parity run must exercise page-in"
        assert st["spills"] > 0, "parity run must park middle groups"
        eng.close()

    @pytest.mark.slow
    def test_seeded_sampling_parity(self, params):
        """Sampling keys depend only on (engine seed, uid, position) —
        partial residency must not perturb the stream."""
        p = _prompt(200)
        kw = dict(do_sample=True, temperature=0.8, top_k=10,
                  max_new_tokens=40)
        ref = _serve(make(params, num_pages=24), [p], **kw)
        out = _serve(make(params, tiering=dict(LC_TIER), num_pages=8),
                     [p], **kw)
        _assert_same_outputs(ref, out)

    def test_4x_over_hbm_decodes_end_to_end(self, params):
        """The acceptance bar: one sequence at >= 4x the HBM pool
        decodes to its full budget with clean audits throughout."""
        eng = make(params, tiering=dict(LC_TIER), num_pages=8)
        outs = _serve(eng, [_prompt(400)], max_new_tokens=56)
        (_, toks), = outs.items()
        assert toks.size == 456
        usable_tokens = (8 - 1) * 16
        assert toks.size >= 4 * usable_tokens
        st = eng.serving_stages()["kv_tiering"]
        assert st["pageins"] > 0 and st["pagein_pages"] > 0
        assert st["pagein_wait_s"] >= 0
        eng.close()

    @pytest.mark.slow
    def test_mixed_lc_and_normal_traffic(self, params):
        """An LC sequence decodes alongside normal fully-resident
        requests; every stream matches its solo-run control."""
        long_p, shorts = _prompt(200), [_prompt(12, 5), _prompt(20, 6)]
        ref = list(_serve(make(params, num_pages=40), [long_p],
                          max_new_tokens=40).values())
        ref += list(_serve(make(params, num_pages=40), shorts,
                           max_new_tokens=16).values())
        eng = make(params, tiering=dict(LC_TIER), num_pages=12)
        for p in shorts:
            eng.put_request(p, max_new_tokens=16)
        eng.put_request(long_p, max_new_tokens=40)
        outs, steps = {}, 0
        while eng.has_work():
            eng.step()
            outs.update(eng.get_outputs())
            eng.audit_kv_sharing()
            steps += 1
            assert steps < 8000
        outs.update(eng.get_outputs())
        by_len = {v.size: v for v in ref}
        assert len(outs) == 3
        for v in outs.values():
            np.testing.assert_array_equal(v, by_len[v.size])
        eng.close()


# -- admission ------------------------------------------------------------


class TestAdmission:

    def test_rejection_names_resident_window(self, params):
        """The resident window (sink + window + chunk + 1 = 6 pages)
        must fit HBM: 5 usable pages reject, 6 accept."""
        small = make(params, tiering=dict(LC_TIER), num_pages=6)
        with pytest.raises(ValueError,
                           match="partial-residency window"):
            small.put_request(_prompt(100), max_new_tokens=60)
        small.close()
        fits = make(params, tiering=dict(LC_TIER), num_pages=7)
        assert fits.put_request(_prompt(100), max_new_tokens=60) >= 0
        fits.close()

    def test_rejection_names_combined_tiers(self, params):
        """Total KV beyond HBM + host + NVMe rejects naming every tier
        budget; one page under the cap accepts."""
        tier = dict(LC_TIER, host_pages=4)
        eng = make(params, tiering=tier, num_pages=8)
        # cap = 7 usable + 4 host = 11 pages = 176 tokens
        with pytest.raises(ValueError, match="combined tiers"):
            eng.put_request(_prompt(120), max_new_tokens=60)
        assert eng.put_request(_prompt(120), max_new_tokens=56) >= 0
        eng.close()

    def test_small_requests_unaffected(self, params):
        """A request that fits HBM outright never touches the LC path
        even on an LC-armed engine."""
        eng = make(params, tiering=dict(LC_TIER), num_pages=8)
        uid = eng.put_request(_prompt(20), max_new_tokens=16)
        assert not eng.waiting[-1].lc
        outs = _serve(eng, [])
        assert outs[uid].size == 36
        eng.close()

    def test_knobs_registered(self, params):
        """Satellite: the prefetch lookahead (old hardcoded islice 8)
        and the residency window are autotuner knobs."""
        eng = make(params, tiering=dict(LC_TIER), num_pages=8)
        reg = eng.knob_registry()
        assert "kv.prefetch_lookahead" in reg
        assert "kv.window_pages" in reg
        assert reg.value("kv.prefetch_lookahead") == 8
        reg.set("kv.prefetch_lookahead", 2)
        assert eng.prefetch_lookahead == 2
        reg.set("kv.window_pages", 3)
        assert eng.kv.tier_cfg.window_pages == 3
        eng.close()


# -- composition ----------------------------------------------------------


class TestComposition:

    @pytest.mark.parametrize(
        "fmt", [pytest.param(f, marks=pytest.mark.slow)
                for f in ("int8", "fp8")])
    def test_quantized_pool_parity(self, params, fmt):
        """Parked quantized pages (payload + scale rows) survive the
        park/page-in cycle byte-identically: LC output equals the
        fully-resident QUANTIZED control."""
        p = _prompt(200)
        ref = _serve(make(params, num_pages=24, fmt=fmt), [p])
        eng = make(params, tiering=dict(LC_TIER), num_pages=8, fmt=fmt)
        out = _serve(eng, [p])
        _assert_same_outputs(ref, out)
        eng.close()

    @pytest.mark.slow
    def test_transient_bitflip_on_pagein_heals(self, params):
        """A flipped bit in a parked group's working copy is caught by
        the per-page digest at page-in and healed by re-read — the tier
        copy stays authoritative, the output stays exact."""
        p = _prompt(200)
        ref = _serve(make(params, num_pages=24), [p])
        with faults.FaultInjector(seed=5) as inj:
            inj.bitflip("kv.read_page", bits=1, count=1)
            eng = make(params, tiering=dict(LC_TIER), num_pages=8)
            out = _serve(eng, [p])
        st = eng.serving_stages()["kv_tiering"]
        assert st["rereads"] >= 1, "fault must have fired"
        assert st["reread_recovered"] >= 1
        assert st["quarantined"] == 0
        _assert_same_outputs(ref, out)
        eng.close()

    def test_conservation_under_prefix_cow_and_spill_pressure(
            self, params):
        """LC decode + shared-prefix normal traffic + whole-session
        spill pressure at once: refcount/page audits hold every step,
        and the drained engine leaves no live refs or parked payload."""
        r = np.random.default_rng(9)
        sys_p = r.integers(1, 64, size=(32,), dtype=np.int32)
        shared = [np.concatenate(
            [sys_p, r.integers(1, 64, size=(12,), dtype=np.int32)])
            for _ in range(4)]
        shared[3] = shared[0].copy()          # full match -> COW
        eng = make(params, tiering=dict(LC_TIER), num_pages=14,
                   prefix=True)
        eng.put_request(_prompt(200), max_new_tokens=40)
        for p in shared:
            eng.put_request(p, max_new_tokens=16)
        steps = 0
        while eng.has_work():
            eng.step()
            eng.get_outputs()
            eng.allocator.audit()
            eng.tiering.audit()
            eng.audit_kv_sharing()
            steps += 1
            assert steps < 8000
        fin = eng.audit_kv_sharing()
        assert fin["referenced"] == eng.kv.pfx.stats()["resident_entries"]
        assert eng.tiering.audit()["sessions"] == 0, (
            "drained run leaves no parked payload")
        eng.close()
        assert eng.allocator.audit(external={})["referenced"] == 0

    def test_zero_new_compiles_steady_state(self, params):
        """The chunked scan is a bounded program set (embed / chunk /
        finish+-carry / head x two query shapes): a second LC request
        recompiles nothing."""
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, tiering=dict(LC_TIER), num_pages=8)
        p = _prompt(200)
        _serve(eng, [p], audit=False)
        assert eng.serving_stages()["kv_tiering"]["pageins"] > 0
        with count_compiles() as compiles:
            _serve(eng, [p], audit=False)
        assert compiles.count == 0, (
            f"{compiles.count} recompilations in LC steady state — the "
            "chunked-scan programs must be fixed-shape")
        eng.close()
