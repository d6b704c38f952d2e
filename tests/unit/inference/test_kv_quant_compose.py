"""Quantized paged-KV as a first-class pool format: config plumbing,
byte-accounted sizing, and composition with every serving subsystem.

The kv_quant tentpole's contracts above the kernel:

- **Plumbing**: ``kv_cache_dtype`` resolves kwarg > config
  (``inference.v2.kv_cache_dtype``) > "none".
- **Byte accounting**: ``kv_pool_bytes`` sizes the pool by exact device
  bytes (payload + scale rows) — the same budget holds ~2x the pages
  quantized.
- **Composition**: spill/restore carries the quantized payload + scales
  digest-verified and byte-identical (a transient bitflip on the
  quantized bytes heals via re-read); the prefix cache shares and COWs
  quantized pages with clean refcount audits; the pipelined host path
  stays output-identical on a quantized pool — each with the
  zero-new-compilations guard where it applies.
- **"none" is untouched**: no scale leaves, no kv_quant stats block —
  the full-width path is structurally the pre-quantization engine.
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
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=False, remat=False,
                 use_flash_attention=False)


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


def make(params, fmt="int8", tiering=None, prefix=None, pipeline=True,
         **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", 16)
    kw.setdefault("num_pages", 9)
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("kv_reserve", "on_demand")
    return RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                   pipeline=pipeline, kv_cache_dtype=fmt,
                                   kv_tiering=tiering, prefix_cache=prefix,
                                   rng=jax.random.PRNGKey(11), **kw)


def _prompts(sizes, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


SIZES = [12, 20, 9, 16]


def _serve(eng, sizes=SIZES, **req_kw):
    req_kw.setdefault("max_new_tokens", 40)
    for p in _prompts(sizes):
        eng.put_request(p, **req_kw)
    outs = {}
    while eng.has_work():
        eng.step()
        outs.update(eng.get_outputs())
    outs.update(eng.get_outputs())
    return outs


def _assert_same_outputs(a, b):
    assert sorted(a) == sorted(b), (sorted(a), sorted(b))
    for uid in a:
        np.testing.assert_array_equal(a[uid], b[uid],
                                      err_msg=f"uid {uid}")


def _scale_leaves(cache):
    return [leaf for leaf in jax.tree_util.tree_leaves(cache)
            if leaf.ndim == 3]


# -- plumbing ------------------------------------------------------------


class TestPlumbing:

    def test_kwarg_beats_config_beats_default(self, params):
        via_cfg = make(params, fmt=None,
                       config={"v2": {"kv_cache_dtype": "int8"}})
        assert via_cfg.kv_cache_dtype == "int8"
        kwarg_wins = make(params, fmt="none",
                          config={"v2": {"kv_cache_dtype": "int8"}})
        assert kwarg_wins.kv_cache_dtype == "none"
        default = make(params, fmt=None)
        assert default.kv_cache_dtype == "none"

    def test_config_validator_rejects_unknown_format(self):
        from deepspeed_tpu.inference.config import InferenceV2Config

        with pytest.raises(ValueError, match="kv_cache_dtype"):
            InferenceV2Config(kv_cache_dtype="int4")

    def test_quant_pool_is_one_byte_plus_scales(self, params):
        eng = make(params, fmt="fp8")
        leaves = jax.tree_util.tree_leaves(eng.cache)
        payload = [leaf for leaf in leaves if leaf.ndim == 4]
        assert payload and all(
            np.dtype(leaf.dtype).itemsize == 1 for leaf in payload)
        scales = _scale_leaves(eng.cache)
        assert scales and all(leaf.dtype == jnp.float32
                              for leaf in scales)

    def test_none_path_structurally_unchanged(self, params):
        eng = make(params, fmt="none")
        assert not _scale_leaves(eng.cache)
        assert all(leaf.dtype == jnp.float32
                   for leaf in jax.tree_util.tree_leaves(eng.cache))
        _serve(eng, sizes=[12], max_new_tokens=8)
        assert "kv_quant" not in eng.serving_stages()

    def test_byte_budget_sizes_pool_exactly(self, params):
        full = make(params, fmt="none", num_pages=9)
        budget = full.cache_bytes()
        sized_f = make(params, fmt="none", num_pages=None,
                       kv_pool_bytes=budget)
        sized_q = make(params, fmt="int8", num_pages=None,
                       kv_pool_bytes=budget)
        assert sized_f.num_pages == 9
        assert sized_q.num_pages >= int(1.8 * sized_f.num_pages)
        assert sized_q.cache_bytes() <= budget
        # the accounting is exact: one more page would not have fit
        per_page = sized_q.cache_bytes() // sized_q.num_pages
        assert sized_q.cache_bytes() + per_page > budget

    def test_serving_stages_kv_quant_block(self, params):
        eng = make(params, fmt="int8")
        _serve(eng, sizes=[12, 9], max_new_tokens=10)
        kq = eng.serving_stages()["kv_quant"]
        assert kq["format"] == "int8"
        assert kq["dequant_path"] in ("pallas-quant", "xla-gather")
        assert kq["pool_bytes"] == eng.cache_bytes()
        assert kq["payload_bytes"] > 0 and kq["scale_bytes"] > 0
        assert kq["pool_bytes"] == (kq["payload_bytes"] +
                                    kq["scale_bytes"])
        assert kq["scale_rows_written"] > 0
        assert 0 < kq["scale_min"] <= kq["scale_mean"] <= kq["scale_max"]


# -- composition ---------------------------------------------------------


class TestComposition:

    @pytest.mark.parametrize(
        "fmt", ["int8", pytest.param("fp8", marks=pytest.mark.slow)])
    def test_tiering_spill_restore_byte_identical(self, params, fmt):
        """Spilling a quantized sequence and restoring it changes
        NOTHING: greedy outputs equal the never-spilled quantized run,
        and every restored page passed its digest over the quantized
        bytes."""
        off = _serve(make(params, fmt=fmt))
        eon = make(params, fmt=fmt, tiering={"host_pages": 64})
        on = _serve(eon)
        assert eon.spills > 0 and eon.restores > 0
        assert eon.evictions == 0
        _assert_same_outputs(off, on)
        st = eon.serving_stages()["kv_tiering"]
        assert st["pages_verified"] == st["pages_restored"] > 0
        assert st["bytes_spilled"] > 0
        eon.close()

    def test_tiering_transient_bitflip_heals(self, params):
        """A transient flip in a spilled QUANTIZED payload is caught by
        the sum64 digest and healed by re-read — output still exact."""
        off = _serve(make(params, fmt="int8"))
        with faults.FaultInjector(seed=5) as inj:
            inj.bitflip("kv.read_page", bits=1, count=1)
            eon = make(params, fmt="int8", tiering={"host_pages": 64})
            on = _serve(eon)
        st = eon.serving_stages()["kv_tiering"]
        assert st["rereads"] >= 1, "fault must have fired"
        assert st["reread_recovered"] >= 1
        assert st["quarantined"] == 0
        _assert_same_outputs(off, on)
        eon.close()

    def test_prefix_cache_shares_quantized_pages(self, params):
        """Shared-prefix admissions attach quantized pages (pages AND
        scales leaves), COW on divergence, outputs equal cache-off, and
        refcount audits stay clean."""
        r = np.random.default_rng(3)
        sys = r.integers(1, 64, size=(32,), dtype=np.int32)
        # 8 prompts over max_seqs=4: the second wave admits against a
        # warm index; #5 repeats #0 verbatim (full match -> COW)
        prompts = [np.concatenate(
            [sys, r.integers(1, 64, size=(16,), dtype=np.int32)])
            for _ in range(8)]
        prompts[5] = prompts[0].copy()

        def run(prefix):
            eng = make(params, fmt="int8", prefix=prefix, num_pages=21)
            for p in prompts:
                eng.put_request(p, max_new_tokens=20)
            outs = {}
            while eng.has_work():
                eng.step()
                outs.update(eng.get_outputs())
                eng.audit_kv_sharing()
            outs.update(eng.get_outputs())
            return outs, eng

        off, _ = run(None)
        on, eng = run(True)
        pc = eng.serving_stages()["prefix_cache"]
        assert pc["hit_requests"] >= 3
        assert pc["cow_copies"] >= 1, (
            "diverging decode over shared quantized pages must COW")
        _assert_same_outputs(off, on)
        # after the drain only the index's resident entries hold refs,
        # and close() releases those too
        fin = eng.audit_kv_sharing()
        assert fin["referenced"] == eng.kv.pfx.stats()["resident_entries"]
        eng.close()
        assert eng.allocator.audit(external={})["referenced"] == 0

    def test_pipeline_parity_on_quant_pool(self, params):
        on = _serve(make(params, fmt="fp8", pipeline=True))
        off = _serve(make(params, fmt="fp8", pipeline=False))
        _assert_same_outputs(on, off)

    def test_zero_new_compiles_quant_steady_state(self, params):
        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = make(params, fmt="int8", tiering={"host_pages": 64})
        prompts = _prompts(SIZES)
        eng.generate_all(prompts, max_new_tokens=40)
        assert eng.spills > 0, "warmup must exercise the spill path"
        with count_compiles() as compiles:
            eng.generate_all(prompts, max_new_tokens=40)
        assert compiles.count == 0, (
            f"{compiles.count} recompilations in quantized steady state — "
            "the quantized read/spill programs must be fixed-shape")
        eng.close()

    def test_quant_run_deterministic(self, params):
        """Same engine seed + quantized pool twice = identical streams
        (the quantization is deterministic, not a noise source)."""
        a = _serve(make(params, fmt="fp8"))
        b = _serve(make(params, fmt="fp8"))
        _assert_same_outputs(a, b)
