"""Serving quantization: fp8/int8 paged KV pools and int8/fp8/fp6
weight-only serving (reference csrc/fp_quantizer selective_dequant,
inference/v2 cuda_linear FP6 GEMM, replace_with_quantized_linear)."""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=False, remat=False,
                 use_flash_attention=False)


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(3),
                               np.zeros((1, 8), np.int32))


def _prompts(sizes, seed=0):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


class _PagedHarness(nn.Module):
    """Minimal module around paged_update_and_attend for KV-quant math."""

    cfg: object

    @nn.compact
    def __call__(self, q, k, v, ragged_meta):
        from deepspeed_tpu.inference.paged import paged_update_and_attend

        return paged_update_and_attend(self, q, k, v, ragged_meta,
                                       self.cfg)


@pytest.mark.parametrize("fmt,tol", [("fp8", 0.04), ("int8", 0.02)])
def test_kv_quant_attention_close_to_exact(fmt, tol):
    """Quantized paged KV (per-row-per-head scales) reproduces exact
    attention within the format's relative error."""
    T, H, Hkv, D, P, page = 8, 4, 2, 16, 5, 4
    cfg = dataclasses.replace(CFG, kv_num_pages=P, kv_page_size=page)
    qcfg = dataclasses.replace(cfg, kv_cache_dtype=fmt)
    rng = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (1, H, T, D), jnp.float32)
    k = jax.random.normal(kk, (1, Hkv, T, D), jnp.float32)
    v = jax.random.normal(kv_, (1, Hkv, T, D), jnp.float32)
    # one sequence of 8 tokens in pages 1..2
    meta = {"kv_lens": jnp.asarray([8], jnp.int32),
            "page_indices": jnp.asarray([[1, 2]], jnp.int32),
            "cu_q_lens": jnp.asarray([0, 8], jnp.int32),
            "num_seqs": jnp.asarray([1], jnp.int32),
            "new_kv_dest": jnp.asarray(
                [4, 5, 6, 7, 8, 9, 10, 11], jnp.int32)}

    outs = {}
    for c in (cfg, qcfg):
        m = _PagedHarness(c)
        vars_ = m.init(jax.random.PRNGKey(1), q, k, v, meta)
        y, _ = m.apply(vars_, q, k, v, meta, mutable=["cache"])
        outs[c.kv_cache_dtype] = np.asarray(y)
    exact = outs["none"]
    got = outs[fmt]
    rel = np.abs(got - exact).max() / max(np.abs(exact).max(), 1e-6)
    assert rel < tol, f"{fmt}: relative error {rel}"


@pytest.mark.parametrize("fmt", ["fp8", "int8"])
def test_kv_quant_serving_end_to_end(params, fmt):
    """Generation over the quantized pool runs, outputs stay finite, and
    the persistent cache shrinks (fp32 pool -> 1-byte payload + scales)."""
    eng_q = RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                    max_seqs=2, max_seq_len=64,
                                    prefill_chunk=8, kv_cache_dtype=fmt,
                                    decode_block_size=4)
    eng_f = RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                    max_seqs=2, max_seq_len=64,
                                    prefill_chunk=8, decode_block_size=4)
    assert eng_q.cache_bytes() < 0.4 * eng_f.cache_bytes()
    outs = eng_q.generate_all(_prompts([5, 9], seed=1), max_new_tokens=6)
    ref = eng_f.generate_all(_prompts([5, 9], seed=1), max_new_tokens=6)
    assert len(outs) == 2
    for toks in outs.values():
        assert np.isfinite(toks).all()
    # same prompts, same params: quantization noise may flip late tokens,
    # but prompts echo exactly and the streams should mostly agree
    agree = sum(int(np.array_equal(a, b))
                for a, b in zip([outs[u] for u in sorted(outs)],
                                [ref[u] for u in sorted(ref)]))
    assert agree >= 1


@pytest.mark.parametrize("fmt,tol", [("int8", 0.06), ("fp8", 0.2),
                                     ("fp6", 0.35)])
def test_weight_quant_logits_close(params, fmt, tol):
    """v1 engine weight-only quantization: full-sequence logits stay
    within the format's error envelope of the fp32 serve — AND the
    quantization actually engages (nonzero error), guarding against the
    min_size filter silently passing weights through."""
    ids = np.asarray([_prompts([12], seed=2)[0]])
    ref_eng = deepspeed_tpu.init_inference(
        model=LlamaForCausalLM(CFG), params=params, dtype="float32")
    ref = np.asarray(ref_eng.forward(ids))
    q_eng = deepspeed_tpu.init_inference(
        model=LlamaForCausalLM(CFG), params=params, dtype="float32",
        quant={"enabled": True, "qtype": fmt})
    got = np.asarray(q_eng.forward(ids))
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert 1e-6 < rel < tol, f"{fmt}: relative logits error {rel}"


def test_weight_quant_ragged_engine(params):
    """v2 engine weight quantization serves end to end."""
    eng = RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                  max_seqs=2, max_seq_len=64,
                                  prefill_chunk=8, decode_block_size=4,
                                  quantize_weights="int8")
    outs = eng.generate_all(_prompts([5, 9], seed=3), max_new_tokens=6)
    assert len(outs) == 2
    for toks in outs.values():
        assert np.isfinite(toks).all()


@pytest.mark.slow
def test_w8a8_native_int8_dots(params):
    """quantize_weights="w8a8" (explicit opt-in: it quantizes
    activations too) runs the NATIVE path on Llama-family models:
    kernels stay int8 in the params tree (never re-expanded per tick)
    and the traced program dots s8 x s8 — the MXU int8 path, reference
    W8A8 inference GEMM semantics."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight

    eng = RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                  max_seqs=2, max_seq_len=64,
                                  prefill_chunk=8, decode_block_size=4,
                                  quantize_weights="w8a8")
    assert eng.param_form["native"] and eng.param_form["wq"] == "w8a8"
    # weight-only int8 keeps the documented dequant semantics (no
    # silent activation quantization)
    eng_i8 = RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                     max_seqs=2, max_seq_len=64,
                                     prefill_chunk=8, decode_block_size=4,
                                     quantize_weights="int8")
    assert (not eng_i8.param_form["native"]
            and eng_i8.param_form["wq"] == "int8")
    # and w8a8 on a model without native Dense consumption fails loudly
    from deepspeed_tpu.models.gptneox import (GPTNeoXForCausalLM,
                                              get_config as neox_config)
    ncfg = neox_config("tinyneox", dtype=jnp.float32,
                       param_dtype=jnp.float32, scan_layers=False,
                       remat=False, use_flash_attention=False)
    nparams = jax.jit(GPTNeoXForCausalLM(ncfg).init)(
        jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    with pytest.raises(AssertionError, match="w8a8"):
        RaggedInferenceEngineV2(GPTNeoXForCausalLM(ncfg), params=nparams,
                                max_seqs=2, max_seq_len=64,
                                prefill_chunk=8, quantize_weights="w8a8")
    qleaves = [l for l in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QuantizedWeight))
        if isinstance(l, QuantizedWeight)]
    # kernels carry the native format; the embedding (a gather, not a
    # dot) keeps the group-wise int8 dequant fallback
    fmts = {l.fmt for l in qleaves}
    assert "w8a8" in fmts and fmts <= {"w8a8", "int8"}, fmts
    assert all(l.arrays[0].dtype == jnp.int8 for l in qleaves)

    # the decode-block program must contain an s8 x s8 dot (int32 accum)
    import re

    from deepspeed_tpu.inference.quantization import dequantize_param_tree

    def fwd(p, x):
        # exactly what the engine's step programs do: expand fallback
        # leaves, keep w8a8 kernels int8 for the model's native dots
        p = dequantize_param_tree(p, native_w8a8=True)
        return eng.model.apply(
            p if "params" in p else {"params": p}, x,
            positions=jnp.zeros((1, 2), jnp.int32),
            ragged_meta={"kv_lens": jnp.ones((2,), jnp.int32),
                         "page_indices": jnp.zeros((2, 1), jnp.int32),
                         "cu_q_lens": jnp.asarray([0, 1, 2], jnp.int32),
                         "num_seqs": jnp.asarray([2], jnp.int32),
                         "new_kv_dest": jnp.asarray([0, 1], jnp.int32)},
            mutable=["cache"])[0]

    jaxpr = str(jax.make_jaxpr(fwd)(eng.params, np.zeros((1, 2), np.int32)))
    assert re.search(r"i32\[[\d,]*\] = dot_general\[", jaxpr), \
        "no int32-accumulating int8 dot in the traced program"

    # and it still generates sanely vs the unquantized engine
    ref_eng = RaggedInferenceEngineV2(LlamaForCausalLM(CFG), params=params,
                                      max_seqs=2, max_seq_len=64,
                                      prefill_chunk=8, decode_block_size=4)
    prompts = _prompts([5, 9], seed=5)
    outs = eng.generate_all(prompts, max_new_tokens=6)
    ref = ref_eng.generate_all(prompts, max_new_tokens=6)
    assert len(outs) == 2
    for (u, toks), (_, rtoks), prompt in zip(sorted(outs.items()),
                                             sorted(ref.items()), prompts):
        assert np.isfinite(toks).all()
        np.testing.assert_array_equal(toks[:prompt.size], prompt)
        assert toks.shape == rtoks.shape


def test_w8a8_scan_stacked_params_unroll_eagerly():
    """Scan-trained checkpoints carry 3-D [L, K, N] kernels, which the
    per-channel w8a8 format cannot represent — the engine must unroll
    them at init so EVERY block kernel gets the native path (a stacked
    tree would silently fall back to dequant for 99% of the weights)."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight

    scfg = dataclasses.replace(CFG, scan_layers=True)
    model = LlamaForCausalLM(scfg)
    sparams = jax.jit(model.init)(jax.random.PRNGKey(3),
                                  np.zeros((1, 8), np.int32))
    eng = RaggedInferenceEngineV2(model, params=sparams, max_seqs=2,
                                  max_seq_len=64, prefill_chunk=8,
                                  decode_block_size=4,
                                  quantize_weights="w8a8")
    assert not eng.param_form["unroll"]      # consumed at init
    qleaves = [l for l in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QuantizedWeight))
        if isinstance(l, QuantizedWeight)]
    n_w8a8 = sum(l.fmt == "w8a8" for l in qleaves)
    # 2 layers x 5 min-size-eligible block kernels (q/o/gate/up/down;
    # the tiny GQA k/v fall under min_size) + lm_head — all 2-D after
    # the unroll.  A stacked tree would leave n_w8a8 == 1 (lm_head only)
    assert n_w8a8 == 11, [l.fmt for l in qleaves]
    outs = eng.generate_all(_prompts([5, 9], seed=6), max_new_tokens=5)
    assert len(outs) == 2
    for toks in outs.values():
        assert np.isfinite(toks).all()


def test_weight_quant_generate_matches_forward_format(params):
    """v1 generate() under quantization produces tokens consistent with
    its own quantized forward (greedy argmax of the first step)."""
    prompt = _prompts([9], seed=4)[0]
    eng = deepspeed_tpu.init_inference(
        model=LlamaForCausalLM(CFG), params=params, dtype="float32",
        quant={"enabled": True, "qtype": "int8"})
    toks = eng.generate(prompt[None], max_new_tokens=2, do_sample=False)
    logits = np.asarray(eng.forward(prompt[None]))
    assert int(toks[0, prompt.size]) == int(np.argmax(logits[0, -1]))
