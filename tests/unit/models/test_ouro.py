"""Ouro (a looped model: 48 layers run four times a token at full size; 2
layers three times here) against the benchmark's plain float32 reference,
through ``apply`` and through the ragged engine's paged cache with a K/V
slot for every (pass, layer); that a served row's logits depend on no other
row of its tick; and that each way a wrong program could differ — the one
only a cache can make among them — FAILS the shipped tolerance.

Everything is float32 with no kernel, so the comparison is about the
mathematics alone.  ``TOL`` = 2e-4: float32 rounding through two layers
three times at logits of order 1 (the figure ``test_olmoe.py`` and
``benchmark/tests/test_reference.py`` hold the other families to); every
mutation moves a logit by over a hundred times that.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.linen import initializers as nn_init

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ouro as ref  # noqa: E402
from deepspeed_tpu.inference.v2.ragged_engine import \
    RaggedInferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import llama, ouro  # noqa: E402
from deepspeed_tpu.telemetry import trace  # noqa: E402

TOL = 2e-4
U = 3
# the reference's view of the tiny preset: the source's key names
CFG = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
           rms_norm_eps=1e-6, rope_theta=1000000.0, total_ut_steps=U,
           early_exit_threshold=1.0)
PLAIN = dict(dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False,
             remat=False, use_flash_attention=False)
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


@pytest.fixture(scope="module")
def served():
    model = ouro.OuroForCausalLM(ouro.get_config("tinyouro", **PLAIN))
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales away from their initial 1 and a gate away from 0, so
    # that every norm's place and the gate's form show
    rng = np.random.default_rng(1)
    scale = lambda: jnp.asarray(rng.uniform(0.5, 1.5, size=32), jnp.float32)
    for i in range(2):
        for name in NORMS:
            params["model"][f"layers_{i}"][name]["scale"] = scale()
    params["model"]["norm"]["scale"] = scale()
    params["model"]["early_exit_gate"]["bias"] = jnp.asarray([-0.7])
    return model, params


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


def _apply(served, toks):
    model, params = served
    return np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([toks], np.int32))[0])


def _engine(served, **kw):
    model, params = served
    return RaggedInferenceEngineV2(
        model, params=params, **{**dict(max_seqs=4, max_seq_len=64,
                                        prefill_chunk=8, page_size=8,
                                        decode_block_size=4), **kw})


def test_the_presets_are_looped_models():
    tiny, big = ouro.get_config("tinyouro"), ouro.get_config("ouro-2.6b")
    assert (tiny.total_ut_steps, tiny.hidden_size, tiny.num_hidden_layers,
            tiny.head_dim, tiny.vocab_size) == (U, 32, 2, 8, 96)
    assert (big.total_ut_steps, big.early_exit_threshold, big.head_dim,
            big.num_hidden_layers, big.num_key_value_heads,
            big.intermediate_size, big.vocab_size, big.rope_theta) == (
                4, 1.0, 128, 48, 16, 5632, 49152, 1e6)
    assert big.sandwich_norm and big.sliding_window is None
    n = ouro.count_params(jax.eval_shape(
        ouro.OuroForCausalLM(big).init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"])
    # 48 x (51,380,224 + 4 x 2048) + embedding + head + final norm + gate
    assert n == 48 * 51388416 + 2 * 49152 * 2048 + 2048 + 2049


def test_a_threshold_under_one_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        ouro.get_config("tinyouro", early_exit_threshold=0.9)


def test_scanned_layers_are_refused_by_name():
    with pytest.raises(AssertionError, match="serving form only"):
        ouro.get_config("tinyouro", scan_layers=True)


def test_the_first_values_are_the_modules_own():
    """What ``init`` draws (the module docstring says why): an embedding
    at the stream's own size, the two post-norms small, attention's the
    smaller, every other norm scale 1, the gate's bias 0."""
    model = ouro.OuroForCausalLM(ouro.get_config("tinyouro", **PLAIN))
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 np.zeros((1, 8), np.int32))["params"]
    inner = params["model"]
    assert abs(float(jnp.std(inner["embed_tokens"]["embedding"]))
               - ouro.EMBED_INIT_STD) < 0.05
    want = {"input_layernorm": 1.0, "post_attention_layernorm": 1.0,
            "input_layernorm_2": ouro.ATTN_POST_NORM_INIT,
            "post_attention_layernorm_2": ouro.MLP_POST_NORM_INIT}
    assert 0 < ouro.ATTN_POST_NORM_INIT < ouro.MLP_POST_NORM_INIT < 1
    for i in range(2):
        for name in NORMS:
            scale = np.asarray(inner[f"layers_{i}"][name]["scale"])
            assert scale.dtype == np.float32 and np.all(
                scale == np.float32(want[name])), (i, name)
    assert np.all(np.asarray(inner["norm"]["scale"]) == 1.0)
    assert np.all(np.asarray(inner["early_exit_gate"]["bias"]) == 0.0)


def test_matrices_are_drawn_in_float32_because_bfloat16_draws_are_off_centre():
    """A normal drawn in bfloat16 directly lies about 1.2 % of a standard
    deviation below zero: a rank-one all-ones part in every matrix, which
    192 block applications turn into the whole stream.  ``in_float32``
    draws centred and rounds; the model's bf16 kernels come from it."""
    key, shape = jax.random.PRNGKey(0), (1024, 2048)
    sd = 1.0 / np.sqrt(shape[0])
    err = 4 * sd / np.sqrt(shape[0] * shape[1])      # four standard errors
    plain = nn_init.lecun_normal()(key, shape, jnp.bfloat16)
    mine = ouro.in_float32(nn_init.lecun_normal())(key, shape, jnp.bfloat16)
    assert mine.dtype == jnp.bfloat16
    assert abs(float(jnp.mean(mine.astype(jnp.float32)))) < err
    assert abs(float(jnp.mean(plain.astype(jnp.float32)))) > 2 * err
    model = ouro.OuroForCausalLM(ouro.get_config(
        "tinyouro", **dict(PLAIN, param_dtype=jnp.bfloat16),
        hidden_size=256, intermediate_size=512, vocab_size=4096))
    params = jax.jit(model.init)(key, np.zeros((1, 8), np.int32))["params"]
    drawn = [a for a in jax.tree_util.tree_leaves(params)
             if a.dtype == jnp.bfloat16]
    assert len(drawn) == 2 * 7 + 2
    pooled = np.concatenate([np.asarray(a, np.float32).ravel()
                             / np.asarray(a, np.float32).std()
                             for a in drawn])
    assert abs(pooled.mean()) < 4 / np.sqrt(pooled.size)


def test_apply_matches_the_plain_reference(served):
    toks = _tokens(40)
    want = np.asarray(ref.logits(served[1], CFG, toks))
    assert np.abs(_apply(served, toks) - want).max() < TOL


def test_one_pass_without_post_norms_is_llama_to_the_bit():
    kw = dict(ouro.PRESETS["tinyouro"], **PLAIN)
    kw.pop("total_ut_steps")
    lm = llama.LlamaForCausalLM(llama.LlamaConfig(**kw))
    looped = ouro.OuroForCausalLM(ouro.OuroConfig(
        **kw, total_ut_steps=1, sandwich_norm=False))
    toks = np.asarray([_tokens(24)], np.int32)
    params = lm.init(jax.random.PRNGKey(2), toks)["params"]
    mine = looped.init(jax.random.PRNGKey(3), toks)["params"]
    assert set(mine["model"]) - set(params["model"]) == {"early_exit_gate"}
    both = {"model": {**params["model"],
                      "early_exit_gate": mine["model"]["early_exit_gate"]},
            "lm_head": params["lm_head"]}
    assert np.array_equal(np.asarray(lm.apply({"params": params}, toks)),
                          np.asarray(looped.apply({"params": both}, toks)))


def test_chunked_prefill_then_paged_decode_matches_the_reference(served):
    """A 21-token prompt prefilled in chunks of 8 over pages of 8 (it
    crosses two chunk and two page boundaries), then 10 tokens decoded
    through the paged cache (fused ticks and decode blocks): every greedy
    token is the reference's own argmax of its full forward pass, or
    within the tolerance of it."""
    eng = _engine(served)
    prompt = _tokens(21, seed=3)
    out = eng.generate_all([np.asarray(prompt, np.int32)],
                           max_new_tokens=10)
    generated = list(out[min(out)])[len(prompt):]
    assert len(generated) == 10
    assert ref.generated_gaps(served[1], CFG, prompt, generated).max() < TOL
    loop = eng.serving_stages()["loop"]
    assert (loop["ut_steps"], loop["kv_slots"]) == (U, U * 2)
    assert "moe" not in eng.serving_stages()


def _first_tick_logits(eng, prompts, which):
    """The fused tick's selected logits of request ``which`` when
    ``prompts`` are admitted together."""
    for p in prompts:
        eng.put_request(np.asarray(p, np.int32), max_new_tokens=4)
    eng._admit()
    arrays, _, sampling, _, _ = eng._pack_tick(eng._plan_rows())
    # the logits tick takes the sampling tick's arrays less the two that
    # say where tokens come from and go to
    token_ids, _tok_src, *meta, _sample_slot = arrays
    sel, eng.kv.cache, _, cdf = eng.fused_step()(
        eng.params, eng.kv.cache, token_ids, *meta)
    j = [j for r, j in sampling if r.uid == which][0]
    return np.asarray(sel[j]), np.asarray(cdf)


def test_a_served_rows_logits_do_not_depend_on_its_tick(served):
    """One 6-token request alone in its tick (the other 6 rows of the 12
    are padding, bound for the trash page's slots) and the same request
    beside three others that fill the tick: the same logits, and the
    reference's.  The gate's sums count live rows only."""
    prompt = _tokens(6, seed=7)
    alone, cdf = _first_tick_logits(_engine(served), [prompt], 0)
    pdf = ref.exit_pdf(served[1], CFG, prompt)
    assert np.abs(cdf - np.cumsum(pdf, axis=1)[:, :-1].sum(0)).max() < 1e-4
    others = [_tokens(2, seed=s) for s in (8, 9, 10)]
    full, _ = _first_tick_logits(_engine(served),
                                 others[:2] + [prompt] + others[2:], 2)
    assert np.abs(alone - full).max() < TOL
    want = np.asarray(ref.logits(served[1], CFG, prompt, last=1))[0]
    assert np.abs(alone - want).max() < TOL


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_tolerance(served, mutate):
    """One pass fewer, the final norm once at the end only, the two
    post-norms left out, RoPE theta 10,000: each moves the logits by over
    a hundred times the tolerance."""
    toks = _tokens(40)
    wrong = np.asarray(ref.logits(served[1], CFG, toks, mutate=mutate))
    assert np.abs(wrong - _apply(served, toks)).max() > 100 * TOL, mutate


def test_an_engine_whose_passes_share_a_kv_slot_fails_the_tolerance(
        served, monkeypatch):
    """The mutation only a cache can make: every pass reads and writes
    slot 0 of its layer's page, so a pass finds, for the earlier
    positions, the keys and values the LAST pass left there.  (Within one
    prefill chunk nothing shows: a pass writes the chunk's rows before it
    reads them.  So the prompt spans chunks and the answer is decoded.)"""
    from deepspeed_tpu.inference import paged
    sound = paged.paged_update_and_attend
    monkeypatch.setattr(
        paged, "paged_update_and_attend",
        lambda mdl, q, k, v, meta, cfg: sound(
            mdl, q, k, v,
            {**meta, "kv_slot": jnp.zeros_like(meta["kv_slot"])}, cfg))
    eng = _engine(served)
    prompt = _tokens(21, seed=3)
    out = eng.generate_all([np.asarray(prompt, np.int32)],
                           max_new_tokens=10)
    generated = list(out[min(out)])[len(prompt):]
    gaps = ref.generated_gaps(served[1], CFG, prompt, generated)
    assert gaps.max() > 100 * TOL


def test_lower_precision_fails_the_tolerance(served):
    """The reference with every matmul operand rounded to e4m3, the
    nearest precision below the configuration's, is not the model."""
    toks = _tokens(40)
    low = np.asarray(ref.logits(served[1], CFG, toks, precision="float8"))
    assert np.abs(low - _apply(served, toks)).max() > 100 * TOL


def test_exit_pdf_sums_to_one_and_the_loop_spans_carry_its_cdf(served):
    """``exit_pdf`` is a distribution over the passes, and the
    ``engine.loop`` spans of a served request add up to the reference's
    cumulative exit probabilities over the positions the engine ran: the
    21 prompt tokens and all but the last of the 10 generated."""
    prompt = _tokens(21, seed=3)
    trace.configure(enabled=True)
    try:
        trace.clear()
        eng = _engine(served)
        out = eng.generate_all([np.asarray(prompt, np.int32)],
                               max_new_tokens=10)
        spans = [e for e in trace.snapshot() if e["name"] == "engine.loop"]
        dispatches = [e["args"] for e in trace.snapshot()
                      if e["name"] == "engine.dispatch"]
    finally:
        trace.configure(enabled=False)
        trace.clear()
    seq = list(out[min(out)])
    pdf = ref.exit_pdf(served[1], CFG, seq[:-1])
    assert pdf.shape == (30, U) and np.abs(pdf.sum(1) - 1).max() < 1e-6
    assert pdf.min() > 0.01          # the gate is not saturated here
    assert {s["args"]["program"] for s in spans} == {"fused", "decode_block"}
    got = [sum(s["args"][f"exit_cdf_{u}"] for s in spans)
           for u in range(1, U)]
    want = np.cumsum(pdf, axis=1)[:, :-1].sum(0)
    assert np.abs(np.asarray(got) - want).max() < 1e-3
    assert sum(s["args"]["rows"] for s in spans
               if s["args"]["program"] == "fused") == 21
    # every dispatch of a looped model says how many passes and slots,
    # and what the allocator held
    assert all(d["ut_steps"] == U and d["kv_slots"] == 2 * U and
               0 < d["pages_in_use"] <= d["pages_total"] == 32
               for d in dispatches)
    assert eng.serving_stages()["loop"]["rows"] >= 21


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
