"""OLMoE (64 small experts top-8 at full size; 8 experts top-2 here) against
the benchmark's plain float32 reference, through ``apply`` and through the
ragged engine's paged cache; that a served row's logits depend on no other
row of its tick; and that each way a wrong program could differ FAILS the
shipped tolerance.

Everything is float32 with no kernel, so the comparison is about the
mathematics alone.  ``TOL`` = 2e-4: float32 rounding through two layers at
logits of order 1 (the same figure ``benchmark/tests/test_reference.py``
holds Mistral to); every mutation moves a logit by over a hundred times
that.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import olmoe as ref  # noqa: E402
from deepspeed_tpu.inference.v2.ragged_engine import \
    RaggedInferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import olmoe  # noqa: E402

TOL = 2e-4
# the reference's view of the tiny preset: the source's key names
CFG = dict(vocab_size=96, hidden_size=32, intermediate_size=16,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
           num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
           rms_norm_eps=1e-5, rope_theta=10000.0)


@pytest.fixture(scope="module")
def served():
    cfg = olmoe.get_config(
        "tinyolmoe", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=False, remat=False, use_flash_attention=False,
        drop_tokens=False)      # plain ``apply`` keeps its capacity paths
    model = olmoe.OlmoeForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales away from their initial 1, so that the FORM of
    # QK-norm (whole projection or per head) shows in the logits
    rng = np.random.default_rng(1)
    for i in range(cfg.num_hidden_layers):
        attn = params["model"][f"layers_{i}"]["self_attn"]
        for name in ("q_norm", "k_norm"):
            attn[name]["scale"] = jnp.asarray(
                rng.uniform(0.5, 1.5, size=cfg.hidden_size), jnp.float32)
    return model, params


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


def _apply(served, toks):
    model, params = served
    return np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([toks], np.int32))[0][0])


def test_the_tiny_preset_is_an_expert_model():
    cfg = olmoe.get_config("tinyolmoe")
    assert cfg.num_experts >= 8 and cfg.num_experts_per_tok >= 2
    assert cfg.num_local_experts == cfg.num_experts
    assert cfg.qk_norm and not cfg.norm_topk_prob and not cfg.attention_bias
    big = olmoe.get_config("olmoe-1b-7b")
    assert (big.num_experts, big.num_experts_per_tok, big.head_dim,
            big.expert_intermediate) == (64, 8, 128, 1024)
    # every family that existed keeps QK-norm off
    from deepspeed_tpu.models import llama, mistral, mixtral, qwen2_moe
    for mod in (llama, mistral, mixtral, qwen2_moe):
        assert not mod.get_config(next(iter(mod.PRESETS))).qk_norm


def test_apply_matches_the_plain_reference(served):
    """(a) plain ``apply`` (the capacity path, told to drop nothing)
    against the reference's logits."""
    toks = _tokens(40)
    got = _apply(served, toks)
    want = np.asarray(ref.logits(served[1], CFG, toks))
    assert np.abs(got - want).max() < TOL


def _engine(served, **kw):
    model, params = served
    return RaggedInferenceEngineV2(
        model, params=params, **{**dict(max_seqs=4, max_seq_len=64,
                                        prefill_chunk=8, page_size=8,
                                        decode_block_size=4), **kw})


def test_chunked_prefill_then_paged_decode_matches_the_reference(
        served, monkeypatch):
    """(b) a 21-token prompt prefilled in chunks of 8, then 10 tokens
    decoded through the paged cache (fused ticks and decode blocks):
    every greedy token is the reference's own argmax of its full forward
    pass, or within the tolerance of it."""
    from deepspeed_tpu.ops import routes

    # the routes are one record a process, and an xdist worker runs other
    # files in this one (the kernel's interpret-mode tests leave
    # ``gmm-...-interpret`` under the same key): read what THIS engine's
    # programs take while they trace, and nothing older
    monkeypatch.setattr(routes, "_taken", {})
    eng = _engine(served)
    prompt = _tokens(21, seed=3)
    out = eng.generate_all([np.asarray(prompt, np.int32)],
                           max_new_tokens=10)
    generated = list(out[min(out)])[len(prompt):]
    assert len(generated) == 10
    gaps = ref.generated_gaps(served[1], CFG, prompt, generated)
    assert gaps.max() < TOL
    moe = eng.serving_stages()["moe"]
    # 2 layers x 2 experts a token: only live rows were routed
    assert moe["rows"] == 2 * 2 * (21 + 9)
    kernels = eng.serving_stages()["kernels"]
    assert kernels["moe_grouped_matmul"] == "ragged-dot-xla"
    assert kernels["moe_grouped_matmul_kernel"] == "ragged-dot"


def test_the_grouped_matmuls_visits_are_counted(served, monkeypatch):
    """``moe_visits`` on the ``engine.moe`` spans and in
    ``serving_stages()['moe']``: the row-tile visits of the grouped matmul
    over every layer of every tick, against a plain loop over the group
    sizes each call was given.  A chunk of 128 tokens routes 256 copies to
    8 experts, so groups straddle the kernel's row tiles of 128; less the
    experts hit it is the visits that needed no new bank."""
    from deepspeed_tpu.telemetry import trace
    from deepspeed_tpu.moe import layer as moe_layer
    from deepspeed_tpu.ops import grouped_matmul as gm

    seen = []

    def counted(rows, banks, group_sizes):
        jax.debug.callback(lambda g: seen.append(np.asarray(g)), group_sizes)
        return gm.grouped_matmul(rows, banks, group_sizes)

    monkeypatch.setattr(moe_layer, "grouped_matmul", counted)
    trace.configure(enabled=True)
    try:
        trace.clear()
        eng = _engine(served, max_seq_len=256, prefill_chunk=128)
        out = eng.generate_all([np.asarray(_tokens(150, seed=4), np.int32)],
                               max_new_tokens=6)
        jax.effects_barrier()
        events = trace.snapshot()
    finally:
        trace.configure(enabled=False)
        trace.clear()
    assert len(out[min(out)]) == 156

    def visits(sizes):
        count, start = 0, 0
        for size in map(int, sizes):
            if size:
                count += (start + size - 1) // gm.TM - start // gm.TM + 1
            start += size
        return count

    # a swiglu layer calls the kernel three times with the same groups
    assert len(seen) % 3 == 0
    want = sum(map(visits, seen)) // 3
    hit = sum(int((g > 0).sum()) for g in seen) // 3
    moe = [e["args"] for e in events if e["name"] == "engine.moe"]
    stages = eng.serving_stages()["moe"]
    assert stages["visits"] == sum(m["moe_visits"] for m in moe) == want
    assert stages["experts_hit"] == hit
    assert all(m["moe_visits"] >= m["moe_experts_hit"] for m in moe)
    # the first chunk alone: 256 copies in two row tiles, a layer
    assert want > hit


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
    sel, eng.kv.cache, counts, _ = eng.fused_step()(
        eng.params, eng.kv.cache, token_ids, *meta)
    j = [j for r, j in sampling if r.uid == which][0]
    return np.asarray(sel[j]), np.asarray(counts)


def test_a_served_rows_logits_do_not_depend_on_its_tick(served):
    """(c) one 6-token request alone in its tick (the other 6 rows of the
    12 are padding) and the same request beside three others that fill
    the tick: the same logits, and the reference's.  Under a capacity a
    token's experts turn on who shares the tick, padding included."""
    prompt = _tokens(6, seed=7)
    alone, counts = _first_tick_logits(_engine(served), [prompt], 0)
    # 6 live rows x top-2 x 2 layers; the padding rows routed nothing
    assert counts[0] == 24 and 1 <= counts[2] <= 6
    others = [_tokens(2, seed=s) for s in (8, 9, 10)]
    full, counts = _first_tick_logits(_engine(served),
                                      others[:2] + [prompt] + others[2:], 2)
    assert counts[0] == 2 * 2 * 12
    assert np.abs(alone - full).max() < TOL
    want = np.asarray(ref.logits(served[1], CFG, prompt, last=1))[0]
    assert np.abs(alone - want).max() < TOL


def test_under_a_capacity_a_rows_output_does_depend_on_its_batch():
    """What (c) guards against, shown on the layer: with the capacity
    paths a row beside others loses an expert that it keeps alone."""
    from deepspeed_tpu.moe.layer import MoE

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    outs = {}
    for impl in ("sorted", "grouped"):
        layer = MoE(hidden_size=32, num_experts=8, intermediate_size=16,
                    k=2, capacity_factor=1.0, min_capacity=1,
                    dtype=jnp.float32, expert_parallel=False,
                    normalize_weights=False, dispatch_impl=impl)
        params = layer.init(jax.random.PRNGKey(1), x)
        apply = jax.jit(layer.apply)
        both = apply(params, x)[0][0]
        alone = jnp.concatenate(
            [apply(params, x[:, i:i + 1])[0][0] for i in range(16)])
        outs[impl] = float(jnp.abs(both - alone).max())
    assert outs["grouped"] < 1e-6
    assert outs["sorted"] > 1e-3


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_tolerance(served, mutate):
    """(d) QK-norm left out or made per head, top-k weights renormalised,
    one expert fewer than top-k, expert 0's output dropped: each moves
    the logits by over a hundred times the tolerance."""
    toks = _tokens(40)
    got = _apply(served, toks)
    wrong = np.asarray(ref.logits(served[1], CFG, toks, mutate=mutate))
    assert np.abs(wrong - got).max() > 100 * TOL, mutate


def test_lower_precision_fails_the_tolerance(served):
    """The reference with every matmul operand rounded to e4m3, the
    nearest precision below the configuration's, is not the model."""
    toks = _tokens(40)
    got = _apply(served, toks)
    low = np.asarray(ref.logits(served[1], CFG, toks, precision="float8"))
    assert np.abs(low - got).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
