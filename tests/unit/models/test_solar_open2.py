"""Solar-Open2 (Kimi Delta Attention, whose decay is a vector a key channel,
beside gated grouped-query attention without rotary embedding; 3 of 12
experts HELD under a router 12 wide, beside one shared expert) against the
benchmark's plain float32 reference, which runs the recurrence token by
token with no cache and is GIVEN THE SAME SHARE: through ``apply`` on whole
sequences (one-call prefill), and through the SERVING FORM over the paged
pool and the two per-slot state leaves on ``test_olmo_hybrid.py``'s schedule
of ragged ticks (three sequences of 150, 70 and 5 tokens packed into ticks of
52 rows: chunks of 48 that do not divide the scan's tile of 128 and cross
pages of 16, several chunks a tick, sequences that go on as one-row decode
rows while others prefill, then ticks as a decode block makes them); then
that each way a wrong program could differ FAILS the same comparison, and
that a reused slot does not leak.

Everything is float32 with no kernel (the twins), so the comparison is
about the mathematics alone.  ``TOL`` = 2e-4: float32 rounding through four
layers at logits of order 1, the figure the other families' tests hold.
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

from benchmark import harness as H  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import solar_open2 as so  # noqa: E402
from deepspeed_tpu.models.llama import count_params  # noqa: E402
from tests.unit.models.test_olmo_hybrid import (_tokens,  # noqa: E402
                                                run_schedule)

TOL = 2e-4
CFG = so.get_config("tinysolar", dtype=jnp.float32, param_dtype=jnp.float32,
                    remat=False, use_flash_attention=False)
MODEL = so.SolarOpen2ForCausalLM(CFG)
# the reference's view of the tiny preset: the source's key names
REF_CFG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4, gqa_layers=[0],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=2, num_kv_heads=None),
    use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, first_k_dense_replace=0,
    n_routed_experts=3, expert_shards=4, expert_shard=1, n_shared_experts=1,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1,
    rms_norm_eps=1e-5)


@pytest.fixture(scope="module")
def served():
    params = jax.jit(MODEL.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales, biases and the gates' A_log and dt_bias away from
    # their first values (the selection bias and c_g start at ZERO: drawn),
    # so that every one of them is in the comparison
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))

    def moved(path, l):
        if l.ndim != 1:
            return l
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("expert_bias") or name.endswith("g_b_proj/bias"):
            return 0.1 * jax.random.normal(next(keys), l.shape)
        return l * (1 + 0.2 * jax.random.normal(next(keys), l.shape))

    return MODEL, jax.tree_util.tree_map_with_path(moved, params)


@pytest.fixture(scope="module")
def schedule(served):
    return run_schedule(served[1], CFG, so.SolarOpen2ForCausalLM)


def worst_difference(params, seqs, got, mutate=None):
    return max(float(np.abs(got[i] - np.asarray(
        ref.logits(params, REF_CFG, seq, mutate=mutate))).max())
        for i, seq in enumerate(seqs))


def test_the_presets_are_the_published_shapes():
    big = so.get_config("solar-open2-250b")
    la = big.linear_attn_config
    assert (big.num_hidden_layers, big.hidden_size, big.head_dim,
            big.num_attention_heads, big.num_key_value_heads,
            la["num_heads"], la["head_dim"], la["short_conv_kernel_size"],
            big.moe_intermediate_size, big.n_routed_experts,
            big.expert_shards, big.num_experts_per_tok, big.vocab_size) == (
                48, 4096, 128, 64, 8, 64, 128, 4, 1280, 320, 1, 8, 196608)
    assert big.gqa_layers == tuple(range(0, 48, 4))
    assert paged.state_layers(big) == 36 and paged.kv_layers(big) == 12


def test_the_served_layers_count_their_parameters_and_bytes_by_shapes():
    """``benchmark/configs/solar-open2-250b-serve.json`` by ``model.init``'s
    shapes: ISSUE 56's 3,308,377,920 parameters to the parameter, term by
    term, the state a sequence as counted and what a token costs the
    pool."""
    config = H.load_json(os.path.join(
        ROOT, "benchmark", "configs", "solar-open2-250b-serve.json"))
    cfg = H.build_model_config(config)
    assert cfg.gqa_layers == (0,) and cfg.num_hidden_layers == 4
    assert (cfg.n_routed_experts, cfg.expert_shards, cfg.expert_shard) == (
        40, 8, 0)
    assert cfg.n_routed_experts * cfg.expert_shards == \
        config["published"]["n_routed_experts"]
    assert cfg.vocab_size * 8 == config["published"]["vocab_size"]
    model = so.SolarOpen2ForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    per = count_params
    layers = shapes["model"]
    gqa, kda_layer = layers["layers_0"], layers["layers_1"]
    E, V, I = 4096, 24576, 1280
    assert per(kda_layer["linear_attn"]) == 137_740_480
    assert per(gqa["self_attn"]) == 109_051_904
    assert per(gqa["mlp"]) == 320 * E + 320 + 40 * 3 * E * I
    assert gqa["mlp"]["gate"].shape == (E, 320)
    assert gqa["mlp"]["w1"].shape == (40, E, I)
    assert per(gqa["shared_experts"]) == 3 * E * I == 15_728_640
    assert per(kda_layer) == 783_933_952
    assert per(gqa) == 755_245_376
    assert per(gqa) + 3 * per(kda_layer) == 3_107_047_232
    total = count_params(shapes)
    assert total == per(gqa) + 3 * per(kda_layer) + 2 * V * E + E
    assert total == 3_308_377_920
    assert total * 2 < 6.62e9                                    # bf16
    page_bytes = programs.paged_kv_page_bytes(model, cfg, 64, "none")
    from benchmark import flops_solar as fs
    assert page_bytes == 64 * 4096 == 64 * fs.pool_bytes_per_token(config)
    assert fs.state_bytes_per_seq(config) == 3 * (4_194_304 + 147_456)
    assert config["engine"]["kv_pool_bytes"] == 2065 * page_bytes


@pytest.mark.parametrize("kw,named", [
    (dict(use_rope=True), "use_rope"),
    (dict(use_gqa_gate=False), "use_gqa_gate"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                  num_heads=2, num_kv_heads=1)),
     "num_kv_heads"),
    (dict(decode=True), "dense decode cache")])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        so.get_config("tinysolar", **kw)


def test_what_a_matrix_state_cannot_carry_is_refused_by_name(served):
    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2

    sizes = dict(max_seqs=1, max_seq_len=128, page_size=16, prefill_chunk=24,
                 decode_block_size=4, num_pages=10)
    with pytest.raises(NotImplementedError, match="per-slot state in 3 of"):
        RaggedInferenceEngineV2(served[0], params=served[1],
                                prefix_cache={"enabled": True}, **sizes)


def test_one_call_prefill_matches_the_plain_reference(served):
    model, params = served
    for n, seed in ((150, 1), (40, 2)):
        toks = _tokens(n, seed)
        got = np.asarray(model.apply({"params": params},
                                     np.asarray(toks)[None]))[0]
        want = np.asarray(ref.logits(params, REF_CFG, toks))
        assert np.abs(got - want).max() < TOL
        assert want.std() > 0.5


def test_the_serving_form_matches_the_reference(served, schedule):
    """Chunked prefill whose chunks do not divide the scan's tile, then
    decode through the pool and both state leaves, against the full forward
    pass; a packed tick holds long sequences and one-row sequences."""
    seqs, got = schedule
    assert worst_difference(served[1], seqs, got) < TOL


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_comparison(served, schedule, mutate,
                                            monkeypatch):
    """Each way a wrong program could differ moves a logit by far more than
    the tolerance: the decay as one number a head, the correction left out,
    the carried state lost (every 64 tokens here), the factor 2 of ``b``,
    the convolution, the L2 norm (not finite counts as wrong), SiLU for the
    KDA gate, the GQA gate left out, rotary embedding on the GQA layer, the
    held range ignored, the shared expert left out."""
    seqs, got = schedule
    monkeypatch.setattr(ref, "CARRY_ZEROED_EVERY", 64)
    worst = worst_difference(served[1], seqs, got, mutate)
    assert not worst <= 100 * TOL


def test_a_reused_slot_does_not_leak(served):
    """A sequence served in a slot whose two state leaves and pages held
    another's generates what it generates in a fresh cache, which is what
    the reference picks; the engine's counts tell the held banks' rows from
    the router's."""
    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2

    model, params = served
    sizes = dict(max_seqs=1, max_seq_len=128, page_size=16, prefill_chunk=24,
                 decode_block_size=4, num_pages=10)
    one = lambda eng, toks: list(eng.generate_all(
        [np.asarray(toks)], max_new_tokens=6).values())[0].tolist()
    eng = RaggedInferenceEngineV2(model, params=params, **sizes)
    try:
        one(eng, _tokens(70, 3))
        again = one(eng, _tokens(41, 4))
        stages = eng.serving_stages()
    finally:
        eng.close()
    fresh = RaggedInferenceEngineV2(model, params=params, **sizes)
    try:
        alone = one(fresh, _tokens(41, 4))
    finally:
        fresh.close()
    assert again == alone
    want = _tokens(41, 4)
    for _ in range(6):
        want.append(int(np.argmax(np.asarray(
            ref.logits(params, REF_CFG, want, last=1))[0])))
    assert again == want
    moe = stages["moe"]
    # 4 copies a token a layer over 4 layers; 3 of 12 experts held a layer
    assert moe["rows_routed"] % 16 == 0 and moe["experts_held"] % 12 == 0
    assert 0 < moe["rows"] < moe["rows_routed"]
    assert moe["experts_hit"] <= moe["experts_held"]
    assert stages["kda_state"]["state_layers"] == 3
    assert set(stages["kernels"]) >= {"kda_scan", "kda_decode"}


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(40, 2)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    low = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                precision="float8"))
    assert np.abs(want - low).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    text = open(ref.__file__).read()
    assert "deepspeed_tpu" not in text.split('"""', 2)[2]
