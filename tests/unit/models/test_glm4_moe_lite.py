"""GLM-4-MoE-Lite (multi-head latent attention, a sigmoid router with a
selection bias beside one ungated shared expert) against the benchmark's
plain float32 reference, which computes the EXPANDED form (per-head keys
and values, no cache): through ``apply``, and through the ENGINE'S OWN
PROGRAMS (``programs.build_fused_step``'s logits and the decode block),
which run the ABSORBED form over the paged latent rows, on
``test_lfm2_moe.py``'s schedule of ticks (chunk boundaries 1, 2 and 5
tokens into a sequence, chunks of one token, a tick that packs a sequence
that starts, one that continues and a decode row, then sixteen decode
ticks); then that each way a wrong program could differ FAILS the same
comparison.

Everything is float32 with no kernel (the latent pool's XLA twin), so the
comparison is about the mathematics alone.  ``TOL`` = 2e-4: float32
rounding through three layers at logits of order 1, the figure
``test_lfm2_moe.py``, ``test_olmoe.py`` and ``test_ouro.py`` hold their
families to; the absorbed form multiplies in another order than the
expanded one and stays well inside it, and the weakest mutation moves a
logit by over ten times that.
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

from benchmark.reference import glm4_moe_lite as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.models import glm4_moe_lite as glm  # noqa: E402
from deepspeed_tpu.moe.sharded_moe import sigmoid_bias_routing  # noqa: E402
from tests.unit.models.test_lfm2_moe import (A, B, C, _tokens,  # noqa: E402
                                             run_schedule)

TOL = 2e-4
PLAIN = dict(dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
             use_flash_attention=False)
# the reference's view of the tiny preset: the source's key names
CFG = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
           moe_intermediate_size=32, num_hidden_layers=3,
           num_attention_heads=3, n_routed_experts=8, n_shared_experts=1,
           num_experts_per_tok=2, norm_topk_prob=True,
           routed_scaling_factor=1.8, first_k_dense_replace=1,
           q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=12, rms_norm_eps=1e-5,
           rope_theta=10000.0)


@pytest.fixture(scope="module")
def served():
    model = glm.Glm4MoeLiteForCausalLM(glm.get_config("tinyglm", **PLAIN))
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales away from their first value 1, so that every norm's
    # place shows (the latent's above all: what the cache holds is c AFTER
    # it), and a selection bias large enough to change most tokens' chosen
    # set among 8 experts
    rng = np.random.default_rng(1)
    scale = lambda n: jnp.asarray(rng.uniform(0.5, 1.5, size=n), jnp.float32)
    inner = params["model"]
    for i in range(3):
        layer = inner[f"layers_{i}"]
        layer["input_layernorm"]["scale"] = scale(64)
        layer["post_attention_layernorm"]["scale"] = scale(64)
        layer["self_attn"]["q_a_layernorm"]["scale"] = scale(24)
        layer["self_attn"]["kv_a_layernorm"]["scale"] = scale(32)
        if "expert_bias" in layer["mlp"]:
            layer["mlp"]["expert_bias"] = jnp.asarray(
                rng.normal(0, 0.1, size=8), jnp.float32)
    inner["norm"]["scale"] = scale(64)
    return model, params


def worst_difference(served, seqs, got, mutate=None):
    """The largest difference between the programs' logit rows and the
    reference's full forward pass of each sequence."""
    want = {slot: np.asarray(ref.logits(served[1], CFG, seq, mutate=mutate))
            for slot, seq in seqs.items()}
    return max(float(np.abs(lg - want[slot][pos]).max())
               for slot, pos, lg in got)


@pytest.fixture(scope="module")
def schedule(served):
    return run_schedule(served)


def test_the_presets_are_the_published_shapes():
    tiny, big = (glm.get_config(p) for p in ("tinyglm", "glm-4.7-flash"))
    assert (tiny.first_k_dense_replace, tiny.n_routed_experts,
            tiny.num_experts_per_tok, tiny.n_shared_experts) == (1, 8, 2, 1)
    assert tiny.v_head_dim != tiny.head_dim       # unequal q/k and v widths
    assert (big.num_hidden_layers, big.hidden_size, big.num_attention_heads,
            big.q_lora_rank, big.kv_lora_rank, big.qk_nope_head_dim,
            big.qk_rope_head_dim, big.v_head_dim, big.intermediate_size,
            big.moe_intermediate_size, big.n_routed_experts,
            big.num_experts_per_tok, big.n_shared_experts,
            big.routed_scaling_factor, big.first_k_dense_replace,
            big.vocab_size, big.rope_theta) == (
                47, 2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 64, 4, 1,
                1.8, 1, 154880, 1e6)
    # the cached row, 512 + 64 (32 + 8), as the pool stores it: whole lanes
    assert big.head_dim == 256 and paged.latent_width(big) == 640
    assert paged.latent_width(tiny) == 128
    assert hash(big) == hash(glm.get_config("glm-4.7-flash"))


def test_the_stage_of_eight_layers_counts_its_parameters_by_shapes():
    """Layers 0-7 of the source at the published widths, by shapes alone:
    the numbers ISSUE 40 reckoned from the config's keys."""
    cfg = glm.get_config("glm-4.7-flash", num_hidden_layers=8)
    shapes = jax.eval_shape(
        glm.Glm4MoeLiteForCausalLM(cfg).init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]
    count, layers = glm.count_params, shapes["model"]
    attention = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512
                 + 512 * 8960 + 5120 * 2048)
    assert attention == 21_759_232
    assert count(layers["layers_0"]["self_attn"]) == attention
    assert count(layers["layers_0"]["mlp"]) == 3 * 2048 * 10240
    assert count(layers["layers_0"]) == 84_677_888
    assert count(layers["layers_1"]["mlp"]) == (
        64 * 3 * 2048 * 1536 + 2048 * 64 + 64)
    assert count(layers["layers_1"]["shared_experts"]) == 3 * 2048 * 1536
    assert count(layers["layers_1"]) == 635_311_424
    assert count(layers["embed_tokens"]) == count(shapes["lm_head"]) \
        == 154880 * 2048
    assert count(shapes) == 5_166_248_384


@pytest.mark.parametrize("kw,named", [
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(n_group=2), "n_group"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(decode=True), "dense decode cache")],
    ids=["multi-token-prediction", "rope-scaling", "router-groups",
         "topk-method", "tied-head", "dense-decode-cache"])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        glm.get_config("tinyglm", **kw)


def test_the_router_scales_by_1_8_and_the_first_bias_changes_some_sets():
    """The published router's weights, ``1.8 * s_i / (sum of the chosen s +
    1e-20)`` with the bias not in them (LFM2's call keeps its 1e-6), and
    what ``init`` draws: a bias of standard deviation EXPERT_BIAS_INIT_STD,
    which changes the chosen 4 of 64 in about six routings of ten, and the
    routed down-projections at EXPERT_DOWN_INIT_SCALE of lecun-normal's."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4096, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, glm.EXPERT_BIAS_INIT_STD, 64),
                       jnp.float32)
    experts, weights, swaps = sigmoid_bias_routing(
        logits, bias, 4, True, 1.8, glm.ROUTED_WEIGHT_EPS)
    assert 0.5 < float(jnp.mean(swaps)) < 0.7
    picked = jnp.take_along_axis(jax.nn.sigmoid(logits), experts, axis=-1)
    assert np.allclose(weights, 1.8 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)
    assert np.allclose(weights.sum(-1), 1.8, atol=1e-5)
    lfm2 = sigmoid_bias_routing(logits, bias, 4, True)[1]
    assert np.allclose(lfm2, picked / (picked.sum(-1, keepdims=True) + 1e-6),
                       atol=1e-7)
    model = glm.Glm4MoeLiteForCausalLM(
        glm.get_config("tinyglm", **PLAIN, n_routed_experts=64))
    drawn = jax.jit(model.init)(jax.random.PRNGKey(3), np.zeros(
        (1, 8), np.int32))["params"]["model"]["layers_2"]
    assert drawn["mlp"]["expert_bias"].dtype == jnp.float32
    assert 0.5 < float(jnp.std(drawn["mlp"]["expert_bias"])
                       ) / glm.EXPERT_BIAS_INIT_STD < 1.5
    assert abs(float(jnp.std(drawn["mlp"]["w2"])) * np.sqrt(32)
               / glm.EXPERT_DOWN_INIT_SCALE - 1) < 0.1
    # the shared expert's, and every other matrix, at lecun-normal's own
    assert abs(float(jnp.std(
        drawn["shared_experts"]["down_proj"]["kernel"])) * np.sqrt(32)
        - 1) < 0.1
    assert abs(float(jnp.std(drawn["self_attn"]["kv_b_proj"]))
               * np.sqrt(32) - 1) < 0.1


def test_apply_matches_the_plain_reference(served):
    model, params = served
    toks = _tokens(40, seed=2)
    got = np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([toks], np.int32))[0])
    want = np.asarray(ref.logits(params, CFG, toks))
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.3            # logits of order 1, not a constant


def test_the_engines_programs_match_the_reference(served, schedule):
    """Fused ticks at every kind of chunk boundary, the mixed tick, sixteen
    decode ticks over two blocks, all ABSORBED over the paged latent rows:
    every logit row against the reference's EXPANDED full forward pass, and
    every token a block sampled the reference's own argmax."""
    seqs, got = schedule
    assert [len(seqs[s]) for s in (A, B, C)] == [14 + 16, 10 + 16, 7 + 16]
    assert len(got) == 1 + 1 + 1 + 2 + 3 + 3
    assert worst_difference(served, seqs, got) < TOL
    for slot, n in ((A, 14), (B, 10), (C, 7)):
        gaps = ref.generated_gaps(served[1], CFG, seqs[slot][:n],
                                  seqs[slot][n:])
        assert gaps.max() < TOL


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_comparison(served, schedule, mutate):
    """The latent cached before its norm, the shared key before its
    rotation, another softmax scale, the value as wide as the row, the
    shared expert left out or scaled, the routed scaling left out, the
    bias in the weights or out of the selection, softmax for sigmoid, no
    renormalisation: the programs' logits are NOT such a model's."""
    seqs, got = schedule
    assert worst_difference(served, seqs, got, mutate=mutate) > 10 * TOL


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(24, seed=4)
    want = np.asarray(ref.logits(served[1], CFG, toks))
    low = np.asarray(ref.logits(served[1], CFG, toks, precision="float8"))
    assert np.abs(low - want).max() > 100 * TOL


def test_the_reference_blocks_its_queries_and_slices_its_head(served,
                                                              monkeypatch):
    """Attention in blocks of queries that do not divide the sequence and
    the head in slices that do not divide the vocabulary: the same
    logits."""
    toks = _tokens(29, seed=6)
    want = np.asarray(ref.logits(served[1], CFG, toks))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "VOCAB_SLICES", 5)
    got = np.asarray(ref.logits(served[1], CFG, toks))
    assert got.shape == want.shape and np.abs(got - want).max() < 1e-5


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]


def test_a_fused_tick_turns_and_pads_nothing_as_large_as_its_queries(
        monkeypatch):
    """The attention module's fused branch, traced where the kernels run
    (bf16, a latent row of whole lane tiles, the backend answering as the
    chip): the absorbed queries are born heads first and as wide as the
    pool's row, and the kernel's answer is multiplied as it lies.  Between
    the absorbing products and the kernel call the jaxpr holds NO
    ``transpose`` and NO ``pad`` of an array of ``T x H x rank`` elements
    or more (the parent turned q and the answer once each and padded q
    twice: ``T x H x W``); both kernels are in it."""
    from deepspeed_tpu.ops import latent_attention as la
    from deepspeed_tpu.ops import routes

    monkeypatch.setattr(routes, "on_tpu", lambda: True)
    monkeypatch.setattr(routes, "_taken", {})
    T, slots, pp, page = 200, 4, 20, 16
    cfg = glm.get_config(
        "tinyglm", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        kv_lora_rank=128, paged_decode=True, kv_page_size=page,
        kv_num_pages=1 + slots * pp, remat=False, use_flash_attention=False)
    H, rank, W = cfg.num_attention_heads, 128, paged.latent_width(cfg)
    assert W == 256
    module = glm.Glm4MoeLiteAttention(cfg)
    i32 = jnp.int32
    meta = {"kv_lens": jnp.asarray([150, 31, 120, 0], i32),
            "page_indices": jnp.arange(1, 1 + slots * pp, dtype=i32
                                       ).reshape(slots, pp),
            "cu_q_lens": jnp.asarray([0, 150, 151, 200, 200], i32),
            "num_seqs": jnp.asarray([3], i32),
            "new_kv_dest": jnp.arange(T, dtype=i32), "fused_tick": True}
    x = jnp.zeros((1, T, cfg.hidden_size), jnp.bfloat16)
    pos = jnp.arange(T)[None]
    variables = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), x, pos, True, meta))
    jaxpr = jax.make_jaxpr(lambda v, x: module.apply(
        v, x, pos, True, meta, mutable=["cache"]))(variables, x)

    def equations(jp):
        """Every equation outside the kernels' own bodies."""
        for eqn in jp.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    eqns = list(equations(jaxpr.jaxpr))
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert kernels == [la.NAME_PREFILL, la.NAME_DECODE]
    assert routes.taken()[la.KERNEL_PREFILL].startswith("pallas-latent-chunk")
    turned = [(e.primitive.name, e.outvars[0].aval.shape) for e in eqns
              if e.primitive.name in ("transpose", "pad")
              and e.outvars[0].aval.size >= T * H * rank]
    assert not turned, turned
    # and the queries the kernel takes are the einsum's own layout
    chunk = next(e for e in eqns if e.primitive.name == "pallas_call")
    q_in = [v.aval.shape for v in chunk.invars if len(v.aval.shape) == 3]
    assert (H, la.tick_rows(T), W) in q_in
    assert chunk.outvars[0].aval.shape == (H, la.tick_rows(T), rank)
