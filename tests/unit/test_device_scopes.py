"""``telemetry/device_scopes.py``: the scope paths the compiled programs
carry, folded into layer kinds and joined to instruction names.

(a) ``classify`` over every equation of the engines' own programs: the four
benchmark families' fused tick and decode block, and a train step with scan
and remat; (b) ``scope_table`` on a hand-written compiled text; (c) the
``jax.named_scope`` sites change no program; (d) ``tables()`` re-uses the
trace and the executable; (e) two variants under one name.
"""
import contextlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.profiling.flops_profiler import walk_equations
from deepspeed_tpu.telemetry import device_scopes as ds
from deepspeed_tpu.telemetry.compiles import count_compiles

ENGINE = dict(max_seqs=4, max_seq_len=64, page_size=16, prefill_chunk=16,
              decode_block_size=4, num_pages=12)
BF16 = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
PLAIN = dict(scan_layers=False, remat=False, use_flash_attention=False)
# a block's kinds, by family: which mixers and which MLPs it may hold
FAMILIES = {
    "mistral": {"attn", "mlp"},
    "olmoe": {"attn", "moe"},
    "ouro": {"attn", "mlp"},
    "lfm2": {"attn", "conv", "mlp", "moe"},
    "glm": {"attn", "mlp", "moe"},
    "sala": {"attn", "linear", "mlp"},
    "keye": {"attn", "moe"},
    "olmo-hybrid": {"attn", "linear", "mlp"},
    "solar": {"attn", "linear", "moe"},
    "glm5": {"attn", "mlp", "moe"},
}


def _model(family):
    from deepspeed_tpu.models import (glm4_moe_lite, glm_moe_dsa, keye_vl2,
                                      lfm2_moe, minicpm_sala, mistral,
                                      olmo_hybrid, olmoe, ouro, solar_open2)

    if family == "glm5":
        return glm_moe_dsa.GlmMoeDsaForCausalLM(
            glm_moe_dsa.get_config("tinyglm5", **BF16))
    if family == "mistral":
        return mistral.MistralForCausalLM(mistral.get_config(
            "tinymistral", hidden_size=256, num_attention_heads=2,
            num_key_value_heads=1, **BF16, **PLAIN))
    if family == "olmoe":
        return olmoe.OlmoeForCausalLM(olmoe.get_config(
            "tinyolmoe", expert_parallel=False, **BF16, **PLAIN))
    if family == "ouro":
        return ouro.OuroForCausalLM(ouro.get_config("tinyouro", **BF16,
                                                    **PLAIN))
    if family == "glm":
        return glm4_moe_lite.Glm4MoeLiteForCausalLM(
            glm4_moe_lite.get_config("tinyglm", **BF16))
    if family == "sala":
        return minicpm_sala.MiniCPMSALAForCausalLM(
            minicpm_sala.get_config("tinysala", **BF16))
    if family == "olmo-hybrid":
        return olmo_hybrid.OlmoHybridForCausalLM(
            olmo_hybrid.get_config("tinyolmohybrid", **BF16))
    if family == "solar":
        return solar_open2.SolarOpen2ForCausalLM(
            solar_open2.get_config("tinysolar", **BF16))
    if family == "keye":
        return keye_vl2.KeyeVL2ForCausalLM(keye_vl2.get_config(
            "tinykeye", expert_parallel=False, **BF16))
    return lfm2_moe.Lfm2MoeForCausalLM(lfm2_moe.get_config("tinylfm2",
                                                           **BF16))


def _engine(family):
    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2

    model = _model(family)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    return RaggedInferenceEngineV2(model, params=params, **ENGINE)


def _program_avals(eng):
    """The avals of the greedy fused tick and decode block, as the loop
    calls them."""
    S, T, pp = eng.max_seqs, eng.T, eng.pages_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)   # noqa: E731
    boo = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)     # noqa: E731
    state = (i32(S),) if eng.state_layers else ()
    return {
        "fused": (eng.fused_sample_step(False), (
            eng.params, eng.kv.cache, i32(S), i32(1, T), i32(1, T),
            i32(1, T), i32(S), i32(S, pp), i32(S + 1), i32(1), i32(T),
            i32(S), i32(S)) + state),
        "decode_block": (eng.decode_block(False), (
            eng.params, eng.kv.cache, i32(S), i32(S), boo(S), i32(S),
            i32(S, pp), i32(S), boo(S), f32(S), i32(S), f32(S), i32(S),
            jax.random.PRNGKey(0))),
    }


def _classified(fn, args):
    """``[(path, (kind, sub, phase))]`` of every equation of ``fn``."""
    closed = jax.make_jaxpr(fn)(*args)
    # an ``op_name`` is the name stack and the primitive
    paths = ["/".join(path + [eqn.primitive.name])
             for eqn, path, _ in walk_equations(closed.jaxpr)]
    kinds = ds.classify_all(paths)
    return [(path, kinds[path]) for path in paths]


# -- (a) the vocabulary ------------------------------------------------------

@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    eng = _engine(request.param)
    yield request.param, eng
    eng.close()


@pytest.mark.parametrize("program", ["fused", "decode_block"])
def test_every_equation_of_a_serving_program_has_a_kind(served, program):
    family, eng = served
    fn, args = _program_avals(eng)[program]
    rows = _classified(fn, args)
    assert len(rows) > 100
    kinds = {k for _, (k, _, _) in rows}
    assert kinds <= set(ds.KINDS)
    # a serving program differentiates nothing
    assert {p for _, (_, _, p) in rows} == {"-"}
    # no equation inside a block falls to ``rest``
    in_block = [(path, k) for path, (k, _, _) in rows
                if re.search(r"/layers_\d+/", path + "/")]
    assert in_block
    assert {k for _, k in in_block} <= FAMILIES[family], sorted(
        {(p, k) for p, k in in_block if k not in FAMILIES[family]})[:5]
    assert FAMILIES[family] | {"head", "embed", "sample"} <= kinds
    # the gather of a fused tick's sampled rows counts to the head; a decode
    # block, where every row samples, has none
    assert (("head", "head.rows") in {(k, s) for _, (k, s, _) in rows}) == (
        program == "fused")
    subs = {s for _, (k, s, _) in rows if k == "moe"}
    if "moe" in FAMILIES[family]:
        assert {"moe.route", "moe.dispatch", "moe.experts",
                "moe.combine"} <= subs
        assert "counts" in {s for _, (_, s, _) in rows}
    if family == "ouro":
        assert ("rest", "early_exit_gate") in {(k, s)
                                               for _, (k, s, _) in rows}
    if family == "glm":
        # latent attention's projections and its absorption each under a
        # sub-scope of their own, the shared expert beside the routed ones
        assert {"q_a_proj", "q_a_layernorm", "q_b_proj",
                "kv_a_proj_with_mqa", "kv_a_layernorm", "absorb",
                "o_proj"} <= {s for _, (k, s, _) in rows if k == "attn"}
        assert "moe.shared" in subs


def test_train_step_shows_the_three_phases_and_the_added_scopes(devices):
    from deepspeed_tpu.models.llama import LlamaLMLoss, get_config

    engine, batch = _train_engine(LlamaLMLoss(get_config(
        "tinyllama", scan_layers=True, remat=True, remat_policy="full",
        use_flash_attention=False, dtype=jnp.bfloat16)))
    engine.train_batch(batch=batch)
    fn = engine._train_step_fn
    rows = _classified(fn, (engine.state, engine._to_gas_batch(batch),
                            engine._lr_device()))
    assert {p for _, (_, _, p) in rows} == {"fwd", "remat", "bwd", "-"}
    kinds = {k for _, (k, _, _) in rows}
    assert {"attn", "mlp", "head", "embed", "optim"} <= kinds
    block = [(path, k, p) for path, (k, _, p) in rows if "/block/" in path]
    assert {k for _, k, _ in block} == {"attn", "mlp"}
    # (and ``-``: what a block computes of constants alone, its rotary
    # table, is traced outside the differentiation)
    assert {p for _, _, p in block} >= {"fwd", "remat", "bwd"}
    # the recompute runs what the backward reads: not the last matmul
    assert {s for _, (k, s, p) in rows if k == "mlp" and p == "remat"} >= {
        "gate_proj", "up_proj"}
    # the optimizer's equations are nobody's forward or backward
    assert {p for _, (k, _, p) in rows if k == "optim"} == {"-"}
    # and the step registered itself: its compiled text says the same,
    # from the executable the call compiled
    with count_compiles() as compiles:
        tables = ds.tables()["train_step"]
    assert compiles.count == 0
    mine = max(tables, key=len)
    assert {(s.kind, s.phase) for s in mine.values()} >= {
        ("attn", "fwd"), ("attn", "remat"), ("attn", "bwd"),
        ("mlp", "remat"), ("head", "bwd"), ("embed", "fwd"), ("optim", "-")}
    # a ZeRO-3 gather carries the name of the layer that reads the weight
    gathers = {s.sub for n, s in mine.items()
               if mine.signatures[n][0].startswith("all-gather")}
    assert {"q_proj", "gate_proj", "down_proj"} <= gathers


def _train_engine(loss_module, dp=2):
    topo = dist.initialize_mesh(dp=dp, devices=jax.devices()[:dp])
    rows = 2 * dp
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 256, size=(rows, 32), dtype=np.int32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=loss_module, topology=topo,
        config={"train_batch_size": rows, "steps_per_print": 100,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {
                    "stage": 3, "stage3_param_persistence_threshold": 64}},
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    return engine, batch


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/transpose(jvp(LlamaForCausalLM))/model/while/body/closed_call/"
     "checkpoint/rematted_computation/layers/block/mlp/gate_proj/"
     "dot_general", ("mlp", "gate_proj", "remat")),
    ("jit(f)/jvp(LlamaForCausalLM)/model/layers/block/self_attn/q_proj/"
     "dot_general", ("attn", "q_proj", "fwd")),
    ("jit(f)/transpose(jvp(LlamaForCausalLM))/lm_head/dot_general",
     ("head", None, "bwd")),
    ("jit(f)/M/model/layers_1/self_attn/jit(_decode)/paged_decode_attention/"
     "pallas_call", ("attn", "paged_decode_attention", "-")),
    ("jit(f)/M/model/layers_3/post_attention_layernorm/rsqrt",
     ("mlp", "post_attention_layernorm", "-")),
    ("jit(f)/M/model/layers_3/input_layernorm/rsqrt",
     ("attn", "input_layernorm", "-")),
    ("jit(f)/M/model/norm/rsqrt", ("head", "norm", "-")),
    ("jit(f)/M/model/embed_tokens/gather", ("embed", None, "-")),
    ("jit(f)/M/model/embed_tokens.attend/dot_general",
     ("head", "embed_tokens.attend", "-")),
    ("jit(f)/M/model/layers_2/feed_forward/moe.experts/"
     "feed_forward._grouped_ffn/ragged-dot-gmm/pallas_call",
     ("moe", "moe.experts", "-")),
    ("jit(f)/M/model/layers_2/conv/in_proj/dot_general",
     ("conv", "in_proj", "-")),
    ("jit(f)/M/model/layers_1/self_attn/kv_a_proj_with_mqa/dot_general",
     ("attn", "kv_a_proj_with_mqa", "-")),
    ("jit(f)/M/model/layers_1/self_attn/absorb/dot_general",
     ("attn", "absorb", "-")),
    ("jit(f)/M/model/layers_1/self_attn/jit(_chunk)/latent_chunk_attention/"
     "pallas_call", ("attn", "latent_chunk_attention", "-")),
    ("jit(f)/M/model/layers_1/self_attn/jit(_decode)/"
     "latent_decode_attention/pallas_call",
     ("attn", "latent_decode_attention", "-")),
    ("jit(f)/M/model/layers_1/moe.shared/shared_experts/gate_proj/"
     "dot_general", ("moe", "moe.shared", "-")),
    ("jit(f)/M/model/layers_1/lightning_attn/q_proj/dot_general",
     ("linear", "q_proj", "-")),
    ("jit(f)/M/model/layers_1/lightning_attn/gate/logistic",
     ("linear", "gate", "-")),
    ("jit(f)/M/model/layers_1/lightning_attn/o_norm/rsqrt",
     ("linear", "o_norm", "-")),
    ("jit(f)/M/model/layers_1/lightning_attn/jit(_scan)/linear_chunk_scan/"
     "pallas_call", ("linear", "linear_chunk_scan", "-")),
    ("jit(f)/M/model/layers_1/lightning_attn/jit(_step)/linear_decode_step/"
     "pallas_call", ("linear", "linear_decode_step", "-")),
    # a gated-delta-rule mixer: its projections, the convolution INSIDE it
    # (kind ``linear``, not ``conv``), its gates, kernels and block norms
    ("jit(f)/M/model/layers_0/linear_attn/q_proj/dot_general",
     ("linear", "q_proj", "-")),
    ("jit(f)/M/model/layers_0/linear_attn/conv/mul",
     ("linear", "conv", "-")),
    ("jit(f)/M/model/layers_0/linear_attn/gates/a_proj/dot_general",
     ("linear", "gates", "-")),
    ("jit(f)/M/model/layers_0/linear_attn/jit(_scan)/delta_chunk_scan/"
     "pallas_call", ("linear", "delta_chunk_scan", "-")),
    ("jit(f)/M/model/layers_0/linear_attn/jit(_step)/delta_decode_step/"
     "pallas_call", ("linear", "delta_decode_step", "-")),
    # a KDA mixer under the same module name: its two kernels, the decay's
    # low-rank pair among its gates, and the held expert layer's ``cond``
    ("jit(f)/M/model/layers_1/linear_attn/jit(_scan)/kda_chunk_scan/"
     "pallas_call", ("linear", "kda_chunk_scan", "-")),
    ("jit(f)/M/model/layers_1/linear_attn/jit(_step)/kda_decode_step/"
     "pallas_call", ("linear", "kda_decode_step", "-")),
    ("jit(f)/M/model/layers_1/linear_attn/gates/f_b_proj/dot_general",
     ("linear", "gates", "-")),
    ("jit(f)/M/model/layers_1/mlp/cond/branch_0_fun/moe.combine/dot_general",
     ("moe", "moe.combine", "-")),
    ("jit(f)/M/model/layers_0/self_attn/compress/gather",
     ("attn", "compress", "-")),
    ("jit(f)/M/model/layers_0/self_attn/select/top_k",
     ("attn", "select", "-")),
    ("jit(f)/M/model/layers_0/self_attn/jit(_decode)/"
     "sparse_decode_attention/pallas_call",
     ("attn", "sparse_decode_attention", "-")),
    # a token-selecting layer: the indexer's module, the chunks' scopes and
    # kernels, the one-row path's scopes (inside the step's ``cond``)
    ("jit(f)/M/model/layers_0/self_attn/indexer/k_norm/rsqrt",
     ("attn", "indexer", "-")),
    ("jit(f)/M/model/layers_0/self_attn/index/gather",
     ("attn", "index", "-")),
    # (the scores and their choice are one kernel since PR 49; the packed
    # queries are its wrapper's, under the same scope)
    ("jit(f)/M/model/layers_0/self_attn/select/jit(_select)/pad",
     ("attn", "select", "-")),
    ("jit(f)/M/model/layers_0/self_attn/select/jit(_select)/index_select/"
     "pallas_call", ("attn", "index_select", "-")),
    ("jit(f)/M/model/layers_0/self_attn/select/cond/branch_1_fun/cumsum",
     ("attn", "select", "-")),
    ("jit(f)/M/model/layers_0/self_attn/jit(_chunk)/"
     "tokensel_chunk_attention/pallas_call",
     ("attn", "tokensel_chunk_attention", "-")),
    ("jit(f)/M/model/layers_0/self_attn/index_row/cond/branch_1_fun/"
     "select_n", ("attn", "index_row", "-")),
    ("jit(f)/M/model/layers_0/self_attn/index_row/cond/branch_1_fun/"
     "jit(_row_index)/row_index_scores/pallas_call",
     ("attn", "row_index_scores", "-")),
    ("jit(f)/M/model/layers_0/self_attn/select_row/top_k",
     ("attn", "select_row", "-")),
    ("jit(f)/M/model/layers_0/self_attn/tokensel_row_attention/gather",
     ("attn", "tokensel_row_attention", "-")),
    # latent rows chosen by token: the chunk pass's kernel, the one-row
    # path's gather and attention (the row's absorbed queries inside it), the
    # indexer's module fed by the query latent
    ("jit(f)/M/model/layers_0/self_attn/jit(_chunk)/"
     "latent_select_chunk_attention/pallas_call",
     ("attn", "latent_select_chunk_attention", "-")),
    ("jit(f)/M/model/layers_0/self_attn/latent_select_row_attention/cond/"
     "branch_1_fun/q_b_proj/dot_general",
     ("attn", "latent_select_row_attention", "-")),
    ("jit(f)/M/model/layers_0/self_attn/latent_select_row_attention/gather",
     ("attn", "latent_select_row_attention", "-")),
    ("jit(f)/M/model/layers_0/self_attn/indexer/wq_b/dot_general",
     ("attn", "indexer", "-")),
    # a model with window layers and full layers: the scope that says which
    # leads the sub-scope, a Pallas kernel's name behind it
    ("jit(f)/M/model/layers_1/self_attn/attn_window/q_proj/dot_general",
     ("attn", "attn_window", "-")),
    ("jit(f)/M/model/layers_1/self_attn/attn_window/jit(_chunk)/"
     "paged_chunk_attention/pallas_call",
     ("attn", "attn_window/paged_chunk_attention", "-")),
    ("jit(f)/M/model/layers_2/self_attn/attn_full/jit(_decode)/"
     "paged_decode_attention/pallas_call",
     ("attn", "attn_full/paged_decode_attention", "-")),
    ("jit(f)/M/model/layers_2/pre_mlp_layernorm/rsqrt",
     ("mlp", "pre_mlp_layernorm", "-")),
    # a router issued BEFORE its layer's attention (``models/smallthinker.py``:
    # the expert module called twice) is the expert layer's all the same
    ("jit(f)/M/model/layers_3/block_sparse_moe/moe.route/dot_general",
     ("moe", "moe.route", "-")),
    ("jit(f)/M/model/layers_3/block_sparse_moe/moe.route/top_k",
     ("moe", "moe.route", "-")),
    ("jit(f)/while/body/closed_call/sample/sample_logits_batched/reduce",
     ("sample", "sample_logits_batched", "-")),
    ("jit(f)/M/head.rows/gather", ("head", "head.rows", "-")),
    ("jit(f)/M/model/head.rows/gather", ("head", "head.rows", "-")),
    ("jit(train_step)/optim/mul", ("optim", "optim", "-")),
    ("jit(train_step)/zero.fetch/device_put", ("zero", "zero.fetch", "-")),
    ("jit(f)/M/model/layers_0/add;jit(f)/M/model/layers_0/mlp/mul",
     ("attn", None, "-")),
    ("jit(f)/SomeOtherModel/encoder/ff/dot_general", ("rest", None, "-")),
    ("", ("rest", None, "-")),
])
def test_classify(op_name, want):
    assert ds.classify(op_name) == want


def test_a_layers_norm_follows_its_mixer_and_an_expert_layer_its_scopes():
    m = "jit(f)/M/model/"
    kinds = {op[len(m):]: k for op, (k, _, _) in ds.classify_all([
        m + "layers_3/conv/in_proj/dot_general",
        m + "layers_3/operator_norm/rsqrt", m + "layers_3/add",
        m + "layers_3/feed_forward/moe.route/dot_general",
        m + "layers_3/feed_forward/reshape", m + "layers_3/ffn_norm/rsqrt",
        m + "layers_4/operator_norm/rsqrt",
        m + "layers_4/feed_forward/up_proj/dot_general"]).items()}
    assert kinds == {
        "layers_3/conv/in_proj/dot_general": "conv",
        "layers_3/operator_norm/rsqrt": "conv", "layers_3/add": "conv",
        "layers_3/feed_forward/moe.route/dot_general": "moe",
        "layers_3/feed_forward/reshape": "moe",
        "layers_3/ffn_norm/rsqrt": "moe",
        # a layer whose mixer is not in sight: attention
        "layers_4/operator_norm/rsqrt": "attn",
        "layers_4/feed_forward/up_proj/dot_general": "mlp"}


# -- (b) a compiled text, by hand --------------------------------------------

def _meta(path):
    return f'metadata={{op_name="jit(f)/M/model/{path}" stack_frame_id=3}}'


TEXT = f"""HloModule jit_f, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,64]) -> bf16[8,64] {{
  %param_0.1 = bf16[8,64]{{1,0}} parameter(0)
  ROOT %mul.9 = bf16[8,64]{{1,0}} multiply(%param_0.1, %param_0.1), {_meta("layers_0/mlp/mul")}
}}

ENTRY %main.1 (p0: bf16[64,256], p1: bf16[8,64]) -> bf16[8,256] {{
  %p0 = bf16[64,256]{{1,0}} parameter(0)
  %p1 = bf16[8,64]{{1,0}} parameter(1)
  %slice-start.1 = ((bf16[64,256]{{1,0}}), bf16[32,256]{{1,0:S(1)}}, s32[]{{:S(2)}}) slice-start(%p0), slice={{[0:32], [0:256]}}
  %slice-done.1 = bf16[32,256]{{1,0:S(1)}} slice-done(%slice-start.1)
  %custom-call.3 = bf16[64,256]{{1,0:S(1)}} custom-call(%slice-done.1), custom_call_target="ConcatBitcast"
  %fusion.7 = bf16[8,256]{{1,0}} fusion(%custom-call.3, %p1), kind=kOutput, calls=%fused_computation.1, {_meta("layers_0/mlp/gate_proj/dot_general")}
  %copy.4 = bf16[8,256]{{0,1}} copy(%fusion.7)
  %bitcast.5 = bf16[8,256]{{0,1}} bitcast(%copy.4)
  %far.1 = bf16[8,64]{{1,0}} copy(%p1)
  %far.2 = bf16[8,64]{{1,0}} copy(%far.1)
  %far.3 = bf16[8,64]{{1,0}} copy(%far.2)
  %far.4 = bf16[8,64]{{1,0}} copy(%far.3)
  %fusion.8 = bf16[8,64]{{1,0}} fusion(%far.4), kind=kLoop, calls=%fused_computation.1, {_meta("layers_0/self_attn/q_proj/dot_general")}
  %lone.1 = s32[] constant(0)
  ROOT %tuple.2 = (bf16[8,256]{{0,1}}, bf16[8,64]{{1,0}}, s32[]) tuple(%bitcast.5, %fusion.8, %lone.1)
}}
"""


def test_scope_table_own_user_operand_the_limit_and_none():
    t = ds.scope_table(TEXT)
    assert t["fusion.7"] == ("mlp", "gate_proj", "-", "own")
    assert t["mul.9"] == ("mlp", None, "-", "own")
    # user fallback: one, two and three hops
    assert t["custom-call.3"] == ("mlp", "gate_proj", "-", "user")
    assert t["slice-done.1"] == ("mlp", "gate_proj", "-", "user")
    assert t["slice-start.1"] == ("mlp", "gate_proj", "-", "user")
    # the weight itself is four hops from the fusion by its users, and has
    # no operand: ``p1`` reaches ``fusion.7`` in one
    assert t["p0"] == ("rest", None, "-", "none")
    assert t["p1"][0:1] + t["p1"][3:] == ("mlp", "user")
    # operand fallback, where no user is scoped within three hops
    assert t["copy.4"] == ("mlp", "gate_proj", "-", "operand")
    assert t["bitcast.5"] == ("mlp", "gate_proj", "-", "operand")
    # the three-hop limit: ``far.1`` is four users from ``fusion.8``, and
    # its operand is a parameter
    assert t["far.2"] == ("attn", "q_proj", "-", "user")
    assert t["far.1"] == ("rest", None, "-", "none")
    assert t["lone.1"] == ("rest", None, "-", "none")
    assert t.signatures["fusion.7"] == ("fusion", "bf16[8,256]")
    assert t.signatures["slice-start.1"] == ("slice-start", "bf16[64,256]")
    assert set(v for s in t.values() for v in (s.via,)) == set(ds.VIAS)


def test_scope_table_tells_an_expert_layer_and_a_layers_mixer():
    def line(name, path):
        return (f"  %{name} = bf16[8,64]{{1,0}} fusion(%p), kind=kLoop, "
                f"calls=%c, {_meta(path)}")

    text = "\n".join([
        "ENTRY %main (p: bf16[8,64]) -> bf16[8,64] {",
        "  %p = bf16[8,64]{1,0} parameter(0)",
        line("a.1", "layers_0/conv/in_proj/dot_general"),
        line("a.2", "layers_0/operator_norm/rsqrt"),
        line("a.3", "layers_0/feed_forward/gate_proj/dot_general"),
        line("a.4", "layers_0/ffn_norm/rsqrt"),
        line("b.1", "layers_1/self_attn/q_proj/dot_general"),
        line("b.2", "layers_1/operator_norm/rsqrt"),
        line("b.3", "layers_1/feed_forward/moe.route/dot_general"),
        line("b.4", "layers_1/feed_forward/reshape"),
        line("b.5", "layers_1/ffn_norm/rsqrt"),
        line("b.6", "layers_1/add"),
        "}"])
    t = ds.scope_table(text)
    kinds = {n: s.kind for n, s in t.items()}
    assert kinds == {"p": "conv", "a.1": "conv", "a.2": "conv",
                     "a.3": "mlp", "a.4": "mlp", "b.1": "attn",
                     "b.2": "attn", "b.3": "moe", "b.4": "moe", "b.5": "moe",
                     "b.6": "attn"}


# -- (c) the scopes change no program ----------------------------------------

def _strip(text):
    text = re.sub(r"loc\(.*?\)\)?", "", text)
    return "\n".join(l.rstrip() for l in text.splitlines()
                     if not l.lstrip().startswith("#loc"))


@contextlib.contextmanager
def _null_scope(name):
    yield


@pytest.mark.parametrize("family", ["olmoe", "lfm2"])
def test_the_serving_programs_lower_to_the_same_text_without_the_scopes(
        family, monkeypatch):
    """The expert layer's stages, ``sample`` and ``counts`` are all in
    these two families' programs."""
    def texts():
        eng = _engine(family)
        try:
            return {k: _strip(fn.lower(*args).as_text())
                    for k, (fn, args) in _program_avals(eng).items()}
        finally:
            eng.close()

    with_scopes = texts()
    monkeypatch.setattr(jax, "named_scope", _null_scope)
    assert texts() == with_scopes


def test_the_train_step_lowers_to_the_same_text_without_the_scopes(
        devices, monkeypatch):
    from deepspeed_tpu.models.llama import LlamaLMLoss, get_config

    def text():
        engine, batch = _train_engine(LlamaLMLoss(get_config(
            "tinyllama", scan_layers=True, remat=True, remat_policy="full",
            use_flash_attention=False, dtype=jnp.bfloat16)))
        return _strip(engine.lower_train_step(batch).as_text())

    with_scopes = text()
    assert "zero.fetch" not in with_scopes      # locations only
    monkeypatch.setattr(jax, "named_scope", _null_scope)
    assert text() == with_scopes


# -- (d), (e) the registry ---------------------------------------------------

def _counted(calls, name, scale):
    def run(x, w):
        calls.append(name)
        with jax.named_scope("sample"):
            return jnp.tanh(x @ w) * scale

    run.__name__ = run.__qualname__ = name
    return jax.jit(run)


def test_tables_reuses_the_trace_and_the_executable(tmp_path):
    reg, calls = ds.Registry(), []
    fn = _counted(calls, "prog", 2.0)
    x, w = jnp.ones((4, 8)), np.ones((8, 16), np.float32)
    reg.register("prog", fn, (x, w))
    reg.register("prog", fn, (x, w))            # the same function: once
    assert reg.programs() == {"prog": 1}
    fn(x, w)
    assert calls == ["prog"]
    with count_compiles() as compiles:
        tables = reg.tables()
    assert calls == ["prog"]                    # the body did not run again
    assert compiles.count == 0                  # nor the compiler
    (table,) = tables["prog"]
    assert {"sample"} <= {s.kind for s in table.values()}
    doc = json.load(open(reg.dump(str(tmp_path / "scopes.json"))))
    assert set(doc) == {"prog"} and len(doc["prog"]) == 1
    assert all(len(v) == 4 for v in doc["prog"][0].values())
    # nothing is kept of an argument but its aval
    (_, args, _), = reg._programs["prog"]
    assert all(isinstance(a, jax.ShapeDtypeStruct) for a in args)
    # a function that is gone takes its entry with it
    del fn
    import gc
    gc.collect()
    assert reg.programs() == {} and reg.tables() == {}


def test_two_variants_under_one_name_join_to_the_right_one():
    reg, calls = ds.Registry(), []
    greedy = _counted(calls, "block", 1.0)

    def run(x, w):
        with jax.named_scope("optim"):
            return jnp.sum(jnp.sin(x[:2] @ w), axis=0)

    run.__name__ = run.__qualname__ = "block"
    sampled = jax.jit(run)
    x, w = jnp.ones((4, 8)), jnp.ones((8, 16))
    for fn in (greedy, sampled):
        reg.register("block", fn, (x, w))
        fn(x, w)
    tables = reg.tables()["block"]
    assert len(tables) == 2 and reg.programs() == {"block": 2}
    # an instruction both texts name, with another opcode or shape in each
    shared = [n for n in tables[0] if n in tables[1]
              and tables[0].signatures[n] != tables[1].signatures[n]
              and tables[0][n].kind != tables[1][n].kind]
    assert shared, "the two texts share no such name: choose other bodies"
    for n in shared:
        for t in tables:
            assert ds.lookup(tables, n, t.signatures[n]) == t[n]
    # a name only one variant holds is found there, whatever the shape
    only = next(n for n in tables[1] if n not in tables[0])
    assert ds.lookup(tables, only, ("fusion", "")) == tables[1][only]
    assert ds.lookup(tables, "no-such-name") is None


def test_registration_survives_a_second_engine(monkeypatch):
    reg = ds.Registry()
    monkeypatch.setattr(ds, "register", reg.register)
    prompt = [np.arange(1, 21, dtype=np.int32)]
    first = _engine("mistral")
    second = _engine("mistral")
    try:
        first.generate_all(prompt, max_new_tokens=6)
        assert reg.programs() == {"ragged_fused_step": 1,
                                  "ragged_decode_block": 1}
        second.generate_all(prompt, max_new_tokens=6)
        assert reg.programs() == {"ragged_fused_step": 2,
                                  "ragged_decode_block": 2}
        with count_compiles() as compiles:
            tables = reg.tables()
        assert compiles.count == 0
        for variants in tables.values():
            assert len(variants) == 2
            for t in variants:
                kinds = {s.kind for s in t.values()}
                assert {"attn", "mlp", "head", "sample"} <= kinds
    finally:
        first.close()
        second.close()
