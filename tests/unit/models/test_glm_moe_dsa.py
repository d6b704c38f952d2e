"""GLM-5's family (latent attention over the rows an indexer fed by the
query latent chooses; a sigmoid router with a selection bias of which this
chip holds a share, beside a shared expert) against the benchmark's plain
float32 reference, which scores, sorts and attends query by query in the
EXPANDED form with no cache: through ``apply`` on whole sequences, and
through the SERVING FORM over the latent and index-key pools on a schedule
of ragged ticks (three sequences of 150, 70 and 5 tokens packed into ticks
of 52 rows: chunks that cross pages of 16 and the switch to chosen rows at
``index_topk`` 24, several chunks a tick, sequences that go on as one-row
decode rows while others prefill, then ticks as a decode block makes them);
then that each way a wrong program could differ FAILS the same comparison;
that ``model.init``'s shapes count the parameters ISSUE 69 counts; and that
the sixteen shares of a layer add up to the uncut layer.

Everything is float32 with no kernel (the twins), so the comparison is
about the mathematics alone.  ``TOL`` = 2e-4: float32 rounding through the
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
from benchmark.reference import glm_moe_dsa as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import glm_moe_dsa as glm5  # noqa: E402
from deepspeed_tpu.models.glm4_moe_lite import routed_experts  # noqa: E402
from deepspeed_tpu.models.llama import count_params  # noqa: E402
from deepspeed_tpu.ops import token_select_attention as tsa  # noqa: E402

TOL = 2e-4
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
           use_flash_attention=False)
CFG = glm5.get_config("tinyglm5", **F32)
MODEL = glm5.GlmMoeDsaForCausalLM(CFG)


def ref_cfg(cfg):
    """The reference's view of a preset: the source's key names."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        first_k_dense_replace=cfg.first_k_dense_replace,
        num_attention_heads=cfg.num_attention_heads,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.rms_norm_eps,
        rope_parameters=dict(cfg.rope_parameters),
        index_n_heads=cfg.index_n_heads, index_head_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk, n_routed_experts=cfg.n_routed_experts,
        expert_shards=cfg.expert_shards, expert_shard=cfg.expert_shard,
        n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor)


REF_CFG = ref_cfg(CFG)
PAGE, SLOTS, CHUNK, MAX_LEN = 16, 4, 48, 256
LENGTHS = (150, 70, 5)
TAIL = 6                            # tokens a sequence ends on as decode rows


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


def _params(model, seed=5):
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales and the LayerNorm's bias away from their first values,
    # so that every one of them is in the comparison
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    unbox = lambda l: getattr(l, "value", l)
    return jax.tree_util.tree_map_with_path(
        lambda path, l: unbox(l) + 0.2 * jax.random.normal(
            next(keys), unbox(l).shape) if unbox(l).ndim == 1 else l, params,
        is_leaf=lambda l: hasattr(l, "value"))


@pytest.fixture(scope="module")
def served():
    return MODEL, _params(MODEL)


def run_schedule(params, lengths=LENGTHS, chunk=CHUNK, tail=TAIL):
    """Every logit row of teacher-forced sequences through the serving
    form: ``{sequence: [len, V]}``."""
    pp = MAX_LEN // PAGE
    cfg = programs.serving_config(
        CFG, max_seq_len=MAX_LEN, page_size=PAGE, num_pages=SLOTS * pp + 1,
        tensor_parallel=False, kv_cache_dtype="none")
    model = glm5.GlmMoeDsaForCausalLM(cfg)
    T = chunk + SLOTS
    cache = programs.init_cache(model, max_seqs=SLOTS, width=T)
    seqs = [_tokens(n, 20 + i) for i, n in enumerate(lengths)]
    slots = [2, 0, 3][:len(seqs)]                   # out of order
    table = np.full((SLOTS, pp), -1, np.int32)
    for s in slots:
        table[s] = 1 + s * pp + np.arange(pp)
    done = [0] * len(seqs)
    got = {i: np.zeros((n, 96), np.float32) for i, n in enumerate(lengths)}
    dest = lambda s, p: table[s, p // PAGE] * PAGE + p % PAGE

    def program(flag):
        @jax.jit
        def apply(cache, ids, pos, meta):
            out, vars_ = model.apply(
                {"params": params, "cache": cache}, ids, positions=pos,
                mutable=["cache"], ragged_meta={**meta, flag: True})
            return out[0], vars_["cache"]
        return apply

    fused, decode = program("fused_tick"), program("one_query_a_slot")

    def tick(takes):
        """One fused tick: ``takes[i]`` rows of sequence ``i``."""
        nonlocal cache
        ids, pos, new = (np.zeros((T,), np.int32) for _ in range(3))
        kv_lens = np.zeros((SLOTS,), np.int32)
        pages = np.full((SLOTS, pp), -1, np.int32)
        cu = np.zeros((SLOTS + 1,), np.int32)
        t = j = 0
        where = []
        for i, take in enumerate(takes):
            if take <= 0:
                continue
            lo = done[i]
            ids[t:t + take] = seqs[i][lo:lo + take]
            pos[t:t + take] = np.arange(lo, lo + take)
            new[t:t + take] = [dest(slots[i], p) for p in range(lo, lo + take)]
            kv_lens[j], pages[j] = lo + take, table[slots[i]]
            cu[j + 1] = cu[j] + take
            where.append((i, lo, t, take))
            done[i] += take
            t += take
            j += 1
        cu[j + 1:] = cu[j]
        meta = {"kv_lens": kv_lens, "page_indices": pages, "cu_q_lens": cu,
                "num_seqs": np.asarray([j], np.int32), "new_kv_dest": new,
                "live": np.arange(T) < t}
        lg, cache = fused(cache, ids[None], pos[None],
                          jax.tree_util.tree_map(jnp.asarray, meta))
        for i, lo, t0, take in where:
            got[i][lo:lo + take] = np.asarray(lg[t0:t0 + take])

    # prompts in ticks of chunk + SLOTS rows, the budget shared in order; a
    # sequence whose prompt part is done goes on a row a tick
    while any(done[i] < n - tail for i, n in enumerate(lengths)):
        budget, takes = chunk, []
        for i, n in enumerate(lengths):
            left = n - tail - done[i]
            if left > 0:
                takes.append(min(left, budget))
                budget -= takes[-1]
            else:                       # a decode row, outside the budget
                takes.append(1 if done[i] < n - 2 else 0)
        tick(takes)
    # what is left, as a decode block's ticks: row s IS slot s
    while any(done[i] < n for i, n in enumerate(lengths)):
        ids, pos = (np.zeros((SLOTS,), np.int32) for _ in range(2))
        live = np.zeros((SLOTS,), bool)
        for i, n in enumerate(lengths):
            if done[i] < n:
                s = slots[i]
                ids[s], pos[s], live[s] = seqs[i][done[i]], done[i], True
        meta = {"kv_lens": np.where(live, pos + 1, 1).astype(np.int32),
                "page_indices": table,
                "cu_q_lens": np.arange(SLOTS + 1, dtype=np.int32),
                "num_seqs": np.asarray([SLOTS], np.int32),
                "new_kv_dest": np.where(
                    live, [dest(s, p) for s, p in enumerate(pos)], 0
                ).astype(np.int32), "live": live}
        lg, cache = decode(cache, ids[None], pos[None],
                           jax.tree_util.tree_map(jnp.asarray, meta))
        for i, n in enumerate(lengths):
            if done[i] < n:
                got[i][done[i]] = np.asarray(lg[slots[i]])
                done[i] += 1
    return seqs, got


@pytest.fixture(scope="module")
def schedule(served):
    return run_schedule(served[1])


def worst_difference(params, seqs, got, mutate=None):
    return max(float(np.abs(got[i] - np.asarray(
        ref.logits(params, REF_CFG, seq, mutate=mutate))).max())
        for i, seq in enumerate(seqs))


def test_the_presets_are_the_published_shapes():
    big = glm5.get_config("glm-5")
    assert (big.num_hidden_layers, big.hidden_size, big.num_attention_heads,
            big.q_lora_rank, big.kv_lora_rank, big.qk_nope_head_dim,
            big.qk_rope_head_dim, big.v_head_dim, big.head_dim,
            big.n_routed_experts, big.num_local_experts, big.expert_shards,
            big.num_experts_per_tok, big.moe_intermediate_size,
            big.intermediate_size, big.first_k_dense_replace, big.vocab_size,
            big.rope_theta, big.rms_norm_eps, big.routed_scaling_factor) == (
                78, 6144, 64, 2048, 512, 192, 64, 256, 256, 256, 256, 1, 8,
                2048, 12288, 3, 154880, 1e6, 1e-5, 2.5)
    assert big.indexer == tsa.Indexer(32, 128, 1, 2048)
    assert abs(big.indexer.head_scale - 32 ** -0.5 * 128 ** -0.5) < 1e-12
    assert paged.state_layers(big) == 0 and paged.kv_layers(big) == 78
    kind = paged.describe(big)
    assert [type(p).__name__ for p in kind.parts] == ["LatentSelect"]
    assert kind.latent_width == 640 and kind.parts[0].indexer == big.indexer


def test_the_served_share_counts_its_parameters_and_bytes_by_shapes():
    """``benchmark/configs/glm-5-serve.json`` by ``model.init``'s shapes:
    ISSUE 69's 4.727 B parameters (attention 174.4 M a layer with its
    indexer's 9.37 M, the dense layer 400.9 M, an expert 37.75 M, an expert
    layer 213.7 M beside its experts and 817.7 M with the 16 it holds, an
    eighth of the vocabulary twice) and 1,536 bytes a cached token a layer,
    to the parameter and the byte."""
    config = H.load_json(os.path.join(
        ROOT, "benchmark", "configs", "glm-5-serve.json"))
    cfg = H.build_model_config(config)
    assert (cfg.n_routed_experts, cfg.expert_shards, cfg.expert_shard,
            cfg.num_experts_per_tok) == (16, 16, 0, 8)
    model = glm5.GlmMoeDsaForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    per = lambda tree: count_params(tree)
    E, V, H_, X = 6144, 19360, 64, 16
    attn = shapes["model"]["layers_1"]["self_attn"]
    assert per(attn["q_a_proj"]) == E * 2048 == 12_582_912
    assert per(attn["q_b_proj"]) == 2048 * H_ * 256 == 33_554_432
    assert per(attn["kv_a_proj_with_mqa"]) == E * 576 == 3_538_944
    assert per(attn["kv_b_proj"]) == 512 * H_ * 448 == 14_680_064
    assert per(attn["o_proj"]) == H_ * 256 * E == 100_663_296
    assert per(attn["indexer"]) == (2048 * 32 * 128 + E * 128 + 2 * 128
                                    + E * 32) == 9_371_904
    assert per(attn) == 174_394_112
    dense, expert = shapes["model"]["layers_0"], shapes["model"]["layers_1"]
    assert per(dense) == per(attn) + 3 * E * 12288 + 2 * E == 400_898_816
    one = 3 * E * 2048
    assert one == 37_748_736
    assert expert["mlp"]["gate"].shape == (E, 256)       # the router, whole
    assert expert["mlp"]["expert_bias"].shape == (256,)
    assert expert["mlp"]["w1"].shape == (X, E, 2048)     # the banks held
    assert per(expert) - X * one == 213_728_256
    assert per(expert) == 817_708_032
    total = count_params(shapes)
    assert total == per(dense) + 5 * per(expert) + 2 * V * E + E
    assert total == 4_727_340_800
    assert 9.4e9 < total * 2 < 9.5e9                             # bf16
    page_bytes = programs.paged_kv_page_bytes(model, cfg, 64, "none")
    # a latent row of 576 stored as 640 and one index key of 128 a layer
    assert page_bytes == 64 * 6 * (640 + 128) * 2 == 64 * 9_216
    from benchmark import flops_glm5
    assert page_bytes == 64 * flops_glm5.pool_bytes_per_token(config)
    pool = config["engine"]["kv_pool_bytes"]
    # all eight callers hold their longest request, and the null page
    assert pool % page_bytes == 0 and pool // page_bytes >= 8 * 258 + 1


def test_the_first_values_are_the_ones_the_check_was_read_with():
    """What ``init`` draws decides whether the benchmark's check can fail
    (the module docstring): the embedding at EMBED_INIT_STD a value, the
    routed experts' down-projections at EXPERT_DOWN_INIT_SCALE of
    lecun-normal's size, the selection bias at 0.03, every other matrix at
    lecun-normal's own."""
    assert glm5.EMBED_INIT_STD == 1.0
    assert glm5.EXPERT_DOWN_INIT_SCALE == 0.12 == CFG.expert_down_init_scale
    drawn = jax.jit(MODEL.init)(jax.random.PRNGKey(3), np.zeros(
        (1, 8), np.int32))["params"]["model"]
    std = lambda leaf: float(jnp.std(jax.tree_util.tree_leaves(leaf)[0]))
    assert abs(std(drawn["embed_tokens"]["embedding"]) - 1) < 0.05
    layer = drawn["layers_1"]
    assert abs(std(layer["mlp"]["w2"]) * np.sqrt(CFG.moe_intermediate_size)
               / glm5.EXPERT_DOWN_INIT_SCALE - 1) < 0.15
    assert 0.01 < std(layer["mlp"]["expert_bias"]) < 0.06
    for leaf in (layer["mlp"]["w1"], layer["self_attn"]["q_a_proj"]["kernel"],
                 layer["self_attn"]["indexer"]["wk"]["kernel"]):
        assert abs(std(leaf) * np.sqrt(CFG.hidden_size) - 1) < 0.1


@pytest.mark.parametrize("kw,named", [
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(qk_head_dim=32), "qk_head_dim"),
    (dict(rope_parameters=dict(rope_theta=1e6, rope_type="yarn")),
     "rope_parameters"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(n_group=2), "n_group"),
    (dict(decode=True), "dense decode cache")])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        glm5.get_config("tinyglm5", **kw)


def test_the_index_keys_turn_their_first_rope_dims_alone():
    """The indexer's rotation: the first ``qk_rope_head_dim`` of an index
    head's dims turn (half-split), the rest pass; the reference's own
    rotation says the same."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 16))
    pos = jnp.arange(7, 47)
    mine = np.asarray(glm5._rope_first(x, pos, 1e6, 8))[0]
    theirs = np.asarray(ref._rope(x[0], pos, 1e6, 8))
    np.testing.assert_allclose(mine, theirs, atol=1e-6)
    np.testing.assert_array_equal(mine[..., 8:], np.asarray(x)[0, ..., 8:])
    assert np.abs(mine[..., :8] - np.asarray(x)[0, ..., :8]).max() > 0.1


def test_apply_matches_the_plain_reference(served):
    model, params = served
    for n, seed in ((150, 1), (40, 2)):
        toks = _tokens(n, seed)
        got = np.asarray(model.apply({"params": params},
                                     np.asarray(toks)[None]))[0]
        want = np.asarray(ref.logits(params, REF_CFG, toks))
        assert np.abs(got - want).max() < TOL
        assert want.std() > 0.5


def test_the_serving_form_matches_the_reference(served, schedule):
    """Prefill then decode through the two pools, across chunks, pages and
    the switch to chosen rows, against the full forward pass."""
    seqs, got = schedule
    assert worst_difference(served[1], seqs, got) < TOL


@pytest.mark.parametrize("chunk,tail", [(48, 6), (20, 1), (100, 30)])
def test_a_prompt_chunked_three_ways_gives_the_same_logits(served, chunk,
                                                           tail):
    """The choice is a rule by query: however the 150 tokens are cut into
    chunks and decode rows, every position's logits are the reference's."""
    seqs, got = run_schedule(served[1], lengths=(150,), chunk=chunk,
                             tail=tail)
    assert worst_difference(served[1], seqs, got) < TOL


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_comparison(served, schedule, mutate):
    """Each way a wrong program could differ moves a logit by far more than
    the tolerance."""
    seqs, got = schedule
    assert worst_difference(served[1], seqs, got, mutate) > 100 * TOL


def test_selection_left_out_shows_only_past_topk(served):
    toks = _tokens(150, 1)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    dense = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                  mutate="selection_left_out"))
    topk = CFG.index_topk
    assert np.abs(want - dense)[:topk].max() == 0
    assert np.abs(want - dense)[topk:].max() > 100 * TOL


def test_the_chosen_tokens_are_the_references(served):
    """The set a query takes in the first layer, program against reference:
    the program's scores through ``token_mask`` (the whole-sequence form's
    choice) and through the one-row path's ``lax.top_k``, against the
    reference's full sort, wherever the k-th and (k+1)-th scores are apart."""
    model, params = served
    toks = np.asarray(_tokens(150, 3))
    taken = np.asarray(ref.forward(params, REF_CFG, toks, keep=True)[1][0])
    topk = CFG.index_topk
    assert (taken.sum(-1) == np.minimum(np.arange(150) + 1, topk)).all()
    _, state = model.apply({"params": params}, toks[None],
                           capture_intermediates=lambda m, _: isinstance(
                               m, glm5.LatentIndexer))
    qI, kI, w = state["intermediates"]["model"]["layers_0"]["self_attn"][
        "indexer"]["__call__"][0]
    s = jnp.einsum("qhd,kd->qhk", qI[0], kI[0])
    score = jnp.einsum("qh,qhk->qk", w[0], jnp.maximum(s, 0.0))
    seen = jnp.arange(150)[None] <= jnp.arange(150)[:, None]
    mine = np.asarray(tsa.token_mask(score, seen, topk))
    ordered = np.sort(np.where(np.asarray(seen), np.asarray(score), -np.inf),
                      axis=-1)[:, ::-1]
    apart = np.ones((150,), bool)
    apart[topk:] = (ordered[topk:, topk - 1] - ordered[topk:, topk]) > 1e-5
    assert apart.sum() > 100
    np.testing.assert_array_equal(mine[apart], taken[apart])
    rows = tsa.row_choice(jnp.where(seen, score, -jnp.inf), topk)
    for t in np.nonzero(apart)[0]:
        idx = np.asarray(rows[0])[t][np.asarray(rows[1])[t]]
        np.testing.assert_array_equal(np.sort(idx), taken[t].nonzero()[0])


def _uncut(seed=11):
    """A tiny model that holds ALL 8 of its router's experts, and its
    parameters."""
    cfg = glm5.get_config("tinyglm5", n_routed_experts=8, expert_shards=1,
                          **F32)
    model = glm5.GlmMoeDsaForCausalLM(cfg)
    return cfg, model, _params(model, seed)


def _share(tree, shard, held):
    """``tree`` with every expert layer's banks cut to share ``shard``."""
    cut = lambda path, leaf: (
        leaf[shard * held:(shard + 1) * held]
        if getattr(path[-1], "key", None) in ("w1", "w2", "w3") else leaf)
    return jax.tree_util.tree_map_with_path(cut, tree)


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(shards):
    """THE SHARE TIED TO THE MODEL: what each of the chips that share an
    expert layer computes for the rows routed to ITS experts (the program's
    own routed half, ``held_moe`` under the whole router), summed over the
    shares, with the shared expert counted once, is the uncut reference's
    layer: nothing is computed twice and nothing is left out."""
    cfg, _, params = _uncut()
    layer = params["model"]["layers_1"]
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 300, cfg.hidden_size))
    held = 8 // shards
    routed = 0.0
    for shard in range(shards):
        part = glm5.get_config("tinyglm5", n_routed_experts=held,
                               expert_shards=shards, expert_shard=shard,
                               paged_decode=True, **F32)
        y, _ = routed_experts(part).apply(
            {"params": _share(layer["mlp"], shard, held)}, h)
        routed = routed + np.asarray(y[0])
    kw = dict(top_k=cfg.num_experts_per_tok, renormalise=True,
              scaling=cfg.routed_scaling_factor, mutate=None,
              precision="float32")
    with jax.default_matmul_precision("highest"):
        want, (ids, _) = ref._experts(h[0], layer["mlp"], first_held=0, **kw)
        shared = ref._mlp(h[0], layer["shared_experts"], precision="float32")
        np.testing.assert_allclose(routed, np.asarray(want), atol=2e-5)
        # most of the 8 experts took rows: most shares had work
        assert len(set(np.asarray(ids).reshape(-1))) >= 6
        # and ONE share's part is the reference's under that share's range
        one, _ = ref._experts(h[0], _share(layer["mlp"], 1, held),
                              first_held=held, **kw)
    part = glm5.get_config("tinyglm5", n_routed_experts=held,
                           expert_shards=shards, expert_shard=1,
                           paged_decode=True, **F32)
    y, _ = routed_experts(part).apply(
        {"params": _share(layer["mlp"], 1, held)}, h)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(one), atol=2e-5)
    assert np.abs(np.asarray(shared)).max() > 0.01


def test_a_share_of_the_model_is_the_reference_with_the_same_share():
    """The whole model at a share (4 of 8 held, shard 1): program and
    reference leave out the same 4 experts' parts."""
    cfg, _, params = _uncut()
    part = glm5.get_config("tinyglm5", n_routed_experts=4, expert_shards=2,
                           expert_shard=1, **F32)
    tree = _share(params, 1, 4)
    toks = _tokens(60, 4)
    got = np.asarray(glm5.GlmMoeDsaForCausalLM(part).apply(
        {"params": tree}, np.asarray(toks)[None]))[0]
    want = np.asarray(ref.logits(tree, ref_cfg(part), toks))
    assert np.abs(got - want).max() < TOL
    whole = np.asarray(ref.logits(params, ref_cfg(cfg), toks))
    assert np.abs(whole - want).max() > 100 * TOL


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(40, 2)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    low = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                precision="float8"))
    assert np.abs(want - low).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    text = open(ref.__file__).read()
    assert "deepspeed_tpu" not in text.split('"""', 2)[2]
