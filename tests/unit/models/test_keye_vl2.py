"""The language model of Keye-VL-2.0 (attention over the tokens an indexer
of its own chooses, 128-expert-shaped FFN) against the benchmark's plain
float32 reference, which scores, sorts and attends query by query with no
cache: through ``apply`` on whole sequences, and through the SERVING FORM
over the two paged pools on a schedule of ragged ticks (three sequences of
150, 70 and 5 tokens packed into ticks of 52 rows: chunks that cross pages
of 16 and the switch to chosen tokens at ``topk`` 24, several chunks a
tick, sequences that go on as one-row decode rows while others prefill,
then ticks as a decode block makes them); then that each way a wrong
program could differ FAILS the same comparison, "selection left out",
"relu left out", "head weights left out" and "eight weights not
renormalised" first.

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
from benchmark.reference import keye_vl2 as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import keye_vl2 as keye  # noqa: E402
from deepspeed_tpu.models.llama import count_params  # noqa: E402
from deepspeed_tpu.models.llama import rotary_embedding  # noqa: E402
from deepspeed_tpu.ops import token_select_attention as tsa  # noqa: E402

TOL = 2e-4
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
           use_flash_attention=False,
           drop_tokens=False)   # plain ``apply`` keeps its capacity paths
CFG = keye.get_config("tinykeye", **F32)
MODEL = keye.KeyeVL2ForCausalLM(CFG)


def ref_cfg(cfg):
    """The reference's view of a preset: the source's key names."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        rope_scaling={k: list(v) if isinstance(v, tuple) else v
                      for k, v in cfg.rope_scaling.items()},
        sa_config=dict(cfg.sa_config),
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob)


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
    model = keye.KeyeVL2ForCausalLM(cfg)
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
            return out[0][0], vars_["cache"]
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
    big = keye.get_config("keye-vl2-30b-a3b")
    assert (big.num_hidden_layers, big.hidden_size, big.head_dim,
            big.num_attention_heads, big.num_key_value_heads,
            big.num_experts, big.num_local_experts, big.num_experts_per_tok,
            big.expert_intermediate, big.intermediate_size, big.vocab_size,
            big.rope_theta, big.rms_norm_eps, big.norm_topk_prob) == (
                48, 2048, 128, 32, 4, 128, 128, 8, 768, 6144, 151936, 1e7,
                1e-6, True)
    assert big.indexer == tsa.Indexer(16, 64, 1, 2048, 512, 512)
    assert abs(big.indexer.head_scale - 1 / 32.0) < 1e-9
    assert tuple(big.rope_scaling["mrope_section"]) == (16, 24, 24)
    assert paged.state_layers(big) == 0 and paged.kv_layers(big) == 48
    kind = paged.describe(big)
    assert [type(p).__name__ for p in kind.parts] == ["IndexSelect"]


def test_the_served_layers_count_their_parameters_and_bytes_by_shapes():
    """``benchmark/configs/keye-vl2-30b-a3b-serve.json`` by ``model.init``'s
    shapes: ISSUE 47's 4,375 M parameters and 13,056 bytes a cached token,
    to the parameter and the byte."""
    config = H.load_json(os.path.join(
        ROOT, "benchmark", "configs", "keye-vl2-30b-a3b-serve.json"))
    cfg = H.build_model_config(config)
    model = keye.KeyeVL2ForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    per = lambda tree: count_params(tree)
    layer = shapes["model"]["layers_0"]
    E, V, d, H_, Hkv, X, F = 2048, 151936, 128, 32, 4, 128, 768
    attention = 2 * E * H_ * d + 2 * E * Hkv * d + 2 * d        # 18.87 M
    indexer = E * 16 * 64 + E * 64 + E * 16 + 2 * 64             # 2.26 M
    assert per(layer["self_attn"]) == attention + indexer
    assert per(layer["mlp"]) == E * X + X * 3 * E * F            # 604.2 M
    assert per(layer) == attention + indexer + E * X + X * 3 * E * F + 2 * E
    total = count_params(shapes)
    assert total == 6 * per(layer) + 2 * V * E + E
    assert total == 4_374_622_464
    assert total * 2 < 8.76e9                                    # bf16
    page_bytes = programs.paged_kv_page_bytes(model, cfg, 64, "none")
    # K and V of 4 heads of 128 in bf16 and one index key of 64 a layer
    assert page_bytes == 64 * 6 * (2 * 4 * 128 * 2 + 64 * 2)
    assert page_bytes == 64 * 13_056
    from benchmark import flops_keye
    assert page_bytes == 64 * flops_keye.pool_bytes_per_token(config)
    assert flops_keye.index_bytes_per_token(config) == 768
    pool = config["engine"]["kv_pool_bytes"]
    assert pool % page_bytes == 0 and pool // page_bytes >= 8 * 513 + 1


def test_the_first_values_are_the_ones_the_check_was_read_with():
    """What ``init`` draws decides whether the benchmark's check can fail
    (the module docstring): the embedding at EMBED_INIT_STD a value (at
    flax's ``1/sqrt(hidden)`` the streams of different tokens collapse and
    a lower precision moves no token), the experts' down-projections at
    EXPERT_DOWN_INIT_SCALE of lecun-normal's size, every other matrix at
    lecun-normal's own."""
    assert keye.EMBED_INIT_STD == 1.0 and keye.EXPERT_DOWN_INIT_SCALE == 0.3
    drawn = jax.jit(MODEL.init)(jax.random.PRNGKey(3), np.zeros(
        (1, 8), np.int32))["params"]["model"]
    std = lambda leaf: float(jnp.std(jax.tree_util.tree_leaves(leaf)[0]))
    assert abs(std(drawn["embed_tokens"]["embedding"])
               / keye.EMBED_INIT_STD - 1) < 0.05
    layer = drawn["layers_1"]
    width = CFG.moe_intermediate_size
    assert abs(std(layer["mlp"]["w2"]) * np.sqrt(width)
               / keye.EXPERT_DOWN_INIT_SCALE - 1) < 0.1
    for leaf in (layer["mlp"]["w1"], layer["self_attn"]["q_proj"]["kernel"],
                 layer["self_attn"]["indexer"]["q_proj"]["kernel"]):
        assert abs(std(leaf) * np.sqrt(CFG.hidden_size) - 1) < 0.1


@pytest.mark.parametrize("kw,named", [
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(mlp_only_layers=(0,)), "mlp_only_layers"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(decode=True), "dense decode cache")])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        keye.get_config("tinykeye", **kw)


def test_sectioned_rotation_on_equal_rows_is_plain_rotation():
    """M-RoPE's three position rows are equal for a text token, and then
    the reference's sectioned rotation IS the program's plain one."""
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 3, 128))
    pos = jnp.arange(7, 47)
    mine = rotary_embedding(x.transpose(1, 0, 2)[None], pos, 1e7)[0]
    theirs = ref._rope_sections(x, jnp.stack([pos, pos, pos]), 1e7,
                                [16, 24, 24])
    np.testing.assert_allclose(np.asarray(mine.transpose(1, 0, 2)),
                               np.asarray(theirs), atol=1e-6)
    # and rows that differ do not: an image token would need the sections
    other = ref._rope_sections(x, jnp.stack([pos, pos + 3, pos + 5]), 1e7,
                               [16, 24, 24])
    assert np.abs(np.asarray(other) - np.asarray(theirs)).max() > 0.1


def test_apply_matches_the_plain_reference(served):
    model, params = served
    for n, seed in ((150, 1), (40, 2)):
        toks = _tokens(n, seed)
        got = np.asarray(model.apply({"params": params},
                                     np.asarray(toks)[None])[0])[0]
        want = np.asarray(ref.logits(params, REF_CFG, toks))
        assert np.abs(got - want).max() < TOL
        assert want.std() > 0.5


def test_the_serving_form_matches_the_reference(served, schedule):
    """Prefill then decode through the two pools, across chunks, pages and
    the switch to chosen tokens, against the full forward pass."""
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
    the tolerance: the selection, the relu and the head weights left out and
    the experts' weights not renormalised among them."""
    seqs, got = schedule
    assert worst_difference(served[1], seqs, got, mutate) > 100 * TOL


def test_selection_left_out_shows_only_past_topk(served):
    toks = _tokens(150, 1)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    dense = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                  mutate="no_selection"))
    topk = CFG.indexer.topk
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
    topk = CFG.indexer.topk
    assert (taken.sum(-1) == np.minimum(np.arange(150) + 1, topk)).all()
    # the program's own index scores of layer 0
    _, state = model.apply({"params": params}, toks[None],
                           capture_intermediates=lambda m, _: isinstance(
                               m, keye.LightningIndexer))
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


def test_128_experts_top_8_renormalised_route_as_the_reference(served):
    """The published router's shape at small widths: 128 experts, the 8
    largest softmax values divided by their sum; the reference's ids and
    weights, and the logits that follow."""
    cfg = keye.get_config("tinykeye", num_experts=128, num_experts_per_tok=8,
                          moe_intermediate_size=16, **F32)
    model = keye.KeyeVL2ForCausalLM(cfg)
    params = _params(model, seed=9)
    toks = _tokens(60, 4)
    got = np.asarray(model.apply({"params": params},
                                 np.asarray(toks)[None])[0])[0]
    want, _, routed = ref.forward(params, ref_cfg(cfg), toks, keep=True)
    assert np.abs(got - np.asarray(want)).max() < TOL
    ids, weights = routed[0]
    assert ids.shape == (60, 8) and len(set(np.asarray(ids)[0])) == 8
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    off = np.asarray(ref.logits(params, ref_cfg(cfg), toks,
                                mutate="topk_not_renormalised"))
    assert np.abs(got - off).max() > 100 * TOL


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(40, 2)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    low = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                precision="float8"))
    assert np.abs(want - low).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    text = open(ref.__file__).read()
    assert "deepspeed_tpu" not in text.split('"""', 2)[2]
