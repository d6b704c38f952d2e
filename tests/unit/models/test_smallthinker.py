"""SmallThinker (window attention layers and full attention layers in ONE
model, each kind with a page pool and a page table of its own; plain
grouped-query attention at FOUR KV heads, rotary embedding on the window layers
only; in every layer a softmax router that reads the layer's INPUT, before the
input norm and before attention, over ReGLU experts) against the benchmark's
plain float32 reference, which has no cache and no page: through ``apply`` on
whole sequences (one-call prefill), and through the SERVING FORM over BOTH
pools on a schedule of ragged ticks (three sequences of 150, 70 and 5 tokens
packed into ticks of 52 rows that cross pages of 16; the window of 12 makes
the long prompt a dozen windows deep, so the window layers' table, driven by
``paged.WindowPool`` as ``KVState`` drives it, gives pages back behind every
tick and the kernels' twins meet the holes; sequences go on as one-row decode
rows while others prefill, then ticks as a decode block makes them, the window
crossed again WHILE DECODING); then that each way a wrong program could differ
FAILS the same comparison.

Everything is float32 with no kernel (the twins), so the comparison is
about the mathematics alone.  ``TOL`` = 2e-4: float32 rounding through eight
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
from benchmark.reference import smallthinker as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.common import HostStageStats  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import smallthinker as st  # noqa: E402
from deepspeed_tpu.models.llama import count_params  # noqa: E402

TOL = 2e-4
CFG = st.get_config("tinysmallthinker", dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False,
                    use_flash_attention=False)
MODEL = st.SmallThinkerForCausalLM(CFG)
# the reference's view of the tiny preset: the source's key names
REF_CFG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=8,
    rope_layout=list(CFG.rope_layout),
    sliding_window_layout=list(CFG.sliding_window_layout),
    sliding_window_size=12, num_attention_heads=12, num_key_value_heads=4,
    head_dim=16, rope_theta=1.5e6, rope_scaling=None,
    moe_num_primary_experts=8, moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rms_norm_eps=1e-6)
PAGE, SLOTS, CHUNK, MAX_LEN = 16, 4, 48, 256
LENGTHS = (150, 70, 5)
TAIL = 20                           # tokens a sequence ends on as decode rows


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


@pytest.fixture(scope="module")
def served():
    params = jax.jit(MODEL.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # the norms' learned scales away from their first values, so that every
    # one of them is in the comparison
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    return MODEL, jax.tree_util.tree_map(
        lambda l: l if l.ndim != 1 else
        l * (1 + 0.2 * jax.random.normal(next(keys), l.shape)), params)


def run_schedule(params):
    """Every logit row of three teacher-forced sequences through the serving
    form: ``({sequence: tokens}, {sequence: [len, V]}, the most window pages
    a sequence held, the window pool)``.  The full layers' table is whole
    from the start; the window layers' is ``paged.WindowPool``'s, grown and
    given back tick by tick."""
    pp = MAX_LEN // PAGE
    T = CHUNK + SLOTS
    stats = HostStageStats()
    window = paged.WindowPool(
        SLOTS * paged.pages_bound(CFG.sliding_window, T, PAGE) + 1, PAGE,
        SLOTS, pp, CFG.sliding_window, T, stats)
    cfg = programs.serving_config(
        CFG, max_seq_len=MAX_LEN, page_size=PAGE, num_pages=SLOTS * pp + 1,
        tensor_parallel=False, kv_cache_dtype="none",
        window_num_pages=window.num_pages)
    model = st.SmallThinkerForCausalLM(cfg)
    cache = programs.init_cache(model, max_seqs=SLOTS, width=T)
    seqs = [_tokens(n, 20 + i) for i, n in enumerate(LENGTHS)]
    slots = [2, 0, 3]                               # out of order
    table = np.full((SLOTS, pp), -1, np.int32)
    for s in slots:
        table[s] = 1 + s * pp + np.arange(pp)
        assert window.attach(s, 1)
    done = [0] * len(seqs)
    got = {i: np.zeros((n, 96), np.float32) for i, n in enumerate(LENGTHS)}
    held = 0

    def dest(tab, s, p):
        page = tab[s, p // PAGE]
        assert page > 0, "write into a page the table does not hold"
        return page * PAGE + p % PAGE

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
        nonlocal cache, held
        ids, pos, new, wnew = (np.zeros((T,), np.int32) for _ in range(4))
        kv_lens = np.zeros((SLOTS,), np.int32)
        pages, wpages = (np.full((SLOTS, pp), -1, np.int32)
                         for _ in range(2))
        cu = np.zeros((SLOTS + 1,), np.int32)
        t = j = 0
        where = []
        for i, take in enumerate(takes):
            if take <= 0:
                continue
            lo, s = done[i], slots[i]
            assert window.ensure(s, lo + take, lo)
            held = max(held, window.allocator.owned(s))
            ids[t:t + take] = seqs[i][lo:lo + take]
            pos[t:t + take] = np.arange(lo, lo + take)
            new[t:t + take] = [dest(table, s, p) for p in range(lo, lo + take)]
            wnew[t:t + take] = [dest(window.page_table, s, p)
                                for p in range(lo, lo + take)]
            kv_lens[j], pages[j] = lo + take, table[s]
            wpages[j] = window.page_table[s]
            cu[j + 1] = cu[j] + take
            where.append((i, lo, t, take))
            done[i] += take
            t += take
            j += 1
        cu[j + 1:] = cu[j]
        meta = {"kv_lens": kv_lens, "page_indices": pages, "cu_q_lens": cu,
                "num_seqs": np.asarray([j], np.int32), "new_kv_dest": new,
                "window_page_indices": wpages, "window_new_kv_dest": wnew,
                "live": np.arange(T) < t}
        lg, cache = fused(cache, ids[None], pos[None],
                          jax.tree_util.tree_map(jnp.asarray, meta))
        for i, lo, t0, take in where:
            got[i][lo:lo + take] = np.asarray(lg[t0:t0 + take])

    # prompts in ticks of CHUNK + SLOTS rows, the budget shared in order;
    # a sequence whose prompt part is done goes on a row a tick
    while any(done[i] < n - TAIL for i, n in enumerate(LENGTHS)):
        budget, takes = CHUNK, []
        for i, n in enumerate(LENGTHS):
            left = n - TAIL - done[i]
            if left > 0:
                takes.append(min(left, budget))
                budget -= takes[-1]
            else:                       # a decode row, outside the budget
                takes.append(1 if done[i] < n - 2 else 0)
        tick(takes)
    # what is left, as a decode block's ticks: row s IS slot s
    while any(done[i] < n for i, n in enumerate(LENGTHS)):
        ids, pos = (np.zeros((SLOTS,), np.int32) for _ in range(2))
        live = np.zeros((SLOTS,), bool)
        for i, n in enumerate(LENGTHS):
            if done[i] < n:
                s = slots[i]
                assert window.ensure(s, done[i] + 1, done[i])
                ids[s], pos[s], live[s] = seqs[i][done[i]], done[i], True
        at = lambda tab: np.where(
            live, [dest(tab, s, p) if live[s] else 0
                   for s, p in enumerate(pos)], 0).astype(np.int32)
        meta = {"kv_lens": np.where(live, pos + 1, 1).astype(np.int32),
                "page_indices": table,
                "window_page_indices": window.page_table.copy(),
                "cu_q_lens": np.arange(SLOTS + 1, dtype=np.int32),
                "num_seqs": np.asarray([SLOTS], np.int32),
                "new_kv_dest": at(table),
                "window_new_kv_dest": at(window.page_table), "live": live}
        lg, cache = decode(cache, ids[None], pos[None],
                           jax.tree_util.tree_map(jnp.asarray, meta))
        for i, n in enumerate(LENGTHS):
            if done[i] < n:
                got[i][done[i]] = np.asarray(lg[slots[i]])
                done[i] += 1
    return seqs, got, held, window


@pytest.fixture(scope="module")
def schedule(served):
    return run_schedule(served[1])


def worst_difference(params, seqs, got, mutate=None):
    return max(float(np.abs(got[i] - np.asarray(
        ref.logits(params, REF_CFG, seq, mutate=mutate))).max())
        for i, seq in enumerate(seqs))


def test_the_presets_are_the_published_shapes():
    big = st.get_config("smallthinker-21b-a3b")
    assert (big.num_hidden_layers, big.hidden_size, big.head_dim,
            big.num_attention_heads, big.num_key_value_heads,
            big.moe_ffn_hidden_size, big.moe_num_primary_experts,
            big.moe_num_active_primary_experts, big.sliding_window_size,
            big.vocab_size, big.rope_theta, big.rms_norm_eps) == (
                52, 2560, 128, 28, 4, 768, 64, 6, 4096, 151936, 1.5e6, 1e-6)
    # the published period: a full layer without positions, then three
    # window layers with the rotary embedding
    assert big.rope_layout == big.sliding_window_layout == (0, 1, 1, 1) * 13
    assert big.layer_types[:5] == (st.FULL,) + (st.WINDOW,) * 3 + (st.FULL,)
    kind = paged.describe(big)
    assert [type(p).__name__ for p in kind.parts] == ["KVPages",
                                                      "WindowPages"]
    assert [p.layers for p in kind.parts] == [13, 39]
    assert kind.window.window == 4096 and kind.kv_layers == 52


def test_the_served_layers_count_their_parameters_and_bytes_by_shapes():
    """``benchmark/configs/smallthinker-21b-a3b-serve.json`` by
    ``model.init``'s shapes: ISSUE 64's 3,966,937,600 parameters to the
    parameter, term by term, what a token costs each pool (FOUR KV heads,
    stored as four) and what the two pools hold."""
    config = H.load_json(os.path.join(
        ROOT, "benchmark", "configs", "smallthinker-21b-a3b-serve.json"))
    cfg = H.build_model_config(config)
    pub = config["published"]
    assert cfg.num_hidden_layers == 8 and pub["num_hidden_layers"] == 52
    assert cfg.rope_layout == tuple(pub["rope_layout"][:8]) == (0, 1, 1, 1,
                                                                0, 1, 1, 1)
    assert cfg.sliding_window_layout == tuple(
        pub["sliding_window_layout"][:8]) == cfg.rope_layout
    assert sorted(config["reduced"]) == ["num_hidden_layers", "rope_layout",
                                         "sliding_window_layout"]
    model = st.SmallThinkerForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    per = count_params
    layer = shapes["model"]["layers_0"]
    E, V, I = 2560, 151936, 768
    assert per(layer["self_attn"]) == 20_971_520 == (
        2 * E * 28 * 128 + 2 * E * 4 * 128)
    assert layer["self_attn"]["q_proj"]["kernel"].shape == (E, 28 * 128)
    assert per(layer["self_attn"]["k_proj"]) == 1_310_720
    assert layer["block_sparse_moe"]["gate"].shape == (E, 64)
    assert per(layer["block_sparse_moe"]["gate"]) == 163_840
    assert layer["block_sparse_moe"]["w1"].shape == (64, E, I)
    assert 3 * E * I == 5_898_240 and 64 * 3 * E * I == 377_487_360
    assert per(layer) == 398_627_840 == (20_971_520 + 163_840 + 2 * E
                                         + 377_487_360)
    assert per(shapes["lm_head"]) == V * E == 388_956_160
    total = count_params(shapes)
    assert total == 8 * per(layer) + 2 * V * E + E == 3_966_937_600
    assert 7.93e9 < total * 2 < 7.94e9                           # bf16
    # the whole model: the row's 21B
    assert 21.4e9 < 52 * per(layer) + 2 * V * E + E < 21.6e9
    assert paged.kv_heads_stored(cfg.num_key_value_heads) == 4
    full = programs.paged_kv_page_bytes(model, cfg, 64, "none")
    own = programs.paged_kv_page_bytes(model, cfg, 64, "none", window=True)
    assert (full, own) == (2 * 64 * 2048, 6 * 64 * 2048)
    eng = config["engine"]
    bound = paged.pages_bound(4096, eng["max_seqs"] + eng["prefill_chunk"],
                              64)
    assert eng["max_seqs"] == 32 and eng["max_seq_len"] == 8256 == 129 * 64
    assert eng["kv_pool_bytes"] == (32 * 129 + 1) * full
    assert eng["window_pool_bytes"] == (32 * bound + 1) * own


@pytest.mark.parametrize("kw,named", [
    (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(moe_primary_router_apply_softmax=False),
     "moe_primary_router_apply_softmax"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(decode=True), "dense decode cache")])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        st.get_config("tinysmallthinker", **kw)


def test_the_two_layouts_are_read_apart(served):
    """``rope_layout`` and ``sliding_window_layout`` are two lists: a model
    whose full layers take the rotary embedding too, and one whose window
    layers take none, each agree with the reference given the same lists."""
    toks = _tokens(40, 2)
    for ropes in ((1,) * 8, (0,) * 8):
        model = st.SmallThinkerForCausalLM(st.get_config(
            "tinysmallthinker", dtype=jnp.float32, param_dtype=jnp.float32,
            remat=False, use_flash_attention=False, rope_layout=ropes))
        got = np.asarray(model.apply({"params": served[1]},
                                     np.asarray(toks)[None]))[0]
        want = np.asarray(ref.logits(
            served[1], {**REF_CFG, "rope_layout": list(ropes)}, toks))
        other = np.asarray(ref.logits(served[1], REF_CFG, toks))
        assert np.abs(got - want).max() < TOL < np.abs(got - other).max() / 100


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
    """Chunked prefill, then decode, through BOTH pools, against the full
    forward pass; the window layers' table gave pages back behind every
    tick, the last of them WHILE DECODING, and a sequence never held more
    than its bound."""
    seqs, got, held, window = schedule
    assert worst_difference(served[1], seqs, got) < TOL
    assert held <= window.bound == paged.pages_bound(12, CHUNK + SLOTS, PAGE)
    assert window.stats.window_pages_released >= (150 - 12 - 52) // PAGE
    # the long sequence ends holding its last columns and no other: the
    # column its last TAIL rows left behind went back from a decode tick
    row = window.page_table[2]
    assert (150 - TAIL - 12) // PAGE < (150 - 1 - 12) // PAGE
    assert (row[:(150 - 1 - 12) // PAGE] == -1).all() and row[149 // PAGE] > 0


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_comparison(served, schedule, mutate):
    """Each way a wrong program could differ moves a logit by far more than
    the tolerance: a window layer that attends every key, the full layers
    windowed, rotary embedding on the full layers or none on the window
    layers, the router reading the rows it dispatches or the normed input,
    SwiGLU experts, the weights not renormalised, a sigmoid router."""
    seqs, got, _, _ = schedule
    worst = worst_difference(served[1], seqs, got, mutate)
    assert not worst <= 100 * TOL


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(40, 2)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    low = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                precision="float8"))
    assert np.abs(want - low).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    text = open(ref.__file__).read()
    assert "deepspeed_tpu" not in text.split('"""', 2)[2]
