"""Trinity (window attention layers and full attention layers in ONE model,
each kind with a page pool and a page table of its own; gated grouped-query
attention with a norm of q and k a head, rotary embedding on the window layers
only; four norms a layer; one leading dense layer, then 3 of 12 experts HELD
under a router 12 wide, beside one shared expert) against the benchmark's
plain float32 reference, which has no cache and no page and is GIVEN THE SAME
SHARE: through ``apply`` on whole sequences (one-call prefill), and through
the SERVING FORM over BOTH pools on a schedule of ragged ticks (three
sequences of 150, 70 and 5 tokens packed into ticks of 52 rows that cross
pages of 16; the window of 12 makes the long prompt a dozen windows deep, so
the window layers' table, driven by ``paged.WindowPool`` as ``KVState`` drives
it, gives pages back behind every tick and the kernels' twins meet the holes;
sequences go on as one-row decode rows while others prefill, then ticks as a
decode block makes them); then that each way a wrong program could differ
FAILS the same comparison.

Everything is float32 with no kernel (the twins), so the comparison is
about the mathematics alone.  ``TOL`` = 2e-4: float32 rounding through five
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
from benchmark.reference import trinity as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.common import HostStageStats  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import trinity as tr  # noqa: E402
from deepspeed_tpu.models.llama import count_params  # noqa: E402

TOL = 2e-4
CFG = tr.get_config("tinytrinity", dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False,
                    use_flash_attention=False)
MODEL = tr.TrinityForCausalLM(CFG)
# the reference's view of the tiny preset: the source's key names
REF_CFG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=5,
    layer_types=list(CFG.layer_types), num_dense_layers=1,
    sliding_window=12, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rope_theta=10000.0, rope_scaling=None, mup_enabled=True,
    num_experts=3, expert_shards=4, expert_shard=1, num_shared_experts=1,
    num_experts_per_tok=4, route_norm=True, route_scale=2.448,
    score_func="sigmoid", n_group=1, topk_group=1, rms_norm_eps=1e-5)
PAGE, SLOTS, CHUNK, MAX_LEN = 16, 4, 48, 256
LENGTHS = (150, 70, 5)
TAIL = 6                            # tokens a sequence ends on as decode rows


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


@pytest.fixture(scope="module")
def served():
    params = jax.jit(MODEL.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales away from their first values (the selection bias starts
    # at ZERO: drawn), so that every one of them is in the comparison
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))

    def moved(path, l):
        if l.ndim != 1:
            return l
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("expert_bias"):
            return 0.1 * jax.random.normal(next(keys), l.shape)
        return l * (1 + 0.2 * jax.random.normal(next(keys), l.shape))

    return MODEL, jax.tree_util.tree_map_with_path(moved, params)


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
    model = tr.TrinityForCausalLM(cfg)
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
    big = tr.get_config("trinity-large-preview")
    assert (big.num_hidden_layers, big.hidden_size, big.head_dim,
            big.num_attention_heads, big.num_key_value_heads,
            big.intermediate_size, big.moe_intermediate_size,
            big.num_experts, big.expert_shards, big.num_experts_per_tok,
            big.num_dense_layers, big.sliding_window, big.vocab_size,
            big.route_scale) == (
                60, 3072, 128, 48, 8, 12288, 3072, 256, 1, 4, 6, 4096,
                200192, 2.448)
    kinds = big.layer_types
    assert kinds.count(tr.WINDOW) == 45 and kinds.count(tr.FULL) == 15
    assert kinds[:8] == (tr.WINDOW,) * 3 + (tr.FULL,) + (tr.WINDOW,) * 3 + (
        tr.FULL,)
    kind = paged.describe(big)
    assert [type(p).__name__ for p in kind.parts] == ["KVPages",
                                                      "WindowPages"]
    assert [p.layers for p in kind.parts] == [15, 45]
    assert kind.window.window == 4096 and kind.kv_layers == 60


def test_the_served_layers_count_their_parameters_and_bytes_by_shapes():
    """``benchmark/configs/trinity-large-serve.json`` by ``model.init``'s
    shapes: ISSUE 59's 4,321,903,872 parameters to the parameter, term by
    term, what a token costs each pool and what the two pools hold."""
    config = H.load_json(os.path.join(
        ROOT, "benchmark", "configs", "trinity-large-serve.json"))
    cfg = H.build_model_config(config)
    pub = config["published"]
    assert cfg.layer_types == tuple(pub["layer_types"][5:10])
    assert cfg.layer_types == (tr.WINDOW, tr.WINDOW, tr.FULL, tr.WINDOW,
                               tr.WINDOW)
    assert (cfg.num_experts, cfg.expert_shards, cfg.expert_shard,
            cfg.num_dense_layers) == (32, 8, 0, 1)
    assert cfg.num_experts * cfg.expert_shards == pub["num_experts"]
    assert cfg.vocab_size * 8 == pub["vocab_size"]
    model = tr.TrinityForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    per = count_params
    layers = shapes["model"]
    dense, expert = layers["layers_0"], layers["layers_1"]
    E, V, I, D = 3072, 25024, 3072, 12288
    assert per(dense["self_attn"]) == 62_914_816 == (
        3 * E * 48 * 128 + 2 * E * 8 * 128 + 2 * 128)
    assert per(dense) == 176_173_312 == 62_914_816 + 4 * E + 3 * E * D
    assert expert["mlp"]["gate"].shape == (E, 256)
    assert expert["mlp"]["w1"].shape == (32, E, I)
    assert per(expert["shared_experts"]) == 3 * E * I == 28_311_552
    assert per(expert) - 32 * 3 * E * I == 92_025_344
    assert per(expert) == 997_995_008
    total = count_params(shapes)
    assert total == per(dense) + 4 * per(expert) + 2 * V * E + E
    assert total == 4_321_903_872
    assert 8.64e9 < total * 2 < 8.65e9                           # bf16
    full = programs.paged_kv_page_bytes(model, cfg, 64, "none")
    own = programs.paged_kv_page_bytes(model, cfg, 64, "none", window=True)
    assert (full, own) == (64 * 4096, 4 * 64 * 4096)
    eng = config["engine"]
    bound = paged.pages_bound(4096, eng["max_seqs"] + eng["prefill_chunk"],
                              64)
    assert bound == 82
    assert eng["kv_pool_bytes"] == (8 * 513 + 1) * full
    assert eng["window_pool_bytes"] == (8 * bound + 1) * own
    # under the one table the five layers would hold five times the first
    assert 5 * eng["kv_pool_bytes"] > 3 * (eng["kv_pool_bytes"]
                                           + eng["window_pool_bytes"])


@pytest.mark.parametrize("kw,named", [
    (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(n_group=2), "n_group"), (dict(topk_group=2), "topk_group"),
    (dict(score_func="softmax"), "score_func"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(decode=True), "dense decode cache")])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        tr.get_config("tinytrinity", **kw)


@pytest.mark.parametrize("kw", [dict(mup_enabled=False),
                                dict(route_norm=False)],
                         ids=["no-mup", "no-route-norm"])
def test_the_two_switches_are_each_one_branch(served, kw):
    """``mup_enabled`` false and ``route_norm`` false are implemented: the
    program and the reference agree under either."""
    model = tr.TrinityForCausalLM(tr.get_config(
        "tinytrinity", dtype=jnp.float32, param_dtype=jnp.float32,
        remat=False, use_flash_attention=False, **kw))
    toks = _tokens(40, 2)
    got = np.asarray(model.apply({"params": served[1]},
                                 np.asarray(toks)[None]))[0]
    want = np.asarray(ref.logits(served[1], {**REF_CFG, **kw}, toks))
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
    tick and a sequence never held more than its bound."""
    seqs, got, held, window = schedule
    assert worst_difference(served[1], seqs, got) < TOL
    assert held <= window.bound == paged.pages_bound(12, CHUNK + SLOTS, PAGE)
    assert window.stats.window_pages_released >= (150 - 12 - 52) // PAGE
    # the long sequence ends holding its last columns and no other
    row = window.page_table[2]
    assert (row[:(150 - 1 - 12) // PAGE] == -1).all() and row[149 // PAGE] > 0


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_comparison(served, schedule, mutate):
    """Each way a wrong program could differ moves a logit by far more than
    the tolerance: a window layer that attends every key, the full layer
    windowed, rotary embedding on the full layer or none on the window
    layers, the gate, the head norms, the two output norms, the embedding's
    factor, the router's scale, the held range ignored, the shared expert
    left out."""
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
