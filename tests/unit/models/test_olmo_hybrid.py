"""Olmo-Hybrid (a gated delta rule over a matrix state beside multi-head
attention without rotary embedding) against the benchmark's plain float32
reference, which runs the recurrence token by token with no cache: through
``apply`` on whole sequences (one-call prefill), and through the SERVING
FORM over the paged pool and the two per-slot state leaves on a schedule of
ragged ticks (three sequences of 150, 70 and 5 tokens packed into ticks of
52 rows: chunks of 48 that do not divide the scan's tile of 128 and cross
pages of 16, several chunks a tick, sequences that go on as one-row decode
rows while others prefill, then ticks as a decode block makes them; fifteen
attention heads stored as sixteen); then that each way a wrong program
could differ FAILS the same comparison, and that a reused slot does not
leak.

Everything is float32 with no kernel (the twins), so the comparison is
about the mathematics alone.  ``TOL`` = 2e-4: float32 rounding through
four layers at logits of order 1, the figure the other families' tests
hold; the chunked form solves where the recurrence corrects token by token
and stays well inside it, and the weakest mutation moves a logit by over a
hundred times that.
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
from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import olmo_hybrid as oh  # noqa: E402
from deepspeed_tpu.models.llama import count_params  # noqa: E402

TOL = 2e-4
CFG = oh.get_config("tinyolmohybrid", dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False,
                    use_flash_attention=False)
MODEL = oh.OlmoHybridForCausalLM(CFG)
# the reference's view of the tiny preset: the source's key names
REF_CFG = dict(
    vocab_size=96, hidden_size=120, num_hidden_layers=4,
    layer_types=list(CFG.layer_types), num_attention_heads=15,
    num_key_value_heads=15, linear_num_key_heads=2, linear_num_value_heads=2,
    linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rms_norm_eps=1e-6)
PAGE, SLOTS, CHUNK, MAX_LEN = 16, 4, 48, 256
LENGTHS = (150, 70, 5)
TAIL = 6                            # tokens a sequence ends on as decode rows


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


@pytest.fixture(scope="module")
def served():
    params = jax.jit(MODEL.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales (and the gates' A_log and dt_bias) away from their
    # first values, so that every norm's scale is in the comparison
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, l: l * (1 + 0.2 * jax.random.normal(
            next(keys), l.shape)) if l.ndim == 1 else l, params)
    return MODEL, params


def run_schedule(params, cfg=CFG, module=oh.OlmoHybridForCausalLM):
    """Every logit row of three teacher-forced sequences through the
    serving form: ``{sequence: [len, V]}`` (``cfg``, ``module``: another
    family of the same tiny vocabulary, ``test_solar_open2.py``)."""
    pp = MAX_LEN // PAGE
    cfg = programs.serving_config(
        cfg, max_seq_len=MAX_LEN, page_size=PAGE, num_pages=SLOTS * pp + 1,
        tensor_parallel=False, kv_cache_dtype="none")
    model = module(cfg)
    T = CHUNK + SLOTS
    cache = programs.init_cache(model, max_seqs=SLOTS, width=T)
    seqs = [_tokens(n, 20 + i) for i, n in enumerate(LENGTHS)]
    slots = [2, 0, 3]                               # out of order
    table = np.full((SLOTS, pp), -1, np.int32)
    for s in slots:
        table[s] = 1 + s * pp + np.arange(pp)
    done = [0] * len(seqs)
    got = {i: np.zeros((n, 96), np.float32) for i, n in enumerate(LENGTHS)}
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
        seq_slot = np.zeros((SLOTS,), np.int32)
        t = j = 0
        where = []
        for i, take in enumerate(takes):
            if take <= 0:
                continue
            lo = done[i]
            ids[t:t + take] = seqs[i][lo:lo + take]
            pos[t:t + take] = np.arange(lo, lo + take)
            new[t:t + take] = [dest(slots[i], p) for p in range(lo, lo + take)]
            kv_lens[j], pages[j], seq_slot[j] = lo + take, table[slots[i]], \
                slots[i]
            cu[j + 1] = cu[j] + take
            where.append((i, lo, t, take))
            done[i] += take
            t += take
            j += 1
        cu[j + 1:] = cu[j]
        meta = {"kv_lens": kv_lens, "page_indices": pages, "cu_q_lens": cu,
                "num_seqs": np.asarray([j], np.int32), "new_kv_dest": new,
                "seq_slot": seq_slot, "live": np.arange(T) < t}
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
        for i, n in enumerate(LENGTHS):
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
    big = oh.get_config("olmo-hybrid-7b")
    assert (big.num_hidden_layers, big.hidden_size, big.head_dim,
            big.num_attention_heads, big.num_key_value_heads,
            big.linear_num_key_heads, big.linear_key_head_dim,
            big.linear_value_head_dim, big.linear_conv_kernel_dim,
            big.intermediate_size, big.vocab_size) == (
                32, 3840, 128, 30, 30, 30, 96, 192, 4, 11008, 100352)
    full = [i for i, t in enumerate(big.layer_types) if t == oh.FULL]
    assert full == [3, 7, 11, 15, 19, 23, 27, 31]
    assert big.rope_parameters["rope_theta"] is None
    assert paged.state_layers(big) == 24 and paged.kv_layers(big) == 8


def test_the_served_layers_count_their_parameters_and_bytes_by_shapes():
    """``benchmark/configs/olmo-hybrid-7b-serve.json`` by ``model.init``'s
    shapes: ISSUE 50's 3,268,268,508 parameters to the parameter, the state
    a sequence as counted, and what a token costs the pool at 32 stored
    heads."""
    config = H.load_json(os.path.join(
        ROOT, "benchmark", "configs", "olmo-hybrid-7b-serve.json"))
    cfg = H.build_model_config(config)
    assert list(cfg.layer_types) == config["published"]["layer_types"][:12]
    model = oh.OlmoHybridForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    per = lambda tree: count_params(tree)
    layers = shapes["model"]
    linear, full = layers["layers_0"], layers["layers_3"]
    E, F, V, H_, dk, dv = 3840, 11008, 100352, 30, 96, 192
    mlp = 3 * E * F
    mixer = (2 * E * H_ * dk + 3 * E * H_ * dv + 2 * E * H_
             + 4 * H_ * (2 * dk + dv) + 2 * H_ + dv)
    assert per(linear["linear_attn"]) == mixer == 88_750_332
    assert per(linear) == mixer + mlp + 2 * E == 215_570_172
    assert per(full) == 4 * E * E + mlp + 4 * E == 185_809_920
    total = count_params(shapes)
    assert total == 9 * per(linear) + 3 * per(full) + 2 * V * E + E
    assert total == 3_268_268_508
    assert total * 2 < 6.54e9                                    # bf16
    page_bytes = programs.paged_kv_page_bytes(model, cfg, 64, "none")
    from benchmark import flops_olmo_hybrid as fo
    # K and V of 32 stored heads of 128 in bf16, three layers
    assert page_bytes == 64 * 3 * 2 * 32 * 128 * 2 == 64 * 49152
    assert page_bytes == 64 * fo.pool_bytes_per_token(config)
    assert fo.state_bytes_per_seq(config) == 9 * (2_211_840 + 69_120)
    assert config["engine"]["kv_pool_bytes"] == 2065 * page_bytes


@pytest.mark.parametrize("kw,named", [
    (dict(rope_parameters=dict(rope_theta=500000.0)), "rope_theta"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(decode=True), "dense decode cache")])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        oh.get_config("tinyolmohybrid", **kw)


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
    """Each way a wrong program could differ moves a logit by far more
    than the tolerance: the decay left out, the correction left out (``d =
    b v``), the factor 2 of ``b``, the convolution, the L2 norm (without it
    the correction overshoots and the state diverges: not finite counts as
    wrong), rotary embedding on the attention layers, the other norm
    placement, and the carried state lost (every 64 tokens here, where 150
    tokens can show it)."""
    seqs, got = schedule
    monkeypatch.setattr(ref, "CARRY_ZEROED_EVERY", 64)
    worst = worst_difference(served[1], seqs, got, mutate)
    assert not worst <= 100 * TOL


def test_a_lost_carry_shows_after_the_boundary(served, monkeypatch):
    toks = _tokens(150, 1)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    monkeypatch.setattr(ref, "CARRY_ZEROED_EVERY", 64)
    lost = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                 mutate="carry_zeroed"))
    assert np.abs(want - lost)[:64].max() == 0
    assert np.abs(want - lost)[64:].max() > 100 * TOL


def test_a_reused_slot_does_not_leak(served):
    """A sequence served in a slot whose two state leaves and pages held
    another's generates what it generates in a fresh cache, which is what
    the reference picks."""
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


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(40, 2)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    low = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                precision="float8"))
    assert np.abs(want - low).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    text = open(ref.__file__).read()
    assert "deepspeed_tpu" not in text.split('"""', 2)[2]
