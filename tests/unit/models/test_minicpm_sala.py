"""MiniCPM-SALA (attention over chosen blocks beside linear attention over
a decaying matrix state) against the benchmark's plain float32 reference,
which runs the recurrence token by token and the selection query by query
with no cache: through ``apply`` on whole sequences, and through the
SERVING FORM over the paged pools and the per-slot state on a schedule of
ragged ticks (three sequences of 150, 70 and 5 tokens packed into ticks of
52 rows: chunks that cross pages of 16 and the switch to chosen blocks at
``dense_len`` 64, several chunks a tick, sequences that go on as one-row
decode rows while others prefill, then ticks as a decode block makes
them); then that each way a wrong program could differ FAILS the same
comparison, "selection left out" and "decay left out" first.

Everything is float32 with no kernel (the twins), so the comparison is
about the mathematics alone.  ``TOL`` = 2e-4: float32 rounding through
four layers at logits of order 1, the figure the other families' tests
hold; the chunked scan sums in another order than the recurrence and stays
well inside it, and the weakest mutation moves a logit by over a hundred
times that.
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
from benchmark.reference import minicpm_sala as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import minicpm_sala as sala  # noqa: E402
from deepspeed_tpu.models.llama import count_params  # noqa: E402

TOL = 2e-4
CFG = sala.get_config("tinysala", dtype=jnp.float32,
                      param_dtype=jnp.float32, remat=False,
                      use_flash_attention=False)
MODEL = sala.MiniCPMSALAForCausalLM(CFG)
# the reference's view of the tiny preset: the source's key names
REF_CFG = dict(
    vocab_size=96, hidden_size=256, num_hidden_layers=4,
    mixer_types=list(CFG.mixer_types), num_attention_heads=2,
    num_key_value_heads=1, head_dim=128, lightning_nh=2,
    lightning_head_dim=128, rms_norm_eps=1e-6, rope_theta=10000.0,
    scale_emb=12, scale_depth=1.4, dim_model_base=64, depth_scale_layers=32,
    sparse_config=dict(CFG.sparse_config))
PAGE, SLOTS, CHUNK, MAX_LEN = 16, 4, 48, 256
LENGTHS = (150, 70, 5)
TAIL = 6                            # tokens a sequence ends on as decode rows


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


@pytest.fixture(scope="module")
def served():
    params = jax.jit(MODEL.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales away from their first value 1, so that every norm's
    # scale is in the comparison
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, l: l * (1 + 0.2 * jax.random.normal(
            next(keys), l.shape)) if l.ndim == 1 else l, params)
    return MODEL, params


def run_schedule(params):
    """Every logit row of three teacher-forced sequences through the
    serving form: ``{sequence: [len, V]}``."""
    pp = MAX_LEN // PAGE
    cfg = programs.serving_config(
        CFG, max_seq_len=MAX_LEN, page_size=PAGE, num_pages=SLOTS * pp + 1,
        tensor_parallel=False, kv_cache_dtype="none")
    model = sala.MiniCPMSALAForCausalLM(cfg)
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
    big = sala.get_config("minicpm-sala")
    assert (big.num_hidden_layers, big.hidden_size, big.head_dim,
            big.num_attention_heads, big.num_key_value_heads,
            big.lightning_nh, big.lightning_head_dim, big.intermediate_size,
            big.vocab_size, big.rope_theta) == (
                32, 4096, 128, 32, 2, 32, 128, 16384, 73448, 10000.0)
    sparse = [i for i, t in enumerate(big.mixer_types) if t == sala.SPARSE]
    assert sparse == [0, 9, 16, 17, 22, 29, 30, 31]
    assert big.selection == sala.Selection(32, 16, 64, 1, 2048, 64, 8192)
    assert abs(big.residual_scale - 1.4 / np.sqrt(32)) < 1e-7
    assert paged.state_layers(big) == 24 and paged.kv_layers(big) == 8


def test_the_served_layers_count_their_parameters_and_bytes_by_shapes():
    """``benchmark/configs/minicpm-sala-serve.json`` by ``model.init``'s
    shapes: ISSUE 43's 3,930 M parameters to the parameter, 18 MiB of
    state a sequence, and what a token costs the pool."""
    config = H.load_json(os.path.join(
        ROOT, "benchmark", "configs", "minicpm-sala-serve.json"))
    cfg = H.build_model_config(config)
    assert list(cfg.mixer_types) == (
        config["published"]["mixer_types"][9:21])
    model = sala.MiniCPMSALAForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    per = lambda tree: count_params(tree)
    layers = shapes["model"]
    linear, sparse = layers["layers_1"], layers["layers_0"]
    E, F, V, d = 4096, 16384, 73448, 128
    mlp = 3 * E * F
    assert per(linear) == 5 * E * E + mlp + 2 * d + 3 * E        # 285.2 M
    assert per(sparse) == (3 * E * E + 2 * E * 2 * d + mlp + 2 * d
                           + 2 * E)                              # 253.8 M
    total = count_params(shapes)
    assert total == 9 * per(linear) + 3 * per(sparse) + 2 * V * E + E
    assert total == 3_930_008_576
    assert total * 2 < 7.87e9                                    # bf16
    page_bytes = programs.paged_kv_page_bytes(model, cfg, 64, "none")
    # K and V of 2 heads in bf16 (3 KiB a token over the three layers,
    # heads first: no padding) and 4 compressed keys a page a head in f32
    assert page_bytes == 64 * (3 * 1024 + 3 * 64)
    from benchmark import flops_sala
    assert page_bytes == 64 * flops_sala.pool_bytes_per_token(config)
    assert flops_sala.state_bytes_per_seq(config) == 18_874_368
    assert config["engine"]["kv_pool_bytes"] == 2457 * page_bytes


@pytest.mark.parametrize("kw,named", [
    (dict(lightning_use_rope=False), "lightning_use_rope"),
    (dict(attn_use_rope=True), "attn_use_rope"),
    (dict(use_output_norm=False), "use_output_norm"),
    (dict(decode=True), "dense decode cache")])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        sala.get_config("tinysala", **kw)


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
    """Prefill then decode through the pools and the state, across chunks,
    pages and the switch to chosen blocks, against the full forward pass."""
    seqs, got = schedule
    assert worst_difference(served[1], seqs, got) < TOL


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_comparison(served, schedule, mutate):
    """Each way a wrong program could differ moves a logit by far more
    than the tolerance: the selection left out (dense attention past
    ``dense_len``) and the decay left out (``l_h`` = 1) among them."""
    seqs, got = schedule
    assert worst_difference(served[1], seqs, got, mutate) > 100 * TOL


def test_selection_left_out_shows_only_past_dense_len(served):
    toks = _tokens(150, 1)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    dense = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                  mutate="no_selection"))
    far = CFG.selection.dense_len + CFG.selection.block_size
    assert np.abs(want - dense)[:far].max() == 0
    assert np.abs(want - dense)[far:].max() > 100 * TOL


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(40, 2)
    want = np.asarray(ref.logits(served[1], REF_CFG, toks))
    low = np.asarray(ref.logits(served[1], REF_CFG, toks,
                                precision="float8"))
    assert np.abs(want - low).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    text = open(ref.__file__).read()
    assert "deepspeed_tpu" not in text.split('"""', 2)[2]
