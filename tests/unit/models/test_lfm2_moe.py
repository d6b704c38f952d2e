"""LFM2-MoE (gated short convolutions beside grouped-query attention, a
sigmoid router with a selection bias) against the benchmark's plain float32
reference: through ``apply``, and through the ENGINE'S OWN PROGRAMS
(``programs.build_fused_step``'s logits and the decode block) on a schedule
of ticks whose chunk boundaries fall 1, 2 and 5 tokens into a sequence,
with chunks of exactly one token and a tick that packs a sequence that
starts, one that continues and a decode row; then that each way a wrong
program could differ FAILS the same comparison.

Everything is float32 with no kernel (heads of 64, so the pool is the
padded route's twin), so the comparison is about the mathematics alone.
``TOL`` = 2e-4: float32 rounding through six layers at logits of order 1
(the figure ``test_olmoe.py`` and ``test_ouro.py`` hold their families to);
the weakest mutation moves a logit by over ten times that.
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

from benchmark.reference import lfm2_moe as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.models import lfm2_moe  # noqa: E402
from deepspeed_tpu.moe.sharded_moe import sigmoid_bias_routing  # noqa: E402

TOL = 2e-4
PLAIN = dict(dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
             use_flash_attention=False)
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv"]
# the reference's view of the tiny preset: the source's key names
CFG = dict(vocab_size=96, hidden_size=128, intermediate_size=96,
           moe_intermediate_size=48, num_hidden_layers=6, layer_types=TYPES,
           num_dense_layers=2, num_attention_heads=2, num_key_value_heads=1,
           num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
           use_expert_bias=True, routed_scaling_factor=1.0, norm_eps=1e-5,
           conv_L_cache=3,
           rope_parameters={"rope_theta": 1000000.0, "rope_type": "default"})
S, PAGE, CHUNK, MAX_LEN = 4, 16, 16, 64
PP = MAX_LEN // PAGE


@pytest.fixture(scope="module")
def served():
    model = lfm2_moe.Lfm2MoeForCausalLM(
        lfm2_moe.get_config("tinylfm2", **PLAIN))
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    # learned scales away from their first value 1, so that every norm's
    # place shows, and a selection bias large enough to change most
    # tokens' chosen set among 8 experts
    rng = np.random.default_rng(1)
    scale = lambda n: jnp.asarray(rng.uniform(0.5, 1.5, size=n), jnp.float32)
    inner = params["model"]
    for i in range(6):
        layer = inner[f"layers_{i}"]
        layer["operator_norm"]["scale"] = scale(128)
        layer["ffn_norm"]["scale"] = scale(128)
        if "self_attn" in layer:
            layer["self_attn"]["q_norm"]["scale"] = scale(64)
            layer["self_attn"]["k_norm"]["scale"] = scale(64)
        if "expert_bias" in layer["feed_forward"]:
            layer["feed_forward"]["expert_bias"] = jnp.asarray(
                rng.normal(0, 0.1, size=8), jnp.float32)
    inner["norm"]["scale"] = scale(128)
    return model, params


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


# -- the engine's programs on a schedule of the test's own ------------------

class Programs:
    """The serving form of the model, a zeroed cache, and the two engine
    programs, driven tick by tick: slot ``s`` owns pages ``1 + s * PP ..``,
    so the test decides what every tick packs."""

    def __init__(self, served, with_blocks=True):
        model, self.params = served
        cfg = programs.serving_config(
            model.config, max_seq_len=MAX_LEN, page_size=PAGE,
            num_pages=1 + S * PP, tensor_parallel=False,
            kv_cache_dtype="none")
        self.model = type(model)(cfg)
        self.T = S + CHUNK
        self.cache = programs.init_cache(self.model, max_seqs=S,
                                         width=self.T)
        self.step = programs.build_fused_step(self.model)
        self.block = programs.build_decode_block(
            self.model, sampled=False, max_seqs=S, block=8, page_size=PAGE,
            max_seq_len=MAX_LEN, sample_base=jax.random.PRNGKey(0)
        ) if with_blocks else None
        self.page_table = np.arange(1, 1 + S * PP, dtype=np.int32
                                    ).reshape(S, PP)

    def tick(self, rows, seq_slot=None):
        """One fused tick of ``rows`` = [(slot, tokens, first position)];
        the logits of each row's last token, in order."""
        token_ids = np.zeros((self.T,), np.int32)
        positions = np.zeros((self.T,), np.int32)
        dest = np.zeros((self.T,), np.int32)
        kv_lens = np.zeros((S,), np.int32)
        page_indices = np.full((S, PP), -1, np.int32)
        cu = np.zeros((S + 1,), np.int32)
        sample_rows = np.zeros((S,), np.int32)
        slots = np.zeros((S,), np.int32)
        t = 0
        for j, (slot, toks, p0) in enumerate(rows):
            n = len(toks)
            pos = np.arange(p0, p0 + n)
            token_ids[t:t + n] = toks
            positions[t:t + n] = pos
            dest[t:t + n] = (self.page_table[slot, pos // PAGE] * PAGE
                             + pos % PAGE)
            kv_lens[j] = p0 + n
            page_indices[j] = self.page_table[slot]
            cu[j + 1] = cu[j] + n
            sample_rows[j] = t + n - 1
            slots[j] = slot
            t += n
        cu[len(rows) + 1:] = cu[len(rows)]
        sel, self.cache, _, _ = self.step(
            self.params, self.cache, token_ids[None], positions[None],
            kv_lens, page_indices, cu, np.asarray([len(rows)], np.int32),
            dest, sample_rows, slots if seq_slot is None else seq_slot)
        return [np.asarray(sel[j]) for j in range(len(rows))]

    def decode(self, last_tok, pos, active):
        """One greedy decode block of 8 ticks: the tokens ``[8, S]``."""
        z = np.zeros((S,), np.int32)
        out = self.block(
            self.params, self.cache, np.asarray(last_tok, np.int32),
            np.asarray(pos, np.int32), np.asarray(active, bool),
            np.full((S,), 100, np.int32), self.page_table,
            np.full((S,), -1, np.int32), np.zeros((S,), bool),
            np.ones((S,), np.float32), z, np.ones((S,), np.float32), z,
            jax.random.PRNGKey(0))
        self.cache = out[0]
        return np.asarray(out[5])


A, B, C = 0, 2, 1                  # three sequences' slots, out of order


def run_schedule(served, mutant=None, blocks=2):
    """The schedule of the module docstring; returns ``(sequences by slot,
    [(slot, position, logits)])``: every logit row a tick gave, and after
    the decode blocks one more tick of three decode rows."""
    a, b, c = _tokens(14, 11), _tokens(10, 12), _tokens(7, 13)
    seqs = {A: a, B: b, C: c}
    p = Programs(served, with_blocks=blocks > 0)
    got = []

    def tick(rows, **kw):
        before = p.cache
        if mutant == "continuing_sequence_given_zeros":
            p.cache = paged.map_cache(p.cache, state_fn=jnp.zeros_like)
            before = p.cache
        before = jax.tree_util.tree_map(jnp.copy, before)
        for (slot, toks, p0), lg in zip(rows, p.tick(rows, **kw)):
            got.append((slot, p0 + len(toks) - 1, lg))
        return before

    tick([(A, a[0:1], 0)])              # a chunk of one token
    tick([(A, a[1:2], 1)])              # boundary 1 token in; one token
    stale = tick([(A, a[2:5], 2)])      # boundary 2 tokens in
    if mutant == "state_not_carried_over_a_boundary":
        # the tick's state write is lost: the pages it wrote stay
        fresh = iter(paged.split_cache(stale)[1])
        p.cache = paged.map_cache(p.cache, state_fn=lambda l: next(fresh))
    tick([(A, a[5:12], 5), (B, b[0:4], 0)])       # boundary 5 tokens in
    swap = None
    if mutant == "slot_a_state_read_by_slot_b":
        swap = np.asarray([A, A, C, 0], np.int32)
    # a sequence that starts, one that continues, and a decode row
    tick([(C, c[0:6], 0), (B, b[4:9], 4), (A, a[12:13], 12)], seq_slot=swap)
    if blocks:
        last = np.zeros((S,), np.int32)
        pos = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        for slot, n in ((A, 13), (B, 9), (C, 6)):
            last[slot], pos[slot], active[slot] = seqs[slot][n], n, True
            del seqs[slot][n + 1:]
        for _ in range(blocks):
            toks = p.decode(last, pos, active)
            for slot in (A, B, C):
                seqs[slot] += toks[:, slot].tolist()
            last, pos = toks[-1], pos + 8
        # the blocks' last tokens as decode rows: what they left in the
        # pool and in the state, read back as logits
        tick([(slot, seqs[slot][-1:], len(seqs[slot]) - 1)
              for slot in (A, C, B)])
    return seqs, got


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
    tiny, big = (lfm2_moe.get_config(p) for p in ("tinylfm2", "lfm2-24b-a2b"))
    assert list(tiny.layer_types) == TYPES and tiny.head_dim == 64
    assert (tiny.num_dense_layers, tiny.num_experts,
            tiny.num_experts_per_tok) == (2, 8, 2)
    assert (big.num_hidden_layers, big.hidden_size, big.head_dim,
            big.num_key_value_heads, big.intermediate_size,
            big.moe_intermediate_size, big.num_experts,
            big.num_experts_per_tok, big.vocab_size, big.rope_theta,
            big.conv_L_cache) == (40, 2048, 64, 8, 11776, 1536, 64, 4,
                                  65536, 1e6, 3)
    assert big.layer_types[:10] == (
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv")
    assert (paged.state_layers(big), paged.kv_layers(big)) == (30, 10)
    assert big.qk_norm and big.qk_norm_per_head and big.tie_embedding
    # the config hashes (flax hashes a module's fields), dict and all
    assert hash(big) == hash(lfm2_moe.get_config("lfm2-24b-a2b"))


def test_the_stage_of_ten_layers_counts_its_parameters_by_shapes():
    """Layers 0-9 of the source at the published widths, by shapes alone:
    the numbers the configuration file states."""
    cfg = lfm2_moe.get_config(
        "lfm2-24b-a2b", num_hidden_layers=10,
        layer_types=lfm2_moe.PRESETS["lfm2-24b-a2b"]["layer_types"][:10])
    shapes = jax.eval_shape(
        lfm2_moe.Lfm2MoeForCausalLM(cfg).init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]["model"]
    count = lfm2_moe.count_params
    assert count(shapes["embed_tokens"]) == 65536 * 2048
    assert count(shapes["layers_0"]["conv"]) == 4 * 2048 * 2048 + 3 * 2048
    assert count(shapes["layers_2"]["self_attn"]) == (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64)
    assert count(shapes["layers_0"]["feed_forward"]) == 3 * 2048 * 11776
    assert count(shapes["layers_2"]["feed_forward"]) == (
        64 * 3 * 2048 * 1536 + 2048 * 64 + 64)
    assert "lm_head" not in shapes and count(shapes) == 5_267_090_176


@pytest.mark.parametrize("kw,named", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_type"),
    (dict(decode=True), "dense decode cache")],
    ids=["conv-bias", "rope-type", "dense-decode-cache"])
def test_what_the_family_does_not_have_is_refused_by_name(kw, named):
    with pytest.raises(NotImplementedError, match=named):
        lfm2_moe.get_config("tinylfm2", **kw)


def test_the_sigmoid_router_has_its_serving_form_only():
    from deepspeed_tpu.moe.layer import MoE

    moe = MoE(hidden_size=16, num_experts=4, intermediate_size=8, k=2,
              scoring="sigmoid", expert_bias=True, dispatch_impl="sorted",
              expert_parallel=False)
    with pytest.raises(NotImplementedError, match="sigmoid"):
        moe.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))


def test_the_first_selection_bias_is_not_zero_and_changes_some_sets():
    """What ``init`` draws for the bias, at the published router width:
    standard deviation EXPERT_BIAS_INIT_STD, under which the chosen 4 of 64
    differ from the top 4 of the scores in about six routings of ten (the
    module docstring says why more than a third); and the experts'
    down-projections at EXPERT_DOWN_INIT_SCALE of lecun-normal's size."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4096, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, lfm2_moe.EXPERT_BIAS_INIT_STD, 64),
                       jnp.float32)
    experts, weights, swaps = sigmoid_bias_routing(logits, bias, 4, True)
    share = float(jnp.mean(swaps))
    assert 0.5 < share < 0.7, share
    # the weights are the chosen SCORES renormalised, the bias not in them
    s = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(s, experts, axis=-1)
    assert np.allclose(weights, picked / (picked.sum(-1, keepdims=True)
                                          + 1e-6), atol=1e-7)
    model = lfm2_moe.Lfm2MoeForCausalLM(
        lfm2_moe.get_config("tinylfm2", **PLAIN, num_experts=64))
    drawn = jax.jit(model.init)(jax.random.PRNGKey(3), np.zeros(
        (1, 8), np.int32))["params"]["model"]["layers_3"]["feed_forward"]
    assert drawn["expert_bias"].dtype == jnp.float32
    assert 0.5 < float(jnp.std(drawn["expert_bias"])
                       ) / lfm2_moe.EXPERT_BIAS_INIT_STD < 1.5
    # lecun-normal over the 48 inputs of a down-projection, times the scale
    assert abs(float(jnp.std(drawn["w2"])) * np.sqrt(48)
               / lfm2_moe.EXPERT_DOWN_INIT_SCALE - 1) < 0.1
    assert abs(float(jnp.std(drawn["w1"])) * np.sqrt(128) - 1) < 0.1


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
    decode ticks over two blocks: every logit row against the reference's
    full forward pass, and every token a block sampled the reference's own
    argmax."""
    seqs, got = schedule
    assert [len(seqs[s]) for s in (A, B, C)] == [14 + 16, 10 + 16, 7 + 16]
    assert len(got) == 1 + 1 + 1 + 2 + 3 + 3
    assert worst_difference(served, seqs, got) < TOL
    for slot, n in ((A, 14), (B, 10), (C, 7)):
        gaps = ref.generated_gaps(served[1], CFG, seqs[slot][:n],
                                  seqs[slot][n:])
        assert gaps.max() < TOL


@pytest.mark.parametrize("mutant", [
    "state_not_carried_over_a_boundary", "continuing_sequence_given_zeros",
    "slot_a_state_read_by_slot_b", "conv_reads_across_a_sequence_boundary"])
def test_a_wrong_state_path_fails_the_comparison(served, mutant,
                                                 monkeypatch):
    """Each way the state beside the pages could go wrong, made in the
    programs themselves (the cache and the slots are in the test's hands;
    the convolution that reads across a boundary is a patched call)."""
    if mutant == "conv_reads_across_a_sequence_boundary":
        real = paged.ragged_short_conv

        def one_sequence(mdl, u, taps, pos, meta):
            cu = meta["cu_q_lens"]
            merged = jnp.zeros_like(cu).at[1:].set(cu[meta["num_seqs"][0]])
            return real(mdl, u, taps, pos, {
                **meta, "cu_q_lens": merged,
                "num_seqs": jnp.ones_like(meta["num_seqs"])})

        monkeypatch.setattr(paged, "ragged_short_conv", one_sequence)
    seqs, got = run_schedule(served, mutant=mutant, blocks=0)
    assert worst_difference(served, seqs, got) > 10 * TOL


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_a_wrong_model_fails_the_comparison(served, schedule, mutate):
    """Taps reversed, B/C/X permuted, the bias in the weights or out of
    the selection, softmax for sigmoid, no renormalisation, QK-norm over
    the whole projection or none: the programs' logits are NOT such a
    model's."""
    seqs, got = schedule
    assert worst_difference(served, seqs, got, mutate=mutate) > 10 * TOL


def test_lower_precision_fails_the_comparison(served):
    toks = _tokens(24, seed=4)
    want = np.asarray(ref.logits(served[1], CFG, toks))
    low = np.asarray(ref.logits(served[1], CFG, toks, precision="float8"))
    assert np.abs(low - want).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
