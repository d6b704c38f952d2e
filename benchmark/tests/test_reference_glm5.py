"""The GLM-5 reference against the program at a tiny size (through ``apply``:
the program's own tier-1 tests drive the serving form), every mutation and a
lower precision outside the tolerance, the rule by query (a prefix's logits
do not depend on what follows it), the sizes of what it takes, the SHARE it
is given (the same experts the program holds), and that it imports nothing
of the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_moe_dsa as ref

TOL = 2e-4
CFG = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=3,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
           rms_norm_eps=1e-5,
           rope_parameters=dict(rope_theta=1000000, rope_type="default"),
           index_n_heads=2, index_head_dim=16, index_topk=24,
           n_routed_experts=4, expert_shards=2, expert_shard=0,
           n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
           routed_scaling_factor=2.5)


def _model(**kw):
    from deepspeed_tpu.models import glm_moe_dsa as glm5

    return glm5.GlmMoeDsaForCausalLM(glm5.get_config(
        "tinyglm5", dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        use_flash_attention=False, **kw))


@pytest.fixture(scope="module")
def served():
    model = _model()
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    return model, params


TOKENS = np.random.default_rng(0).integers(1, 96, size=130).tolist()


def test_reference_matches_the_program(served):
    model, params = served
    got = np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([TOKENS], np.int32))[0])
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    assert np.abs(got - want).max() < TOL
    assert np.abs(np.asarray(ref.logits(params, CFG, TOKENS, last=3))
                  - want[-3:]).max() < 1e-5
    losses = ref.token_losses(params, CFG, TOKENS)
    assert losses.shape == (129,) and ref.loss(params, CFG, TOKENS) == \
        pytest.approx(float(losses.mean()))
    greedy = [int(np.argmax(want[-1]))]
    assert ref.generated_gaps(params, CFG, TOKENS, greedy)[0] == 0.0


def test_what_a_query_and_a_token_take(served):
    """A query takes ``min(t + 1, index_topk)`` keys, none after itself; a
    token takes ``num_experts_per_tok`` distinct experts of the router's 8,
    whose weights sum to ``routed_scaling_factor``; the dense layer routes
    nothing."""
    _, params = served
    _, taken, routed = ref.forward(params, CFG, TOKENS, keep=True)
    assert len(taken) == len(routed) == 3 and routed[0] is None
    for layer in taken:
        layer = np.asarray(layer)
        assert (layer.sum(-1) == np.minimum(np.arange(130) + 1, 24)).all()
        assert not np.triu(layer, 1).any()
    ids, weights = (np.asarray(a) for a in routed[1])
    assert ids.shape == (130, 2) and (ids[:, 0] != ids[:, 1]).all()
    assert ids.max() > 3                    # experts this chip does not hold
    np.testing.assert_allclose(weights.sum(-1), 2.5, atol=1e-5)


def test_the_share_is_the_programs(served):
    """The reference is given the share the program holds: with the OTHER
    half of the router's experts named as held, the same tree reads as
    another model, and the program follows it there."""
    _, params = served
    other = dict(CFG, expert_shard=1)
    mine = np.asarray(ref.logits(params, CFG, TOKENS))
    theirs = np.asarray(ref.logits(params, other, TOKENS))
    assert np.abs(mine - theirs).max() > 100 * TOL
    got = np.asarray(jax.jit(_model(expert_shard=1).apply)(
        {"params": params}, np.asarray([TOKENS], np.int32))[0])
    assert np.abs(got - theirs).max() < TOL


def test_the_rule_is_by_query_not_by_the_length_of_the_call(served):
    """A prefix's logits are the same whatever follows it."""
    _, params = served
    whole = np.asarray(ref.logits(params, CFG, TOKENS))
    for n in (20, 24, 25, 100):
        part = np.asarray(ref.logits(params, CFG, TOKENS[:n]))
        assert np.abs(part - whole[:n]).max() < 1e-5


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_every_mutation_is_outside_the_tolerance(served, mutate):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    got = np.asarray(ref.logits(params, CFG, TOKENS, mutate=mutate))
    assert np.abs(got - want).max() > 10 * TOL


def test_the_indexers_mutations_show_only_past_topk(served):
    """While ``t + 1 <= index_topk`` every visible key is taken however it
    scores: the selection and each of the indexer's four mutations move no
    logit there, and move them past it."""
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    for mutate in ("selection_left_out", "relu_left_out",
                   "head_weights_left_out", "index_query_from_x",
                   "index_rope_left_out"):
        got = np.asarray(ref.logits(params, CFG, TOKENS, mutate=mutate))
        assert np.abs(got - want)[:24].max() == 0, mutate
        assert np.abs(got - want)[24:].max() > 10 * TOL, mutate


def test_e4m3_operands_are_outside_the_tolerance(served):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    low = np.asarray(ref.logits(params, CFG, TOKENS, precision="float8"))
    assert np.abs(low - want).max() > 100 * TOL


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_mean_gap_separates_e4m3_from_the_program(seed):
    """The control of ``logit_mean_margin`` at a size a test run can hold:
    the reference with every matmul operand rounded to e4m3, put in the
    program's place at every position of one document, puts tokens first
    whose float32 logits lie under the float32 maximum by 0.003 or more IN
    THE MEAN; the program (float32 at this size) puts the reference's own
    token first everywhere."""
    model = _model()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 np.zeros((1, 8), np.int32))["params"]
    exact = np.asarray(ref.logits(params, CFG, TOKENS))
    at = np.arange(len(TOKENS))

    def mean_gap(first):
        return float((exact.max(axis=-1) - exact[at, first]).mean())

    low = np.asarray(ref.logits(params, CFG, TOKENS, precision="float8"))
    got = np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([TOKENS], np.int32))[0])
    assert mean_gap(low.argmax(axis=-1)) > 3e-3
    assert mean_gap(got.argmax(axis=-1)) < 1e-4


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
