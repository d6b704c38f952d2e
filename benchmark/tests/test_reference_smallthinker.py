"""The SmallThinker reference against the program at a tiny size (through
``apply``: the program's own tier-1 tests drive the serving form through both
pools); every mutation and a lower precision outside the tolerance, a
prefix's logits independent of what follows it, the shipped mean margin at
the tiny size, and that it imports nothing of the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker as ref

TOL = 2e-4
LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]
CFG = dict(vocab_size=96, hidden_size=64, num_hidden_layers=8,
           rope_layout=LAYOUT, sliding_window_layout=LAYOUT,
           sliding_window_size=12, num_attention_heads=12,
           num_key_value_heads=4, head_dim=16, rope_theta=1.5e6,
           rope_scaling=None, moe_num_primary_experts=8,
           moe_num_active_primary_experts=3,
           moe_primary_router_apply_softmax=True, norm_topk_prob=True,
           rms_norm_eps=1e-6)


@pytest.fixture(scope="module")
def served():
    from deepspeed_tpu.models import smallthinker as st

    model = st.SmallThinkerForCausalLM(st.get_config(
        "tinysmallthinker", dtype=jnp.float32, param_dtype=jnp.float32,
        remat=False, use_flash_attention=False))
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


def test_a_prefixs_logits_do_not_depend_on_what_follows(served):
    _, params = served
    whole = np.asarray(ref.logits(params, CFG, TOKENS))
    for n in (10, 40, 100):             # inside the window, and past it
        part = np.asarray(ref.logits(params, CFG, TOKENS[:n]))
        assert np.abs(part - whole[:n]).max() < 1e-5


def test_the_layouts_are_the_configurations(served):
    """The same layers under the other layout are another model; what the
    program refuses the reference refuses too."""
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    for key in ("rope_layout", "sliding_window_layout"):
        swapped = dict(CFG, **{key: LAYOUT[1:] + LAYOUT[:1]})
        assert np.abs(np.asarray(ref.logits(params, swapped, TOKENS))
                      - want).max() > 100 * TOL
    for key, value in (("rope_scaling", {"type": "yarn"}),
                       ("norm_topk_prob", False),
                       ("moe_primary_router_apply_softmax", False)):
        with pytest.raises(AssertionError):
            ref.logits(params, dict(CFG, **{key: value}), TOKENS)


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_every_mutation_is_outside_the_tolerance(served, mutate):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    got = np.asarray(ref.logits(params, CFG, TOKENS, mutate=mutate))
    assert not np.abs(got - want).max() <= 10 * TOL


def test_the_window_mutations_leave_what_is_inside_the_window(served):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    for mutate in ("window_ignored", "window_on_full"):
        got = np.asarray(ref.logits(params, CFG, TOKENS, mutate=mutate))
        assert np.abs(got - want)[:12].max() < 1e-5
        assert np.abs(got - want)[12:].max() > 100 * TOL


def test_e4m3_operands_are_outside_the_tolerance(served):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    low = np.asarray(ref.logits(params, CFG, TOKENS, precision="float8"))
    assert np.abs(low - want).max() > 100 * TOL


def test_the_shipped_mean_margin_at_the_tiny_size(served):
    """The sound program's greedy tokens pass the shipped
    ``logit_mean_margin``; the e4m3 reference's do not."""
    from benchmark import harness as H

    margin = float(H.load_json(
        H.HERE + "/configs/smallthinker-21b-a3b-serve.json")
        ["correct"]["logit_mean_margin"])
    model, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    gaps = lambda lg: want.max(-1) - want[np.arange(len(TOKENS)),
                                          np.argmax(lg, -1)]
    sound = np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([TOKENS], np.int32))[0])
    assert gaps(sound).mean() <= margin
    low = np.asarray(ref.logits(params, CFG, TOKENS, precision="float8"))
    assert gaps(low).mean() > margin


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
