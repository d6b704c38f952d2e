"""The Olmo-Hybrid reference against the program at a tiny size (through
``apply``: the program's own tier-1 tests drive the serving form), every
mutation that the chip's check sees and a lower precision outside the
tolerance, a prefix's logits independent of what follows it, and that it
imports nothing of the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as ref

TOL = 2e-4
CFG = dict(vocab_size=96, hidden_size=120, num_hidden_layers=4,
           layer_types=["linear_attention"] * 3 + ["full_attention"],
           num_attention_heads=15, num_key_value_heads=15,
           linear_num_key_heads=2, linear_num_value_heads=2,
           linear_key_head_dim=8, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
           rms_norm_eps=1e-6)


@pytest.fixture(scope="module")
def served():
    from deepspeed_tpu.models import olmo_hybrid as oh

    model = oh.OlmoHybridForCausalLM(oh.get_config(
        "tinyolmohybrid", dtype=jnp.float32, param_dtype=jnp.float32,
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
    for n in (40, 64, 100):
        part = np.asarray(ref.logits(params, CFG, TOKENS[:n]))
        assert np.abs(part - whole[:n]).max() < 1e-5


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_every_mutation_is_outside_the_tolerance(served, mutate, monkeypatch):
    """``carry_zeroed`` every 64 tokens here (130 tokens cannot cross
    1,024); ``no_l2norm`` lets the correction overshoot and the state
    diverge: not finite is outside too."""
    _, params = served
    monkeypatch.setattr(ref, "CARRY_ZEROED_EVERY", 64)
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    got = np.asarray(ref.logits(params, CFG, TOKENS, mutate=mutate))
    assert not np.abs(got - want).max() <= 10 * TOL


def test_e4m3_operands_are_outside_the_tolerance(served):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    low = np.asarray(ref.logits(params, CFG, TOKENS, precision="float8"))
    assert np.abs(low - want).max() > 100 * TOL


def test_the_shipped_margin_at_the_tiny_size(served):
    """The sound program's greedy tokens pass the shipped ``logit_margin``;
    the e4m3 reference's and each mutation's that the chip's check SEES do
    not (at the tiny size a gap is read over every position of a sequence,
    not sixteen)."""
    from benchmark import harness as H

    margin = float(H.load_json(H.HERE + "/configs/olmo-hybrid-7b-serve.json")
                   ["correct"]["logit_margin"])
    model, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    gaps = lambda lg: want.max(-1) - want[np.arange(len(TOKENS)),
                                          np.argmax(lg, -1)]
    sound = np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([TOKENS], np.int32))[0])
    assert gaps(sound).max() <= margin
    low = np.asarray(ref.logits(params, CFG, TOKENS, precision="float8"))
    assert gaps(low).max() > margin
    for mutate in ("no_gate", "no_delta", "no_conv", "prenorm_full"):
        wrong = np.asarray(ref.logits(params, CFG, TOKENS, mutate=mutate))
        assert gaps(wrong).max() > margin, mutate


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
