"""The LFM2-MoE reference against the program at a tiny size (through
``apply``: the program's own tier-1 tests drive the engine), every mutation
and a lower precision outside the tolerance, and that it imports nothing of
the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref

TOL = 2e-4
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv"]
CFG = dict(vocab_size=96, hidden_size=128, intermediate_size=96,
           moe_intermediate_size=48, num_hidden_layers=6, layer_types=TYPES,
           num_dense_layers=2, num_attention_heads=2, num_key_value_heads=1,
           num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
           use_expert_bias=True, routed_scaling_factor=1.0, norm_eps=1e-5,
           conv_L_cache=3,
           rope_parameters={"rope_theta": 1000000.0, "rope_type": "default"})


@pytest.fixture(scope="module")
def served():
    from deepspeed_tpu.models import lfm2_moe

    model = lfm2_moe.Lfm2MoeForCausalLM(lfm2_moe.get_config(
        "tinylfm2", dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        use_flash_attention=False))
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    rng = np.random.default_rng(1)
    for i in range(2, 6):       # a bias that changes most chosen sets
        params["model"][f"layers_{i}"]["feed_forward"]["expert_bias"] = \
            jnp.asarray(rng.normal(0, 0.1, size=8), jnp.float32)
    for norm in ("q_norm", "k_norm"):
        params["model"]["layers_2"]["self_attn"][norm]["scale"] = \
            jnp.asarray(rng.uniform(0.5, 1.5, size=64), jnp.float32)
    return model, params


TOKENS = np.random.default_rng(0).integers(1, 96, size=40).tolist()


def test_reference_matches_the_program(served):
    model, params = served
    got = np.asarray(jax.jit(model.apply)(
        {"params": params}, np.asarray([TOKENS], np.int32))[0])
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    assert np.abs(got - want).max() < TOL
    assert np.abs(np.asarray(ref.logits(params, CFG, TOKENS, last=3))
                  - want[-3:]).max() < 1e-6
    losses = ref.token_losses(params, CFG, TOKENS)
    assert losses.shape == (39,) and ref.loss(params, CFG, TOKENS) == \
        pytest.approx(float(losses.mean()))
    greedy = [int(np.argmax(want[-1]))]
    assert ref.generated_gaps(params, CFG, TOKENS, greedy)[0] == 0.0


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_every_mutation_is_outside_the_tolerance(served, mutate):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    got = np.asarray(ref.logits(params, CFG, TOKENS, mutate=mutate))
    assert np.abs(got - want).max() > 10 * TOL


def test_e4m3_operands_are_outside_the_tolerance(served):
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    low = np.asarray(ref.logits(params, CFG, TOKENS, precision="float8"))
    assert np.abs(low - want).max() > 100 * TOL


def test_reference_is_independent_of_the_program():
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
