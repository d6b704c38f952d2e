"""The GLM-4-MoE-Lite reference against the program at a tiny size (through
``apply``: the program's own tier-1 tests drive the engine), every mutation
and a lower precision outside the tolerance, and that it imports nothing of
the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm4_moe_lite as ref

TOL = 2e-4
CFG = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
           moe_intermediate_size=32, num_hidden_layers=3,
           num_attention_heads=3, n_routed_experts=8, n_shared_experts=1,
           num_experts_per_tok=2, norm_topk_prob=True,
           routed_scaling_factor=1.8, first_k_dense_replace=1,
           q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=12, rms_norm_eps=1e-5,
           rope_theta=10000.0)


@pytest.fixture(scope="module")
def served():
    from deepspeed_tpu.models import glm4_moe_lite as glm

    model = glm.Glm4MoeLiteForCausalLM(glm.get_config(
        "tinyglm", dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        use_flash_attention=False))
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 np.zeros((1, 8), np.int32))["params"]
    rng = np.random.default_rng(1)
    for i in range(3):          # the latent's norm shows; the bias swaps
        layer = params["model"][f"layers_{i}"]
        layer["self_attn"]["kv_a_layernorm"]["scale"] = jnp.asarray(
            rng.uniform(0.5, 1.5, size=32), jnp.float32)
        if "expert_bias" in layer["mlp"]:
            layer["mlp"]["expert_bias"] = jnp.asarray(
                rng.normal(0, 0.1, size=8), jnp.float32)
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
