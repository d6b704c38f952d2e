"""The MiniCPM-SALA reference against the program at a tiny size (through
``apply``: the program's own tier-1 tests drive the serving form), every
mutation and a lower precision outside the tolerance, the rule by position
(a prompt's logits do not depend on what follows it), and that it imports
nothing of the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala as ref

TOL = 2e-4
SPARSE = dict(kernel_size=8, kernel_stride=4, block_size=16, init_blocks=1,
              window_size=32, topk=5, dense_len=64)
CFG = dict(vocab_size=96, hidden_size=256, num_hidden_layers=4,
           mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                        "minicpm4"],
           num_attention_heads=2, num_key_value_heads=1, head_dim=128,
           lightning_nh=2, lightning_head_dim=128, rms_norm_eps=1e-6,
           rope_theta=10000.0, scale_emb=12, scale_depth=1.4,
           dim_model_base=64, depth_scale_layers=32, sparse_config=SPARSE)


@pytest.fixture(scope="module")
def served():
    from deepspeed_tpu.models import minicpm_sala as sala

    model = sala.MiniCPMSALAForCausalLM(sala.get_config(
        "tinysala", dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        use_flash_attention=False))
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


def test_the_rule_is_by_position_not_by_the_length_of_the_call(served):
    """A prefix's logits are the same whatever follows it: the selection
    starts at position ``dense_len`` of a sequence, not with a call longer
    than it."""
    _, params = served
    whole = np.asarray(ref.logits(params, CFG, TOKENS))
    for n in (40, 64, 100):
        part = np.asarray(ref.logits(params, CFG, TOKENS[:n]))
        assert np.abs(part - whole[:n]).max() < 1e-5


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
