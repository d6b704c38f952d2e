"""The Solar-Open2 reference against the program at a tiny size (through
``apply``: the program's own tier-1 tests drive the serving form), GIVEN THE
SAME SHARE of the experts; every mutation and a lower precision outside the
tolerance, a prefix's logits independent of what follows it, the shipped
mean margin at the tiny size, and that it imports nothing of the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import solar_open2 as ref

TOL = 2e-4
CFG = dict(vocab_size=96, hidden_size=64, num_hidden_layers=4,
           gqa_layers=[0], num_attention_heads=4, num_key_value_heads=2,
           head_dim=16,
           linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                   num_heads=2, num_kv_heads=None),
           use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
           kda_allow_neg_eigval=True, first_k_dense_replace=0,
           n_routed_experts=3, expert_shards=4, expert_shard=1,
           n_shared_experts=1, num_experts_per_tok=4, norm_topk_prob=True,
           routed_scaling_factor=1, rms_norm_eps=1e-5)


@pytest.fixture(scope="module")
def served():
    from deepspeed_tpu.models import solar_open2 as so

    model = so.SolarOpen2ForCausalLM(so.get_config(
        "tinysolar", dtype=jnp.float32, param_dtype=jnp.float32,
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


def test_the_share_is_the_configurations(served):
    """Another share of the same banks is another model; a reference told
    ``expert_shards`` 1 over three banks routes among three."""
    _, params = served
    want = np.asarray(ref.logits(params, CFG, TOKENS))
    other = np.asarray(ref.logits(params, dict(CFG, expert_shard=2), TOKENS))
    assert np.abs(other - want).max() > 100 * TOL
    with pytest.raises(Exception):
        ref.logits(params, dict(CFG, use_rope=True), TOKENS)


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_every_mutation_is_outside_the_tolerance(served, mutate, monkeypatch):
    """``carry_zeroed`` every 64 tokens here (130 tokens cannot cross
    1,024); not finite is outside too."""
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


def test_the_shipped_mean_margin_at_the_tiny_size(served):
    """The sound program's greedy tokens pass the shipped
    ``logit_mean_margin``; the e4m3 reference's do not (at the tiny size the
    mean is over every position of a sequence)."""
    from benchmark import harness as H

    margin = float(H.load_json(
        H.HERE + "/configs/solar-open2-250b-serve.json")
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
