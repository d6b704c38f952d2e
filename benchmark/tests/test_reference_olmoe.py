"""The OLMoE reference agrees with the program's model at ``tinyolmoe`` size
(plain ``apply`` told to drop nothing, and the served, dropless path through
``generated_gaps``), and each mutation the tolerance has to catch fails it.
In the manner of ``test_reference.py``; the same comparisons at the shipped
tolerance are tier-1 (``tests/unit/models/test_olmoe.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe as ref
from deepspeed_tpu.models import olmoe

CFG = dict(vocab_size=96, hidden_size=32, intermediate_size=16,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
           num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
           rms_norm_eps=1e-5, rope_theta=10000.0)
TOL = 2e-4      # float32 rounding through two layers at logits of order 1


@pytest.fixture(scope="module")
def model_and_params():
    cfg = olmoe.get_config(
        "tinyolmoe", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=False, remat=False, use_flash_attention=False,
        drop_tokens=False)
    model = olmoe.OlmoeForCausalLM(cfg)
    # boxed (``expert`` axis metadata) as ``model.init`` returns them: the
    # reference reads leaves either way
    params = jax.jit(model.init)(jax.random.PRNGKey(3),
                                 np.zeros((1, 8), np.int32))
    return model, params


def _tokens(n=40):
    return np.random.default_rng(0).integers(1, 96, size=n).tolist()


def test_reference_matches_the_programs_model(model_and_params):
    model, params = model_and_params
    toks = _tokens()
    want = np.asarray(jax.jit(model.apply)(
        params, np.asarray([toks], np.int32))[0][0])
    got = np.asarray(ref.logits(params, CFG, toks))
    assert np.abs(got - want).max() < TOL
    # the last positions alone, and the losses built on the logits
    tail = np.asarray(ref.logits(params, CFG, toks, last=3))
    assert np.abs(tail - want[-3:]).max() < TOL
    lse = jax.scipy.special.logsumexp(want[:-1], axis=-1)
    ce = np.asarray(lse) - want[np.arange(39), np.asarray(toks[1:])]
    assert np.abs(ref.token_losses(params, CFG, toks) - ce).max() < 5 * TOL
    assert abs(ref.loss(params, CFG, toks) - ce.mean()) < TOL


@pytest.mark.parametrize("mutate", ref.MUTATIONS)
def test_each_mutation_moves_the_logits(model_and_params, mutate):
    _, params = model_and_params
    toks = _tokens()
    base = np.asarray(ref.logits(params, CFG, toks))
    moved = np.asarray(ref.logits(params, CFG, toks, mutate=mutate))
    if mutate == "per_head_qk_norm":
        # with scales of 1 the per-head form still differs: other variances
        assert np.abs(moved - base).max() > 20 * TOL
    else:
        assert np.abs(moved - base).max() > 100 * TOL


def test_generated_gaps_pass_greedy_tokens_and_fail_another(
        model_and_params):
    _, params = model_and_params
    prompt = _tokens(30)
    seq = list(prompt)
    for _ in range(8):          # the reference's own greedy continuation
        seq.append(int(np.argmax(np.asarray(
            ref.logits(params, CFG, seq, last=1))[0])))
    gaps = ref.generated_gaps(params, CFG, prompt, seq[30:])
    assert gaps.shape == (8,) and gaps.max() == 0.0
    # any other token lies under the maximum
    other = [(t + 1) % 96 for t in seq[30:]]
    assert ref.generated_gaps(params, CFG, prompt[:-1] + [prompt[-1]],
                              other[:1]).min() > 0.0
    low = ref.generated_gaps(params, CFG, prompt, seq[30:],
                             precision="float8")
    assert np.isfinite(low).all()


def test_the_reference_imports_nothing_of_the_program():
    code = open(ref.__file__).read().split('"""', 2)[2]
    assert "deepspeed_tpu" not in code and "import flax" not in code
