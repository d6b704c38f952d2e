"""The Ouro reference against a SECOND forward pass written another way
(numpy, float64, a head at a time, RoPE as a complex product, the passes
unrolled by hand), against the program's model at ``tinyouro`` size, and the
mutations the tolerance has to catch.  In the manner of
``test_reference_olmoe.py``; the same comparisons at the tier-1 tolerance
are ``tests/unit/models/test_ouro.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness as H
from benchmark.reference import ouro as ref
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.models import ouro

CFG = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
           rms_norm_eps=1e-6, rope_theta=1000000.0, total_ut_steps=3,
           early_exit_threshold=1.0)
TOL = 2e-4      # float32 rounding through two layers, three times
SHIPPED = H.load_json(H.HERE + "/configs/ouro-2.6b-serve.json")


@pytest.fixture(scope="module")
def model_and_params():
    model = ouro.OuroForCausalLM(ouro.get_config(
        "tinyouro", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=False, remat=False, use_flash_attention=False))
    params = jax.jit(model.init)(jax.random.PRNGKey(3),
                                 np.zeros((1, 8), np.int32))
    rng = np.random.default_rng(2)
    flat, tree = jax.tree_util.tree_flatten(params)
    # every norm's scale and the gate away from their initial values
    flat = [jnp.asarray(rng.uniform(0.5, 1.5, size=a.shape), a.dtype)
            if a.ndim == 1 else a for a in flat]
    return model, jax.tree_util.tree_unflatten(tree, flat)


def _tokens(n=40):
    return np.random.default_rng(0).integers(1, 96, size=n).tolist()


def _second_forward(params, toks):
    """float64, written from the equations: a head at a time, RoPE as
    multiplication by ``exp(i * pos * freq)`` on the pairs (j, j + D/2)."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                               params["params"])
    m = p["model"]
    eps, heads, theta = 1e-6, 4, 1e6
    norm = lambda x, s: x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * s
    x = m["embed_tokens"]["embedding"][np.asarray(toks)]
    n, d = len(toks), 32 // heads
    rot = np.exp(1j * np.arange(n)[:, None]
                 / theta ** (np.arange(d // 2) * 2.0 / d))

    def rope(v):                                    # [n, d]
        z = (v[:, : d // 2] + 1j * v[:, d // 2:]) * rot
        return np.concatenate([z.real, z.imag], -1)

    gates = []
    for _ in range(3):
        for i in range(2):
            L = m[f"layers_{i}"]
            h = norm(x, L["input_layernorm"]["scale"])
            outs = []
            for a in range(heads):
                cols = slice(a * d, (a + 1) * d)
                q = rope(h @ L["self_attn"]["q_proj"]["kernel"][:, cols])
                k = rope(h @ L["self_attn"]["k_proj"]["kernel"][:, cols])
                v = h @ L["self_attn"]["v_proj"]["kernel"][:, cols]
                o = np.zeros((n, d))
                for t in range(n):                  # causal, row by row
                    s = q[t] @ k[: t + 1].T / np.sqrt(d)
                    w = np.exp(s - s.max())
                    o[t] = (w / w.sum()) @ v[: t + 1]
                outs.append(o)
            att = np.concatenate(outs, -1) @ L["self_attn"]["o_proj"]["kernel"]
            x = x + norm(att, L["input_layernorm_2"]["scale"])
            h = norm(x, L["post_attention_layernorm"]["scale"])
            g = h @ L["mlp"]["gate_proj"]["kernel"]
            mlp = ((g / (1 + np.exp(-g))) * (h @ L["mlp"]["up_proj"]["kernel"])
                   ) @ L["mlp"]["down_proj"]["kernel"]
            x = x + norm(mlp, L["post_attention_layernorm_2"]["scale"])
        x = norm(x, m["norm"]["scale"])
        gates.append(1 / (1 + np.exp(-(x @ m["early_exit_gate"]["kernel"]
                                       + m["early_exit_gate"]["bias"]))))
    lam = np.concatenate(gates, -1)                                 # [n, 3]
    pdf = np.stack([lam[:, 0], lam[:, 1] * (1 - lam[:, 0]),
                    (1 - lam[:, 0]) * (1 - lam[:, 1])], -1)
    return x @ p["lm_head"]["kernel"], pdf


def test_reference_matches_a_second_forward_and_the_programs_model(
        model_and_params):
    model, params = model_and_params
    toks = _tokens()
    got = np.asarray(ref.logits(params, CFG, toks))
    second, pdf = _second_forward(params, toks)
    assert np.abs(got - second).max() < TOL
    assert np.abs(ref.exit_pdf(params, CFG, toks) - pdf).max() < 1e-5
    want = np.asarray(jax.jit(model.apply)(
        params, np.asarray([toks], np.int32))[0])
    assert np.abs(got - want).max() < TOL
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
    assert np.abs(moved - base).max() > 100 * TOL


def test_the_served_program_passes_the_shipped_margin_and_mutants_fail(
        model_and_params):
    """The engine's greedy tokens lie within the SHIPPED ``logit_margin``
    of the reference (at this size: within float32 rounding); a model a
    pass short, and one with the final norm once, generate tokens that
    judged the same way lie outside it."""
    model, params = model_and_params
    margin = float(SHIPPED["correct"]["logit_margin"])
    eng = RaggedInferenceEngineV2(model, params=params["params"], max_seqs=4,
                                  max_seq_len=64, prefill_chunk=8,
                                  page_size=8, decode_block_size=4)
    worst = {m: 0.0 for m in ("one_step_fewer", "final_norm_once")}
    for seed in range(4):
        prompt = np.random.default_rng(seed).integers(1, 96, size=21).tolist()
        out = eng.generate_all([np.asarray(prompt, np.int32)],
                               max_new_tokens=10)
        generated = list(out[max(out)])[21:]
        assert ref.generated_gaps(params, CFG, prompt,
                                  generated).max() < TOL < margin
        for mutate in worst:
            seq = list(prompt)
            for _ in range(10):   # the wrong model's own greedy tokens
                seq.append(int(np.argmax(np.asarray(ref.logits(
                    params, CFG, seq, last=1, mutate=mutate))[0])))
            worst[mutate] = max(worst[mutate], float(ref.generated_gaps(
                params, CFG, prompt, seq[21:]).max()))
    assert min(worst.values()) > margin, worst


def test_the_reference_imports_nothing_of_the_program():
    code = open(ref.__file__).read().split('"""', 2)[2]
    assert "deepspeed_tpu" not in code and "import flax" not in code
