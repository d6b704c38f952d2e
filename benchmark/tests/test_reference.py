"""The plain reference agrees with the program's model at ``tinymistral``
size, and a one-position shift of the mask fails the tolerance.  The
training cell's check is run at its SHIPPED tolerances on a model small
enough for the CPU: the bf16 program passes; a shifted mask, a dropped
layer, a narrow window and a shifted target each fail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mistral as ref
from deepspeed_tpu.models import mistral

CFG = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
           sliding_window=16, rms_norm_eps=1e-5, rope_theta=10000.0)
# float32, no kernel: the comparison is then about the mathematics alone;
# 2e-4 is float32 rounding through two layers at logits of order 1
TOL = 2e-4


def _model(scan: bool):
    cfg = mistral.get_config(
        "tinymistral", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=scan, remat=False, use_flash_attention=False)
    model = mistral.MistralForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(3),
                                 np.zeros((1, 8), np.int32))
    return model, params


def _tokens(n=40):      # longer than the window of 16, so the window binds
    return np.random.default_rng(0).integers(1, 256, size=n).tolist()


def test_reference_matches_the_programs_model_in_both_layouts():
    for scan in (False, True):
        model, params = _model(scan)
        toks = _tokens()
        want = np.asarray(jax.jit(model.apply)(
            params, np.asarray([toks], np.int32))[0])
        got = np.asarray(ref.logits(params, CFG, toks))
        assert np.abs(got - want).max() < TOL, scan


def test_a_one_position_mask_shift_fails_the_tolerance():
    model, params = _model(False)
    toks = _tokens()
    want = np.asarray(jax.jit(model.apply)(
        params, np.asarray([toks], np.int32))[0])
    shifted = np.asarray(ref.logits(params, CFG, toks, mask_shift=1))
    assert np.abs(shifted - want).max() > 100 * TOL
    no_window = np.asarray(ref.logits(
        params, dict(CFG, sliding_window=None), toks))
    assert np.abs(no_window - want).max() > 100 * TOL


def test_generated_gaps_pass_greedy_tokens_and_fail_under_a_shifted_mask():
    model, params = _model(False)
    prompt = _tokens(30)
    seq = list(prompt)
    for _ in range(8):                      # greedy, by the program's model
        lg = jax.jit(model.apply)(params, np.asarray([seq], np.int32))[0]
        seq.append(int(np.asarray(lg[-1]).argmax()))
    generated = seq[len(prompt):]
    gaps = ref.generated_gaps(params, CFG, prompt, generated)
    assert gaps.shape == (8,) and gaps.max() < 1e-3
    wrong = ref.generated_gaps(params, CFG, prompt, generated, mask_shift=1)
    assert wrong.max() > 1e-3
    # a wrong token is caught too
    bad = list(generated)
    bad[3] = (bad[3] + 1) % 256
    assert ref.generated_gaps(params, CFG, prompt, bad).max() > 1e-3


def test_reference_loss_matches_the_loss_module():
    from deepspeed_tpu.models.llama import LlamaLMLoss

    cfg = mistral.get_config(
        "tinymistral", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=True, remat=False, use_flash_attention=False)
    module = LlamaLMLoss(cfg)
    toks = np.asarray([_tokens(33)], np.int32)
    params = jax.jit(module.init)(jax.random.PRNGKey(1), {"input_ids": toks})
    want = float(jax.jit(module.apply)(params, {"input_ids": toks}))
    assert abs(ref.loss(params, CFG, toks[0]) - want) < 1e-4


# -- the training cell's check at the tolerances its configuration ships ----

def _train_check_setup(dtype: str):
    """Mistral widths shrunk for the CPU, the published vocabulary kept (the
    loss at random weights is ln(vocab) + var/2, so the vocabulary is what
    makes a mean blind); float32 master weights, ``dtype`` compute."""
    from benchmark import harness as H
    from benchmark.drivers import train

    cfg = H.load_json(H.HERE + "/configs/mistral-7b-zero3.json")
    cfg.update(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2)
    cfg["model_overrides"].update(dtype=dtype, use_flash_attention=False)
    spec = cfg["correct"]
    rows = np.random.default_rng(5).integers(
        1, cfg["vocab_size"], size=(spec["rows"], spec["tokens"]),
        dtype=np.int32)
    module = H.resolve(cfg["family"]["loss_module"])(
        H.build_model_config(cfg))
    params = jax.jit(module.init)(jax.random.PRNGKey(5),
                                  {"input_ids": rows[:1]})
    got = np.asarray(jax.jit(train.position_losses_fn(cfg))(
        params["params"][spec["module_scope"]], rows))
    return cfg, spec, rows, params, got


def _reference_losses(params, cfg, rows, target_shift=0, **fault):
    out = []
    for r in rows:
        lg = np.asarray(ref.logits(params, cfg, r, **fault))[:-1]
        tgt = np.roll(r, -1 - target_shift)[:-1]
        m = lg.max(axis=-1)
        lse = m + np.log(np.exp(lg - m[:, None]).sum(axis=-1))
        out.append(lse - lg[np.arange(len(tgt)), tgt])
    return np.stack(out)


def test_train_check_passes_the_bf16_program_at_the_shipped_tolerances():
    cfg, spec, rows, params, got = _train_check_setup("bfloat16")
    want = np.stack([ref.token_losses(params, cfg, r) for r in rows])
    assert np.abs(got - want).max() <= spec["position_loss_abs_tol"]
    rel = np.abs(got.mean(axis=1) - want.mean(axis=1)) / want.mean(axis=1)
    assert rel.max() <= spec["loss_rel_tol"]


@pytest.mark.parametrize("fault", [
    dict(mask_shift=1), dict(num_hidden_layers=3), dict(num_hidden_layers=2),
    dict(sliding_window=8), dict(target_shift=1)], ids=str)
def test_train_check_fails_each_fault_at_the_shipped_tolerances(fault):
    cfg, spec, rows, params, got = _train_check_setup("float32")
    want = _reference_losses(params, cfg, rows)
    assert np.abs(got - want).max() < 1e-3     # the sound program passes
    kw = {k: fault[k] for k in ("mask_shift", "target_shift") if k in fault}
    wrong_cfg = dict(cfg, **{k: v for k, v in fault.items() if k not in kw})
    wrong = _reference_losses(params, wrong_cfg, rows, **kw)
    if "target_shift" in fault:
        # the program's model has no targets: this fault is the loss
        # module's, and the per-row mean is what the engine's loss is held
        # to.  The run asks it of every row, so one failing row fails it;
        # most rows must fail here, and by far
        rel = np.abs(wrong.mean(axis=1) - want.mean(axis=1)) \
            / want.mean(axis=1)
        assert (rel > spec["loss_rel_tol"]).sum() >= len(rel) - 1
        assert rel.max() > 5 * spec["loss_rel_tol"]
    else:
        assert np.abs(wrong - got).max() > 5 * spec["position_loss_abs_tol"]


# -- the serving cells' check at the margin their configuration ships -------

def test_serve_check_short_prompt_fails_a_shifted_mask_at_the_shipped_margin():
    """Among the dozen keys of the short correctness prompt one key too many
    moves the logits by units; among the 700 of the long one by less than
    the margin, which is why the configuration asks for both."""
    from benchmark import harness as H

    cfg = H.load_json(H.HERE + "/configs/mistral-7b-serve.json")
    cfg.update(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2)
    cfg["model_overrides"].update(dtype="float32", param_dtype="float32")
    spec = cfg["correct"]
    short = min(spec["prompt_tokens"])
    assert short <= 16 < max(spec["prompt_tokens"])
    model = H.resolve(cfg["family"]["module"])(H.build_model_config(cfg))
    params = jax.jit(model.init)(jax.random.PRNGKey(7),
                                 np.zeros((1, 8), np.int32))
    apply = jax.jit(model.apply)
    prompt = np.random.default_rng(7).integers(
        1, cfg["vocab_size"], size=short).tolist()
    seq = list(prompt)
    for _ in range(spec["new_tokens"]):
        seq.append(int(np.asarray(
            apply(params, np.asarray([seq], np.int32))[0, -1]).argmax()))
    generated = seq[short:]
    margin = spec["logit_margin"]
    assert ref.generated_gaps(params, cfg, prompt, generated).max() <= margin
    for fault in (dict(mask_shift=1), dict(num_hidden_layers=3),
                  dict(sliding_window=4)):
        kw = {k: v for k, v in fault.items() if k == "mask_shift"}
        wrong = dict(cfg, **{k: v for k, v in fault.items() if k not in kw})
        gaps = ref.generated_gaps(params, wrong, prompt, generated, **kw)
        assert (gaps > margin).sum() >= 4, fault


# -- the control of the serving check: the nearest precision below bf16 -----

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_check_fails_the_e4m3_reference_at_the_shipped_margin(seed):
    """The control of ``logit_margin``, at a size a test run can hold
    (hidden 512, four layers, the whole vocabulary, the configuration's own
    weights recipe): the reference with every matmul operand rounded to
    e4m3, put in the program's place at every position of one seeded
    document, puts a token first that lies OVER the shipped margin under the
    float32 maximum; the reference rounded to bf16, what the configuration
    states, stays under it.  At the published size on the chip the two read
    0.92-2.03 and at most 0.035 (PERF.md section 6, PR 42)."""
    from benchmark import harness as H

    cfg = H.load_json(H.HERE + "/configs/mistral-7b-serve.json")
    cfg.update(hidden_size=512, intermediate_size=1024, num_hidden_layers=4,
               num_attention_heads=8, num_key_value_heads=2)
    margin = float(cfg["correct"]["logit_margin"])
    model = H.resolve(cfg["family"]["module"])(H.build_model_config(cfg))
    params = H.init_params(model, cfg, seed)
    doc = np.random.default_rng([seed, 0xD0]).integers(
        1, cfg["vocab_size"], size=96).tolist()
    exact = np.asarray(ref.logits(params, cfg, doc))
    at = np.arange(len(doc))

    def widest_gap(precision):
        low = np.asarray(ref.logits(params, cfg, doc, precision=precision))
        return float((exact.max(axis=-1) - exact[at, low.argmax(axis=-1)])
                     .max())

    assert widest_gap("bfloat16") <= margin / 2
    assert widest_gap("float8") > 2 * margin


def test_init_params_centres_matrices_only_where_the_configuration_asks():
    from benchmark import harness as H

    cfg = H.load_json(H.HERE + "/configs/mistral-7b-serve.json")
    cfg.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2)
    assert cfg["weights"] == {"centre_matrices": True}
    model = H.resolve(cfg["family"]["module"])(H.build_model_config(cfg))
    centred = H.init_params(model, cfg, 2 ** 31 + 5)
    again = H.init_params(model, cfg, 2 ** 31 + 5)
    plain = H.init_params(model, dict(cfg, weights={}), 2 ** 31 + 5)
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, c), (_, a), (_, p) in zip(leaves(centred), leaves(again),
                                         leaves(plain)):
        assert c.dtype == p.dtype and c.shape == p.shape
        assert np.array_equal(np.asarray(c, np.float32),
                              np.asarray(a, np.float32)), path
        c32, p32 = np.asarray(c, np.float32), np.asarray(p, np.float32)
        if c.ndim < 2:                      # norm scales: untouched
            assert np.array_equal(c32, p32), path
            continue
        # the bf16 draw lies about 1.2 % of a standard deviation under zero
        assert p32.mean() < -0.005 * p32.std(), path
        assert abs(c32.mean()) < 0.002 * c32.std(), path
