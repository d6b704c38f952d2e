"""``chip_smoke.py`` on the CPU: the same phases, in this process, at a
tiny preset handed in through ``run(plan)`` — and the three things the
driver's chip check leans on: the shape of the last line, a non-zero exit
with no result when there is no TPU, and where the compile cache goes."""
import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look the module up
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules[spec.name]


@pytest.fixture
def cache_config_restored():
    """``enable_compile_cache`` flips process-wide jax config; the rest of
    this worker's tests must not inherit a persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _tiny_plan(chip_smoke):
    from deepspeed_tpu.models import gpt2, mistral

    return chip_smoke.Plan(
        gpt2=gpt2.GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                             n_layer=2, n_head=2, use_flash_attention=True),
        train_seq=64, train_micro=2, train_steps=3,
        mistral=mistral.get_config(
            "tinymistral", dtype=jnp.float32, param_dtype=jnp.float32,
            scan_layers=False, remat=False, use_flash_attention=False),
        mistral_layers_published=2,
        serve=dict(max_seqs=4, max_seq_len=64, prefill_chunk=16,
                   page_size=16, decode_block_size=4),
        prompt_lens=(20, 33, 41, 50), new_tokens=8,
        quant_prompt_lens=(20, 24), quant_new_tokens=6,
        parity_prompt_len=12, delta_models=chip_smoke.delta_models(),
        on_chip=False)


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines()
            if ln.startswith("{")]


def test_one_chip_phases_and_last_line(chip_smoke, capsys, monkeypatch,
                                       tmp_path, cache_config_restored):
    # the env var is read when jax is imported, so setting it now places
    # nothing: run() must report it and leave jax's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    plan = _tiny_plan(chip_smoke)
    # the record of routes is the process's: whatever this worker's earlier
    # files took (heads of 64, say) is not this run's
    from deepspeed_tpu.ops import routes

    monkeypatch.setattr(routes, "_taken", {})
    # one device, as the driver's one-chip machine has
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    assert chip_smoke.run(plan, chips=1) == 0
    assert jax.config.jax_compilation_cache_dir == before
    lines = _json_lines(capsys.readouterr().out)
    train, serve, delta, cache, last = lines[-5:]
    assert train["phase"] == "train" and train["losses"][-1] < \
        train["losses"][0]
    assert serve["phase"] == "serve" and serve["compiles_after_warmup"] == 0
    assert serve["routes"] == {"bf16": "xla-dense", "int8": "xla-gather",
                               "bf16_decode": "xla-dense",
                               "bf16_prefill": "xla-dense"}
    assert serve["streamed"] == 2
    assert delta["phase"] == "delta_rule" and delta["routes"] == {
        "olmo_hybrid": "xla", "solar_open2": "xla"}
    assert cache["phase"] == "compile_cache" and cache["dir"] == \
        str(tmp_path)
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.local_devices()[0].device_kind,
        "count": 1}}
    assert list(last) == ["ok", "device"]


def test_across_chips_phases_on_virtual_devices(chip_smoke, capsys, devices,
                                                monkeypatch, tmp_path,
                                                cache_config_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from deepspeed_tpu.models import gpt2

    # wide enough that ZeRO-3 shards it: leaves under the persistence
    # threshold (1e5 elements) stay replicated by design
    plan = dataclasses.replace(
        _tiny_plan(chip_smoke),
        gpt2=gpt2.GPT2Config(vocab_size=2048, n_positions=64, n_embd=256,
                             n_layer=2, n_head=4, use_flash_attention=True),
        prompt_lens=(20, 33, 41, 50, 22, 35, 43, 48))
    assert chip_smoke.run(plan, chips=len(devices)) == 0
    lines = _json_lines(capsys.readouterr().out)
    zero3, replicas, _, last = lines[-4:]
    assert zero3["phase"] == "zero3" and zero3["devices"] == len(devices)
    assert replicas["phase"] == "replicas"
    assert len({r["device"] for r in replicas["replicas"]}) == len(devices)
    assert last["device"]["count"] == len(devices)


def test_no_tpu_is_an_error_and_prints_no_result(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_a_failing_phase_raises_before_any_result(chip_smoke, capsys,
                                                  monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # a plan that expects the chip's kernels cannot hold on the CPU
    plan = dataclasses.replace(_tiny_plan(chip_smoke), on_chip=True)
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    with pytest.raises(chip_smoke.SmokeFailure, match="fell to a reference"):
        chip_smoke.run(plan, chips=1)
    assert '"ok"' not in capsys.readouterr().out


def test_a_decode_block_on_another_route_fails_the_serve_phase(
        chip_smoke, monkeypatch):
    """The route check holds the decode blocks' own key too: a decode
    block that took another path than the plan expects (on the chip: the
    fused tick's kernel or a twin where the decode kernel should run) is
    a failure, whatever ``paged_attention`` says."""
    from deepspeed_tpu.inference import paged
    from deepspeed_tpu.ops import paged_decode, routes

    def elsewhere(*args, fallback, **kw):
        routes.take(paged_decode.KERNEL, eligible=False, every=True,
                    xla="pallas-ragged")
        return fallback()

    monkeypatch.setattr(routes, "_taken", {})
    monkeypatch.setattr(paged.kv_pages, "paged_decode_attention", elsewhere)
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="'bf16_decode': 'pallas-ragged'"):
        chip_smoke.serve_phase(_tiny_plan(chip_smoke))


def test_a_delta_rule_tick_on_the_twin_fails_the_delta_phase(chip_smoke):
    """On the chip a fused tick's short convolution must take its one-pass
    kernel; here it takes the twin, which a plan for the chip refuses."""
    plan = dataclasses.replace(_tiny_plan(chip_smoke), on_chip=True)
    with pytest.raises(chip_smoke.SmokeFailure, match="took the twin"):
        chip_smoke.delta_phase(plan)


def test_compile_cache_dir(monkeypatch, tmp_path, cache_config_restored):
    from deepspeed_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # jax's to read

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
