"""The device boundary says what it did: kernel routes are recorded, a
missing chip is an error unless the CPU was asked for, and the bench's
device-time helpers raise instead of papering over."""
import importlib.util
import os

import jax.numpy as jnp
import pytest

from deepspeed_tpu.accelerator import require_accelerator
from deepspeed_tpu.ops import routes

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_route_is_the_backend_and_is_recorded():
    # tier-1 runs on the CPU: every switch answers with its XLA twin
    assert routes.take("k_plain") == routes.XLA
    assert routes.take("k_interp", interpret=True) == "pallas-interpret"
    assert routes.take("k_named", eligible=False, interpret=True,
                       pallas="pallas-quant", xla="xla-gather") == \
        "xla-gather"
    taken = routes.taken()
    assert taken["k_plain"] == "xla"
    assert taken["k_interp"] == "pallas-interpret"
    assert taken["k_named"] == "xla-gather"


def test_kernel_switches_record_their_route():
    from deepspeed_tpu.ops import flash_attention, quantize

    x = jnp.ones((1, 2, 16, 8), jnp.float32)
    flash_attention(x, x, x, causal=True)
    quantize(jnp.ones((64,), jnp.float32), group_size=32)
    taken = routes.taken()
    assert taken["flash_attention_fwd"] == "xla"
    assert taken["quantize"] == "xla"


def test_paged_attention_paths_name_the_route():
    from deepspeed_tpu.inference.paged import (kv_dequant_path,
                                               paged_attention_path)

    assert paged_attention_path(128) == "xla-dense"        # CPU backend
    assert paged_attention_path(64) == "xla-dense"
    assert kv_dequant_path(128) == "xla-gather"
    assert routes.taken()["paged_attention"] == "xla-dense"


def test_missing_chip_is_an_error_unless_cpu_was_asked_for(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_accelerator() is False
    monkeypatch.delenv("JAX_PLATFORMS")
    assert require_accelerator(cpu_requested=True) is False
    with pytest.raises(SystemExit, match="no accelerator"):
        require_accelerator()


def test_peak_flops_knows_its_devices_or_raises(bench):
    assert bench.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no such device"):
        bench.peak_flops("no such device")
    with pytest.raises(KeyError):
        bench.peak_flops("cpu")


def test_trace_reader_raises_on_a_missing_trace(bench, tmp_path):
    with pytest.raises(RuntimeError, match="no .xplane.pb"):
        bench._device_seconds_from_trace(str(tmp_path))
