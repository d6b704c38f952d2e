"""The device boundary says what it did: kernel routes are recorded, and a
missing chip is an error unless the CPU was asked for.  (That an unknown
device has no peak and a missing trace is an error is held by
``benchmark/tests``, beside ``peaks.json`` and ``trace_reduce.py``.)"""
import jax.numpy as jnp
import pytest

from deepspeed_tpu.accelerator import require_accelerator
from deepspeed_tpu.ops import routes


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
    # heads of 64 lie in the pool padded to the kernels' 128 lanes
    assert paged_attention_path(64) == "xla-dense-pad128"
    assert paged_attention_path(32) == "xla-dense"
    assert kv_dequant_path(128) == "xla-gather"
    assert routes.taken()["paged_attention"] == "xla-dense"


def test_missing_chip_is_an_error_unless_cpu_was_asked_for(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_accelerator() is False
    monkeypatch.delenv("JAX_PLATFORMS")
    assert require_accelerator(cpu_requested=True) is False
    with pytest.raises(SystemExit, match="no accelerator"):
        require_accelerator()
