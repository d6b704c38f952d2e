"""Test bootstrap: run every test on a virtual 8-device CPU mesh.

Mirrors the reference test strategy (SURVEY §4): distributed logic is
exercised single-node with fake devices — here via
``--xla_force_host_platform_device_count=8`` instead of forked torch
processes, since one JAX controller drives all 8 virtual devices.
"""
import os
import tempfile

import jax  # noqa: E402
import pytest  # noqa: E402

# jax is imported above, so env vars alone are not enough — use jax.config
# (valid until backends initialize).  The chip path is chip_smoke.py's; this
# file makes no TPU call, and neither may anything imported at collection.
if not os.environ.get("DSTPU_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    # The suite is XLA-compile-bound (a long tail of 5-20 s tests, each
    # building unique tiny-model programs whose execution takes
    # milliseconds) — skip the backend optimization passes on the CPU
    # test path: measured 46% off the heaviest file, same results.
    # Anything timing-sensitive runs on the chip through benchmark/run.py,
    # not here.  An explicit user setting of the flag wins.
    if "--xla_backend_optimization_level" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_backend_optimization_level=0")
    jax.config.update("jax_num_cpu_devices", 8)
    # One persistent compile cache for the run, shared by its workers: the
    # same tiny engine's programs are built by dozens of tests, each behind
    # a jit of its own, and XLA compiled every one of them anew (a third of
    # the CPU seconds of tests/unit/test_serving_pipeline.py).  The key is
    # the program's text and the compiler's options, so a hit is what the
    # compile would have given; ``count_compiles`` counts it as a compile.
    # Under the run's TMPDIR, never in the checkout; a user's own setting of
    # JAX_COMPILATION_CACHE_DIR wins.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            tempfile.gettempdir(), "dstpu-tests-jax-cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _reset_comm_state():
    """Fresh topology per test (tests install their own meshes), the
    engine-pinned MoE one included: whatever ran before on this worker
    must not decide what the next test resolves."""
    yield
    from deepspeed_tpu.comm import comm as _comm
    from deepspeed_tpu.moe.layer import pin_auto_dispatch
    _comm._state.topology = None
    pin_auto_dispatch(None)
    _comm.comms_logger.reset()
