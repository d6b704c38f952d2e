"""Accelerator abstraction: ``get_accelerator()``.

TPU-native counterpart of the reference L0 layer
(``accelerator/real_accelerator.py:52 get_accelerator`` returning a
``DeepSpeedAccelerator`` ABC with ~80 methods).  The reference needs a
vendor-dispatch facade because every backend brings its own streams,
events, allocators, and op builders; under JAX one runtime serves every
platform, so the facade collapses to a thin adapter over ``jax.devices``
— kept because user code and the reference's own subsystems call these
entry points by name (``device_name``, ``device_count``,
``total_memory``, ``synchronize``, ``communication_backend_name``, ...).

Stream/event/graph methods are intentionally absent: XLA owns scheduling
on TPU and there is nothing truthful for them to do.  Code portable with
the reference should feature-check via ``hasattr``.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np


def require_accelerator(cpu_requested: bool = False) -> bool:
    """What the entry points (``examples/``) call before
    any work: True on an accelerator.  On the CPU it is False only when
    the caller asked for the CPU in so many words — its own switch
    (``cpu_requested``, e.g. ``--smoke``) or ``JAX_PLATFORMS=cpu`` in the
    environment — and an error otherwise: a missing chip is never
    something discovered and papered over."""
    platform = jax.devices()[0].platform
    if platform != "cpu":
        return True
    if cpu_requested or os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return False
    raise SystemExit(
        "no accelerator: JAX found only the CPU.  Run on the chip, or ask "
        "for the CPU explicitly (JAX_PLATFORMS=cpu, or this program's own "
        "smoke switch)")


class TPU_Accelerator:
    """The one accelerator (platform resolved from the live backend:
    tpu, or cpu under the test mesh)."""

    def __init__(self):
        self._name = jax.devices()[0].platform

    # -- identity -------------------------------------------------------

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def current_device_name(self) -> str:
        return self.device_name(0)

    def current_device(self) -> int:
        return 0

    def device_count(self) -> int:
        return jax.local_device_count()

    def is_available(self) -> bool:
        return len(jax.devices()) > 0

    def communication_backend_name(self) -> str:
        return "xla"            # ICI/DCN collectives compiled by XLA

    # -- capabilities ---------------------------------------------------

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        # fp16 compute is supported (loss-scaled); bf16 is the native
        # matmul dtype on TPU
        return True

    def is_triton_supported(self) -> bool:
        return False

    def device_kind(self) -> str:
        return jax.devices()[0].device_kind

    # -- memory ---------------------------------------------------------

    def _stats(self) -> dict:
        try:
            return jax.local_devices()[0].memory_stats() or {}
        except Exception:
            return {}

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return int(self._stats().get("bytes_limit", 0))

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self._stats().get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: Optional[int] = None
                             ) -> int:
        return int(self._stats().get("peak_bytes_in_use",
                                     self.memory_allocated()))

    def available_memory(self, device_index: Optional[int] = None) -> int:
        s = self._stats()
        return int(s.get("bytes_limit", 0)) - int(s.get("bytes_in_use", 0))

    def empty_cache(self) -> None:
        pass                    # XLA owns the arena

    # -- execution ------------------------------------------------------

    def synchronize(self, device_index: Optional[int] = None) -> None:
        jax.effects_barrier()

    def manual_seed(self, seed: int):
        return jax.random.PRNGKey(seed)

    def manual_seed_all(self, seed: int):
        return jax.random.PRNGKey(seed)

    # -- dtypes ---------------------------------------------------------

    def supported_dtypes(self):
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16]

    # -- misc parity ----------------------------------------------------

    def on_accelerator(self, x) -> bool:
        return isinstance(x, jax.Array)

    def pin_memory(self, x):
        return np.ascontiguousarray(np.asarray(x))

    def lazy_call(self, fn):
        return fn()


_ACCELERATOR: Optional[TPU_Accelerator] = None


def get_accelerator() -> TPU_Accelerator:
    """Reference ``get_accelerator()`` entry point."""
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = TPU_Accelerator()
    return _ACCELERATOR
