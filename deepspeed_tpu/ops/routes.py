"""Which implementation each kernel switch took.

Every Pallas kernel in this package has an XLA twin, because tier-1 runs
on a CPU that Mosaic does not serve.  The switch is the backend the
program runs on — decided up front, never by a caught exception — and
every switch goes through :func:`take`, which records the answer.  So
an engine can report its routes (``serving_stages()["kernels"]``) and
``chip_smoke.py`` can fail when a kernel that should run on the chip
took its twin.
"""
from __future__ import annotations

from typing import Dict

import jax

XLA = "xla"

# kernel name -> route of its most recent trace (a route is chosen while
# jit traces, once per compiled program)
_taken: Dict[str, str] = {}


def on_tpu() -> bool:
    """The one question every switch asks of the backend."""
    return jax.default_backend() == "tpu"


def take(kernel: str, *, eligible: bool = True, interpret: bool = False,
         pallas: str = "pallas", xla: str = XLA) -> str:
    """Choose and record ``kernel``'s route: ``pallas`` on a TPU backend
    (or under the Pallas interpreter, marked ``-interpret``) when the
    call is ``eligible`` for the kernel, else ``xla``."""
    if eligible and on_tpu():
        route = pallas
    elif eligible and interpret:
        route = pallas + "-interpret"
    else:
        route = xla
    _taken[kernel] = route
    return route


def taken() -> Dict[str, str]:
    """Routes recorded so far in this process, by kernel name."""
    return dict(_taken)
