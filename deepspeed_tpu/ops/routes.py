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
# jit traces, once per compiled program), or every distinct route of a
# switch that is asked once a call (``take(every=True)``)
_taken: Dict[str, str] = {}


def on_tpu() -> bool:
    """The one question every switch asks of the backend."""
    return jax.default_backend() == "tpu"


def take(kernel: str, *, eligible: bool = True, interpret: bool = False,
         pallas: str = "pallas", xla: str = XLA, every: bool = False
         ) -> str:
    """Choose and record ``kernel``'s route: ``pallas`` on a TPU backend
    (or under the Pallas interpreter, marked ``-interpret``) when the
    call is ``eligible`` for the kernel, else ``xla``.  A switch whose
    answer turns on the shapes of the call, and is asked several times a
    program, records ``every`` distinct answer (joined by ``+``, in the
    order first taken) and not the last alone."""
    if eligible and on_tpu():
        route = pallas
    elif eligible and interpret:
        route = pallas + "-interpret"
    else:
        route = xla
    seen = _taken[kernel].split("+") if every and kernel in _taken else []
    _taken[kernel] = "+".join(seen if route in seen else seen + [route])
    return route


def taken(prefix: str = "") -> Dict[str, str]:
    """Routes recorded so far in this process, by kernel name: all, or
    those of the kernels whose name starts with ``prefix``."""
    return {k: v for k, v in _taken.items() if k.startswith(prefix)}
