"""Independent requests with random token ids: no shared prefixes.

One general generator for serving mixes; a traffic file picks it with
``"generator": "independent_requests"`` and gives only parameters.

The schedule is the mix's, not the seed's: request sizes, the gaps between
arrivals and their order are drawn once from the mix's own
``population_seed``; ``--seed`` draws the token ids (and, in the driver,
the weights).  So two runs with different seeds do the same work at the
same instants, and their spread is the system's, not the sample's.  (The
same set in another order per seed was tried first: the order alone moved
``ttft_p95_ms`` by +-12 % between seeds against +-4 % between two runs of
one seed, my chip run, PR 24 -- which long prompts collide is work too.)

Open loop (``"loop": "open"``): ``round(rate * seconds)`` requests due in
the window ``[0, seconds)`` and ``round(rate * warmup_s)`` before it, with
exponential gaps (a Poisson process) scaled so that each span is filled
exactly.  Closed loop (``"loop": "closed"``): ``set_size`` requests that
the clients replay in order.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def _lengths(rng: np.random.Generator, spec: Dict[str, Any],
             n: int) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], dtype=np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo = spec.get("min", spec.get("value"))
    hi = spec.get("max", spec.get("value"))
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _arrivals(pop: np.random.Generator, n: int, span: float) -> np.ndarray:
    """``n`` arrival offsets in ``[0, span)``: exponential gaps (a Poisson
    process), scaled to fill the span."""
    if n == 0:
        return np.zeros(0)
    gaps = pop.exponential(1.0, n)
    return (np.cumsum(gaps) - gaps[0] * 0.5) * (span / gaps.sum())


def _requests(pop: np.random.Generator, ids: np.random.Generator,
              traffic: Dict[str, Any], n: int,
              vocab: int) -> List[Dict[str, Any]]:
    prompts = _lengths(pop, traffic["prompt_tokens"], n)
    outs = _lengths(pop, traffic["output_tokens"], n)
    return [{"prompt": ids.integers(1, vocab, size=int(prompts[j])).tolist(),
             "max_new_tokens": int(outs[j])} for j in range(n)]


def make(traffic: Dict[str, Any], seed: int, seconds: float,
         vocab_size: int) -> Dict[str, Any]:
    """The plan's ``requests`` (and what the loop needs beside them)."""
    pop_seed = int(traffic["population_seed"])
    ids = np.random.default_rng([int(seed), 0xA11])
    warmup = float(traffic["warmup_s"])
    if traffic["loop"] == "closed":
        pop = np.random.default_rng([pop_seed, 0])
        reqs = _requests(pop, ids, traffic, int(traffic["set_size"]),
                         vocab_size)
        return {"loop": "closed", "clients": int(traffic["clients"]),
                "warmup_s": warmup, "requests": reqs}
    rate = float(traffic["rate_per_s"])
    out: List[Dict[str, Any]] = []
    # the warm-up's population, then the window's: each its own fixed set
    for k, (start, span) in enumerate(((-warmup, warmup), (0.0, seconds))):
        n = int(round(rate * span))
        pop = np.random.default_rng([pop_seed, k, n])
        due = _arrivals(pop, n, span)
        for r, d in zip(_requests(pop, ids, traffic, n, vocab_size), due):
            r["due"] = float(start + d)
            out.append(r)
    return {"loop": "open", "warmup_s": warmup, "requests": out}
