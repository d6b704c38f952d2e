"""Small helpers the readers share: which records belong to the window or
to the traced slice.  A reader gets one ``ctx`` dict from its driver and
returns a number, or None where there is nothing to read."""
from __future__ import annotations

from typing import Any, Dict, List, Optional


def engine_records_in_window(ctx: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The engine's completed-request records submitted in the window.
    Their clock is ``time.perf_counter``; ``ctx["engine_clock_offset"]``
    (perf_counter - monotonic, taken by the driver) brings the window's
    edges onto it."""
    off = ctx.get("engine_clock_offset", 0.0)
    t0 = ctx["t0"] + off
    t1 = t0 + ctx["seconds"]
    return [r for r in ctx.get("engine_records", [])
            if t0 <= r["submit_t"] < t1]


def program_seconds(ctx: Dict[str, Any], role: str) -> Optional[float]:
    """Device seconds of the configuration's program for ``role``
    (``"decode"``, ``"prefill"``, ``"train_step"``) in the traced slice."""
    red = ctx.get("trace")
    name = ctx["cell"].config.get("programs", {}).get(role)
    if red is None or name is None:
        return None
    return red.program_s.get(name)


def request_spans(records: List[Dict[str, Any]],
                  engine_records: List[Dict[str, Any]],
                  engine_clock_offset: float
                  ) -> Optional[List[Dict[str, float]]]:
    """When the ENGINE worked on each completed request, on the benchmark's
    clock: ``admit``, ``prefill_end``, ``finish`` from
    ``request_latency.completed()``, with the prompt and generated token
    counts the client knows.  The engine's record carries no prompt length
    and the client's no engine time, so the two are paired in order of
    submission (the client's ``send_t``, the engine's ``submit_t``) and the
    pairing is checked: as many records on both sides, the same number of
    generated tokens in every pair.  A pairing that does not check out
    gives None, and the readers that need it report nothing.

    Requests the engine served before the client's first (the correctness
    request of set-up) are dropped."""
    mine = sorted((r for r in records if r["ok"]), key=lambda r: r["send_t"])
    if not mine:
        return None
    first = mine[0]["send_t"] + engine_clock_offset
    theirs = sorted((e for e in engine_records
                     if e["submit_t"] >= first - 0.5 and
                     e["queue_wait_ms"] is not None and
                     e["prefill_ms"] is not None),
                    key=lambda e: e["submit_t"])
    if len(mine) != len(theirs) or any(
            r["max_new_tokens"] != e["tokens"] for r, e in zip(mine, theirs)):
        return None
    out = []
    for r, e in zip(mine, theirs):
        admit = e["submit_t"] - engine_clock_offset + e["queue_wait_ms"] / 1e3
        out.append({"admit": admit,
                    "prefill_end": admit + e["prefill_ms"] / 1e3,
                    "finish": e["finish_t"] - engine_clock_offset,
                    "prompt_tokens": float(r["prompt_tokens"]),
                    "new_tokens": float(e["tokens"])})
    return out


def kv_tokens_held(span: Dict[str, float], t: float) -> float:
    """Tokens whose keys and values a request holds at time ``t``: its
    prompt filling evenly from admission to the end of prefill, then one
    more for every token generated, evenly until it finishes."""
    if not span["admit"] <= t <= span["finish"]:
        return 0.0
    if t < span["prefill_end"]:
        return span["prompt_tokens"] * (t - span["admit"]) / max(
            span["prefill_end"] - span["admit"], 1e-9)
    return span["prompt_tokens"] + span["new_tokens"] * (
        t - span["prefill_end"]) / max(span["finish"] - span["prefill_end"],
                                       1e-9)
