"""Admission -> prefill complete (``prefill_ms`` of
``request_latency.completed()``), median over the requests submitted in
the window."""
from benchmark import harness as H
from benchmark.layer_metrics import windows as W


def read(ctx):
    ms = [r["prefill_ms"] for r in W.engine_records_in_window(ctx)
          if r["prefill_ms"] is not None]
    return H.percentile(ms, 50) if ms else None
