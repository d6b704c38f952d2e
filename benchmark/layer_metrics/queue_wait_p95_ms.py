"""Engine submit -> first admission into a slot (``queue_wait_ms`` of
``request_latency.completed()``), 95th percentile over the requests
submitted in the window."""
from benchmark import harness as H
from benchmark.layer_metrics import windows as W


def read(ctx):
    waits = [r["queue_wait_ms"] for r in W.engine_records_in_window(ctx)
             if r["queue_wait_ms"] is not None]
    return H.percentile(waits, 95) if waits else None
