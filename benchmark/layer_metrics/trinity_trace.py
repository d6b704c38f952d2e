"""What the readers of a model with window layers and full layers share: the
device self time of attention by layer kind and by kernel (``mla_trace.rows``:
every operation of the slice with its scope) and the ``engine.dispatch``
spans' pool counts.  Not a metric itself.

``models/trinity.py`` runs a window layer's attention under the scope
``attn_window`` and a full layer's under ``attn_full``, and
``telemetry/device_scopes.py`` leads an operation's sub-scope with that name
(a Pallas kernel's own name behind it: ``attn_window/paged_chunk_attention``).
A program without the scopes (every other model, or the parent of the PR that
added them) has no such rows and its spans none of the ``window_*`` counts,
so every reader here returns None."""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.layer_metrics import host_spans, mla_trace

KINDS = ("attn_window", "attn_full")
CHUNK_KERNEL, DECODE_KERNEL = "paged_chunk_attention", "paged_decode_attention"


def _mine(ctx: Dict[str, Any]):
    got = mla_trace.rows(ctx)
    if got is None:
        return None, None
    return got, [r for r in got if r[1] == "attn" and r[2]
                 and r[2].split("/", 1)[0] in KINDS]


def share_pct(ctx: Dict[str, Any], kind: str) -> Optional[float]:
    """Self time of every attention operation under scope ``kind`` (the
    projections, norms, rotary embedding, the K/V scatter, the gate and the
    kernels) over the summed self time of the slice, in percent."""
    got, mine = _mine(ctx)
    picked = [r[5] for r in mine or ()
              if r[2].split("/", 1)[0] == kind]
    return 100.0 * sum(picked) / sum(r[5] for r in got) if picked else None


def kernel_seconds(ctx: Dict[str, Any], kernel: str) -> Optional[float]:
    """Self seconds of ``kernel`` under both kinds of layer together."""
    _, mine = _mine(ctx)
    picked = [r[5] for r in mine or () if r[2].endswith("/" + kernel)]
    return sum(picked) if picked else None


def pool_spans(ctx: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The ``engine.dispatch`` spans of the slice that say what the window
    layers' pool holds."""
    ht = host_spans.load(ctx)
    if ht is None:
        return []
    return [e[3] for e in ht.spans("engine.dispatch")
            if "window_pages_held" in e[3]]
