"""Operations and bytes of attention BY LAYER KIND in a model that has window
layers and full layers (``benchmark/reference/trinity.py``), from shapes and
the dispatch spans' counts whatever kernel serves them: a window layer's row
at position ``p`` needs ``min(p + 1, sliding_window)`` keys, a full layer's
``p + 1``.  The spans carry both sums (``window_prefill_key_tokens`` /
``window_decode_key_tokens`` beside ``prefill_key_tokens`` /
``decode_key_tokens`` / ``key_tokens``), counted from the rows' positions.
Only reachable keys are counted, so no share computed from these can pass
100 %.  The readers ``mixed_prefill_roofline`` and ``mixed_decode_roofline``
divide by the device time of the kernel that does the work.

``config`` is the configuration file's object (the source's names)."""
from __future__ import annotations

from typing import Any, Dict

BF16 = 2
WINDOW, FULL = "sliding_attention", "full_attention"


def layers(config: Dict[str, Any], kind: str) -> int:
    return sum(t == kind for t in config["layer_types"])


def _heads(config: Dict[str, Any]):
    return (int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]))


def pool_bytes_per_token(config: Dict[str, Any], kind: str) -> int:
    """What a cached token costs the pool of the layers of ``kind``: K and V
    of every KV head in bf16, a layer."""
    _, hkv, d = _heads(config)
    return layers(config, kind) * 2 * hkv * d * BF16


def pages_bound(config: Dict[str, Any]) -> int:
    """The most window pages a sequence holds: the columns a window behind
    a tick's first row and the tick's rows (``max_seqs + prefill_chunk``)
    touch, at the worst alignment."""
    eng = config["engine"]
    page = int(eng["page_size"])
    span = int(config["sliding_window"]) + int(eng["max_seqs"]) + int(
        eng["prefill_chunk"])
    return -(-span // page) + 1


def key_tokens(span: Dict[str, Any], what: str) -> Dict[str, float]:
    """``{kind: reachable (row, key) pairs}`` of one ``engine.dispatch``
    span, for its prompt chunks (``what="prefill"``) or its one-row
    sequences (``"decode"``)."""
    if span.get("program") == "decode_block":
        rows, ticks = int(span["rows"]), int(span["ticks"])
        full = (ticks * int(span["key_tokens"])
                + rows * ticks * (ticks - 1) / 2.0)
        return ({FULL: full,
                 WINDOW: float(span.get("window_decode_key_tokens", 0))}
                if what == "decode" else {FULL: 0.0, WINDOW: 0.0})
    return {FULL: float(span.get(f"{what}_key_tokens", 0)),
            WINDOW: float(span.get(f"window_{what}_key_tokens", 0))}


def attention(config: Dict[str, Any], pairs: Dict[str, float],
              rows: float = 0.0) -> Dict[str, float]:
    """Attention over ``pairs[kind]`` (row, key) pairs a layer of each kind:
    ``QK^T`` and ``PV`` at ``2 x heads x head_dim`` flops each a pair; every
    pair's K and V read ONCE a KV head where ``rows`` is 0 (a one-row
    sequence: nothing to share a key with), and where ``rows`` prompt rows
    share their keys only q read and o written (the keys' own bytes, a
    thousand rows a key, are left out: they can only raise the bound)."""
    h, hkv, d = _heads(config)
    weighted = sum(layers(config, k) * n for k, n in pairs.items())
    byts = (2 * (layers(config, WINDOW) + layers(config, FULL))
            * rows * h * d * BF16 if rows
            else weighted * 2 * hkv * d * BF16)
    return {"flops": 4.0 * h * d * weighted, "bytes": float(byts)}
