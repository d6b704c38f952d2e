"""Operations and bytes LATENT attention needs on the serving path, from
shapes: the ABSORBED form over a cache of one row ``[c | k_r]`` a token
(``kv_lora_rank + qk_rope_head_dim`` values, no heads), which is the form
``deepspeed_tpu/ops/latent_attention.py`` runs.  The yardstick of the
``mla_*`` roofline shares, beside ``flops.py``, whose ``_dims`` divides the
hidden size by the heads and counts keys and values per head and would
count this model wrong (``benchmark/tests/test_mla_metrics.py`` holds both
facts at the published sizes).

Only what the form that runs must do is counted, at the UNPADDED widths: a
query-key pair a head is a 576-wide dot and a 512-wide weighted sum (the
pool stores the row padded to 640, and the padding is multiplied and read,
but no algorithm needs it), a cached token is read once a decode tick
whatever its heads, and the projections are the matrices' own sizes.  So no
share computed from these can pass 100 %.

All arguments are the configuration file's keys (the source's names).
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(c: Dict[str, Any]):
    return (int(c["num_attention_heads"]), int(c["kv_lora_rank"]),
            int(c["qk_rope_head_dim"]), int(c["num_hidden_layers"]))


def latent_row(c: Dict[str, Any]) -> int:
    """Values of the one row a layer caches for a token, unpadded."""
    _, rank, rope, _ = _dims(c)
    return rank + rope


def latent_bytes_per_token(c: Dict[str, Any], bytes_per_value: int = 2,
                           stored: bool = False) -> int:
    """What a cached token costs over the layers: the row as the algorithm
    needs it, or (``stored``) as the pool keeps it (``latent_row_stored``,
    padded to whole lane tiles)."""
    row = int(c["latent_row_stored"]) if stored else latent_row(c)
    return _dims(c)[3] * row * bytes_per_value


def pair_flops(c: Dict[str, Any]) -> int:
    """One query-key pair, all heads, one layer, absorbed: the score over
    the row and the weighted sum over its value columns."""
    heads, rank, rope, _ = _dims(c)
    return heads * 2 * ((rank + rope) + rank)


def chunk_attention_flops(c: Dict[str, Any], key_tokens: float) -> float:
    """The prompt-chunk kernel over the layers: ``key_tokens`` is the sum,
    over a tick's prompt tokens, of the keys each attends (causal: the
    program's ``prefill_key_tokens``)."""
    return float(_dims(c)[3] * pair_flops(c)) * key_tokens


def decode_attention_bytes(c: Dict[str, Any], key_tokens: float) -> float:
    """The decode-row kernel over the layers: every live context token's
    row once."""
    return float(latent_bytes_per_token(c)) * key_tokens


def attention_params(c: Dict[str, Any]) -> int:
    """The matrices of one layer's latent attention (norm scales left
    out): ``q_a``, ``q_b``, ``kv_a``, ``kv_b``, ``o``."""
    heads, rank, rope, _ = _dims(c)
    e, q_rank = int(c["hidden_size"]), int(c["q_lora_rank"])
    nope, v = int(c["qk_nope_head_dim"]), int(c["v_head_dim"])
    return (e * q_rank + q_rank * heads * (nope + rope) + e * (rank + rope)
            + rank * heads * (nope + v) + heads * v * e)
