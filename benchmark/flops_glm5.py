"""Operations and bytes of GLM-5's indexer and of its latent attention over
chosen rows, from shapes and the dispatch spans' counts whatever implements
them: what the layer equations of ``benchmark/reference/glm_moe_dsa.py``
need, not what a kernel happens to read.  The readers
``dsa_index_prefill_roofline``, ``dsa_index_decode_roofline``,
``dsa_attend_prefill_roofline`` and ``dsa_attend_decode_roofline`` divide by
the device time of the operations that do the work.  A masked dense pass
multiplies ``t + 1`` keys a query where these count ``min(t + 1,
index_topk)``, so its share reads low and no share can pass 100 %; a form
that gathers the chosen rows raises the reading without an edit here.

``config`` is the configuration file's object (the source's names)."""
from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def layers(config: Dict[str, Any]) -> int:
    return int(config["num_hidden_layers"])


def row_width(config: Dict[str, Any]) -> int:
    """A cached latent row as the equations have it: ``[c | k_r]``."""
    return int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])


def index_bytes_per_token(config: Dict[str, Any]) -> int:
    """The index key of a cached token: one ``index_head_dim`` vector a
    layer in bf16 (a key a row of 128 lanes: what the chip holds too)."""
    return layers(config) * int(config["index_head_dim"]) * BF16


def pool_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token costs the pool: its latent row AS STORED (the
    file's ``latent_row_stored``: 576 in 640, whole lane tiles) and its
    index key, a layer, in bf16."""
    return (layers(config) * int(config["latent_row_stored"]) * BF16
            + index_bytes_per_token(config))


def index_scores(config: Dict[str, Any], visible_tokens: int, rows: int,
                 shared_keys: bool) -> Dict[str, float]:
    """The indexer's scores of ``visible_tokens`` (query, key) pairs for
    ``rows`` queries, all layers: ``2 HI dI`` flops a pair (the relu, the
    head weights and the sum are the VPU's and not counted); a query's
    heads read once; the index keys read once a QUERY where each query is
    its sequence's only one (``shared_keys`` false: a decode row's own
    bound, ``dI`` bf16 a pair) and once a tile of 128 queries where a
    prompt chunk's rows share them."""
    hi, di = int(config["index_n_heads"]), int(config["index_head_dim"])
    n = layers(config)
    key_reads = visible_tokens / 128.0 if shared_keys else visible_tokens
    return {"flops": float(n * visible_tokens * 2 * hi * di),
            "bytes": float(n * (key_reads * di * BF16
                                + rows * hi * di * BF16))}


def chosen_attention(config: Dict[str, Any], key_tokens: int, rows: int,
                     shared_keys: bool) -> Dict[str, float]:
    """Latent attention of ``rows`` queries over ``key_tokens`` CHOSEN
    (query, key) pairs in all (``min(t + 1, index_topk)`` a query: not the
    visible ones), all layers, in the ABSORBED form a served token runs:
    ``2 (rank + rope) + 2 rank`` flops a pair a head (2,176: the score over
    the row, the sum over its first ``rank`` columns).  ONE row serves every
    head: a decode row reads its chosen rows once (``shared_keys`` false:
    ``rank + rope`` bf16 a pair), a prompt chunk's rows share what they
    read, so its bound is the flops; a query's absorbed heads in and its
    ``rank``-wide sums out besides."""
    h, w = int(config["num_attention_heads"]), row_width(config)
    rank = int(config["kv_lora_rank"])
    n = layers(config)
    key_reads = key_tokens / 128.0 if shared_keys else key_tokens
    return {"flops": float(n * key_tokens * h * 2 * (w + rank)),
            "bytes": float(n * (key_reads * w * BF16
                                + rows * h * (w + rank) * BF16))}
