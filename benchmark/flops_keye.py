"""Operations and bytes of Keye-VL-2.0's indexer and of its attention over
chosen tokens, from shapes and the dispatch spans' counts whatever
implements them: what the layer equations of ``benchmark/reference/
keye_vl2.py`` need, not what a kernel happens to read.  The readers
``index_prefill_roofline``, ``index_decode_roofline``,
``tokensel_prefill_roofline`` and ``tokensel_decode_roofline`` divide by
the device time of the operations that do the work.

``config`` is the configuration file's object (the source's names)."""
from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def layers(config: Dict[str, Any]) -> int:
    return int(config["num_hidden_layers"])


def index_bytes_per_token(config: Dict[str, Any]) -> int:
    """The index keys of a cached token: one ``indexer_head_dim`` vector a
    layer in bf16 (the pool packs two to a row of 128 lanes, so it is what
    the chip holds too)."""
    return layers(config) * int(config["sa_config"]["indexer_head_dim"]) * BF16


def pool_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token costs the pool: K and V of every KV head in
    bf16 (a token a row: no padding) and its index key, a layer."""
    d, hkv = int(config["head_dim"]), int(config["num_key_value_heads"])
    return (layers(config) * 2 * hkv * d * BF16
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
    sa = config["sa_config"]
    hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    n = layers(config)
    key_reads = visible_tokens / 128.0 if shared_keys else visible_tokens
    return {"flops": float(n * visible_tokens * 2 * hi * di),
            "bytes": float(n * (key_reads * di * BF16
                                + rows * hi * di * BF16))}


def chosen_attention(config: Dict[str, Any], key_tokens: int, rows: int,
                     shared_keys: bool) -> Dict[str, float]:
    """Attention of ``rows`` queries over ``key_tokens`` CHOSEN (query, key)
    pairs in all (``min(t + 1, topk)`` a query: not the visible ones), all
    layers: ``4 d`` flops a pair a query head; a decode row reads its
    chosen tokens' K and V of every KV head (``shared_keys`` false), a
    prompt chunk's rows share what they read, so its bound is the flops."""
    h, d = int(config["num_attention_heads"]), int(config["head_dim"])
    hkv = int(config["num_key_value_heads"])
    n = layers(config)
    key_reads = key_tokens / 128.0 if shared_keys else key_tokens
    return {"flops": float(n * key_tokens * h * 4 * d),
            "bytes": float(n * (key_reads * hkv * 2 * d * BF16
                                + rows * h * d * 2 * BF16))}
