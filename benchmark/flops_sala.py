"""Operations and bytes of MiniCPM-SALA's two mixers, from shapes and the
dispatch spans' counts whatever implements them: what the layer equations
of ``benchmark/reference/minicpm_sala.py`` need, not what a kernel happens
to read.  The readers ``linear_scan_roofline``, ``linear_decode_roofline``,
``sparse_prefill_roofline`` and ``sparse_decode_roofline`` divide by the
device time of the kernel that does the work.

``config`` is the configuration file's object (the source's names)."""
from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def linear_layers(config: Dict[str, Any]) -> int:
    return sum(t == "lightning-attn" for t in config["mixer_types"])


def sparse_layers(config: Dict[str, Any]) -> int:
    return sum(t == "minicpm4" for t in config["mixer_types"])


def state_bytes_per_seq(config: Dict[str, Any]) -> int:
    """A sequence's matrix states: layers x heads x d x d float32."""
    d = int(config["lightning_head_dim"])
    return linear_layers(config) * int(config["lightning_nh"]) * d * d * F32


def pool_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token costs the pool: K and V of every KV head in
    bf16 (heads lead the tokens: no padding), and the compressed keys, one
    float32 vector a KV head a ``kernel_stride`` tokens."""
    d = int(config["head_dim"])
    hkv = int(config["num_key_value_heads"])
    stride = int(config["sparse_config"]["kernel_stride"])
    return sparse_layers(config) * (2 * hkv * d * BF16
                                    + hkv * d * F32 // stride)


def linear_scan(config: Dict[str, Any], tokens: int, chunk: int = 128
                ) -> Dict[str, float]:
    """The chunked scan over ``tokens`` prompt tokens, all linear layers:
    a chunk of ``C`` tokens a head is ``Q K^T`` and ``(..) V`` (``2 C^2 d``
    each), ``Q S`` and ``K^T V`` (``2 C d^2`` each); q, k, v read and o
    written in bf16."""
    h, d = int(config["lightning_nh"]), int(config["lightning_head_dim"])
    flops = tokens * h * (4 * chunk * d + 4 * d * d)
    byts = tokens * h * d * 4 * BF16
    return {"flops": float(linear_layers(config) * flops),
            "bytes": float(linear_layers(config) * byts)}


def linear_decode(config: Dict[str, Any], row_steps: int) -> Dict[str, float]:
    """One token a row: the state read and written once a row a step, a
    head ``4 d^2`` flops (decay, outer product, ``q S``)."""
    h, d = int(config["lightning_nh"]), int(config["lightning_head_dim"])
    return {"flops": float(linear_layers(config) * row_steps * h * 4 * d * d),
            "bytes": float(linear_layers(config) * row_steps
                           * 2 * h * d * d * F32)}


def sparse_attention(config: Dict[str, Any], key_tokens: int, rows: int
                     ) -> Dict[str, float]:
    """Attention of ``rows`` queries over ``key_tokens`` (query, key) pairs
    a KV head in all (chosen keys past ``dense_len``, every earlier key
    before it), all sparse layers: ``4 d`` flops a pair a query head; the
    keys and values read once a query (a decode row's own bound; a prompt
    chunk shares them among its rows, so its bound is the flops)."""
    h, d = int(config["num_attention_heads"]), int(config["head_dim"])
    hkv = int(config["num_key_value_heads"])
    n = sparse_layers(config)
    return {"flops": float(n * key_tokens * h * 4 * d),
            "bytes": float(n * (key_tokens * hkv * 2 * d * BF16
                                + rows * h * d * 2 * BF16))}
