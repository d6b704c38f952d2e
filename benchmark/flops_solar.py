"""Operations and bytes of Solar-Open2's Kimi Delta Attention, from shapes and
the dispatch spans' counts whatever implements them: what the layer equations
of ``benchmark/reference/solar_open2.py`` need in their chunked form, not what
a kernel happens to read.  The readers ``kda_scan_roofline`` and
``kda_decode_roofline`` divide by the device time of the kernel that does the
work.

``config`` is the configuration file's object (the source's names)."""
from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def kda_layers(config: Dict[str, Any]) -> int:
    of = int(config["num_hidden_layers"])
    return of - len({int(i) for i in config["gqa_layers"] if 0 <= i < of})


def attention_layers(config: Dict[str, Any]) -> int:
    return int(config["num_hidden_layers"]) - kda_layers(config)


def _head(config: Dict[str, Any]):
    la = config["linear_attn_config"]
    return int(la["num_heads"]), int(la["head_dim"])


def state_bytes_per_seq(config: Dict[str, Any]) -> int:
    """A sequence's per-slot state AS COUNTED: a float32 ``d x d`` matrix a
    head, and the convolution's last ``taps - 1`` inputs of q~, k~ and v~ in
    bf16, a layer (4,194,304 + 147,456 at the published widths)."""
    h, d = _head(config)
    rows = int(config["linear_attn_config"]["short_conv_kernel_size"]) - 1
    return kda_layers(config) * (h * d * d * F32 + rows * 3 * h * d * BF16)


def pool_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token costs the pool: K and V of every KV head in bf16,
    a GQA layer."""
    return (attention_layers(config) * 2 * int(config["num_key_value_heads"])
            * int(config["head_dim"]) * BF16)


def kda_scan(config: Dict[str, Any], tokens: int, chunk: int = 64
             ) -> Dict[str, float]:
    """The chunked form over ``tokens`` prompt tokens, all KDA layers.  A
    chunk of ``C`` tokens a head: ``P`` and ``R``, the exponent split by a
    reference row so that each is a product (``2 C^2 d`` each), ``(K * exp
    G) S_0`` and ``(Q * exp G) S_0`` (``2 C d^2`` each), the solve's
    application ``T (..)`` and ``tril(R) U`` (``2 C^2 d`` halved by the
    triangle, twice), the solve itself by forward substitution (``2 C^3 /
    3``), ``(K ..)^T U`` (``2 C d^2``): a token a head ``4 C d + 2 C d + 2
    C^2 / 3 + 6 d^2``; q, k, v read and o written in bf16, **g read in
    float32 at ``d`` a head** (128 times the scalar decay's bytes) and b
    once.  ``chunk`` is the count's own (64, as ``flops_olmo_hybrid.py``'s),
    not the kernel's tile: a larger tile, the level products' masked halves
    and the several MXU passes of a float32 product are work, none of it
    needed."""
    h, d = _head(config)
    flops = tokens * h * (6 * chunk * d + 2 * chunk * chunk / 3 + 6 * d * d)
    byts = tokens * h * (4 * d * BF16 + d * F32 + F32)
    n = kda_layers(config)
    return {"flops": float(n * flops), "bytes": float(n * byts)}


def kda_decode(config: Dict[str, Any], row_steps: int) -> Dict[str, float]:
    """One token a row: the state read and written once a row a step at its
    COUNTED size (``2 x heads x d x d x 4`` bytes a layer), a head ``7 d^2``
    flops (decay, ``S^T k``, the correction's outer product, ``S^T q``)."""
    h, d = _head(config)
    n = kda_layers(config)
    return {"flops": float(n * row_steps * h * 7 * d * d),
            "bytes": float(n * row_steps * 2 * h * d * d * F32)}
