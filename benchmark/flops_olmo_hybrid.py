"""Operations and bytes of Olmo-Hybrid's gated delta rule, from shapes and the
dispatch spans' counts whatever implements them: what the layer equations of
``benchmark/reference/olmo_hybrid.py`` need in their chunked form, not what a
kernel happens to read.  The readers ``delta_scan_roofline`` and
``delta_decode_roofline`` divide by the device time of the kernel that does
the work.

``config`` is the configuration file's object (the source's names)."""
from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def delta_layers(config: Dict[str, Any]) -> int:
    return sum(t == "linear_attention" for t in config["layer_types"])


def attention_layers(config: Dict[str, Any]) -> int:
    return sum(t == "full_attention" for t in config["layer_types"])


def _head(config: Dict[str, Any]):
    return (int(config["linear_num_key_heads"]),
            int(config["linear_key_head_dim"]),
            int(config["linear_value_head_dim"]))


def state_bytes_per_seq(config: Dict[str, Any]) -> int:
    """A sequence's per-slot state AS COUNTED: a float32 ``dk x dv`` matrix a
    head, and the convolution's last ``taps - 1`` inputs of q~, k~ and v~ in
    bf16, a layer (2,211,840 + 69,120 at the published widths)."""
    h, dk, dv = _head(config)
    rows = int(config["linear_conv_kernel_dim"]) - 1
    return delta_layers(config) * (h * dk * dv * F32
                                   + rows * h * (2 * dk + dv) * BF16)


def pool_bytes_per_token(config: Dict[str, Any], heads_stored: int = 32
                         ) -> int:
    """What a cached token costs the pool: K and V of every STORED head (30
    are stored as 32: whole sublane tiles of combined heads) in bf16, a
    full_attention layer."""
    head_dim = int(config["hidden_size"]) // int(
        config["num_attention_heads"])
    return attention_layers(config) * 2 * heads_stored * head_dim * BF16


def delta_scan(config: Dict[str, Any], tokens: int, chunk: int = 64
               ) -> Dict[str, float]:
    """The chunked form over ``tokens`` prompt tokens, all linear_attention
    layers.  A chunk of ``C`` tokens a head: ``K K^T`` and ``Q K^T`` (``2 C^2
    dk`` each), ``K S_0`` and ``Q S_0`` (``2 C dk dv`` each), the solve's
    application ``T (..)`` and ``tril(..) U`` (``2 C^2 dv`` halved by the
    triangle, twice), the solve itself by forward substitution (``2 C^3 /
    3``), ``(K ..)^T U`` and the state's decay (``2 C dk dv``): a token a
    head ``4 C dk + 2 C (dk + dv) + 2 C dv + 2 C^2 / 3 + 6 dk dv``; q, k, v
    read and o written in bf16, g and b read in float32, once.  ``chunk``
    is the count's own (ISSUE 50's 64: 0.054 GFLOP a prompt token over nine
    layers), not the kernel's tile: a larger tile does more work a token,
    none of it needed."""
    h, dk, dv = _head(config)
    flops = tokens * h * (4 * chunk * dk + 2 * chunk * (dk + dv)
                          + 2 * chunk * dv + 2 * chunk * chunk / 3
                          + 6 * dk * dv)
    byts = tokens * h * ((2 * dk + 2 * dv) * BF16 + 2 * F32)
    n = delta_layers(config)
    return {"flops": float(n * flops), "bytes": float(n * byts)}


def delta_decode(config: Dict[str, Any], row_steps: int) -> Dict[str, float]:
    """One token a row: the state read and written once a row a step at its
    COUNTED size (``2 x heads x dk x dv x 4`` bytes a layer), a head ``7 dk
    dv`` flops (decay, ``S^T k``, the correction's outer product, ``S^T
    q``)."""
    h, dk, dv = _head(config)
    n = delta_layers(config)
    return {"flops": float(n * row_steps * h * 7 * dk * dv),
            "bytes": float(n * row_steps * 2 * h * dk * dv * F32)}
