"""Operations and bytes a LOOPED model's serving step needs, from shapes:
the layers run ``total_ut_steps`` times a token with the same weights, and
a cached token owns a K/V row for every (pass, layer).  The yardstick of
``loop_decode_roofline``, beside ``flops.py`` (whose ``weight_bytes`` counts
one pass over the blocks and whose ``kv_bytes_per_token`` counts
``num_hidden_layers`` slots: a quarter of this model's step at four passes).

Only what the algorithm needs is counted.  A decode step has to read every
block's matrices once a PASS (no chip keeps 4.9 GB of weights between
passes), the head once, and each live context token's keys and values of
every pass and layer once; the embedding is a gather, the norms and the
exit gate are kilobytes.  So no share computed from these can pass 100 %.

All arguments are the configuration file's keys (the source's names).
"""
from __future__ import annotations

from typing import Any, Dict


def block_matmul_params(c: Dict[str, Any]) -> int:
    """One block: q, k, v, o and the SwiGLU's three matrices."""
    e, i = int(c["hidden_size"]), int(c["intermediate_size"])
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dh = int(c.get("head_dim") or e // h)
    return e * h * dh + 2 * e * hkv * dh + h * dh * e + 3 * e * i


def head_params(c: Dict[str, Any]) -> int:
    return int(c["vocab_size"]) * int(c["hidden_size"])


def kv_slots(c: Dict[str, Any]) -> int:
    """K/V rows a cached token owns: passes x layers."""
    return int(c["total_ut_steps"]) * int(c["num_hidden_layers"])


def kv_bytes_per_token(c: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """Keys and values of one token over every (pass, layer) slot."""
    e, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    dh = int(c.get("head_dim") or e // h)
    return (2 * int(c["num_key_value_heads"]) * dh * bytes_per_value
            * kv_slots(c))


def decode_step_bytes(c: Dict[str, Any], context_tokens: float,
                      bytes_per_param: int = 2) -> float:
    """Bytes one decode token step must move: every block's matrices once
    a pass, the head once, and the K and V of every live context token."""
    weights = (int(c["total_ut_steps"]) * int(c["num_hidden_layers"])
               * block_matmul_params(c) + head_params(c))
    return (bytes_per_param * weights
            + kv_bytes_per_token(c) * float(context_tokens))


def forward_flops_per_token(c: Dict[str, Any]) -> float:
    """Matmul operations a prompt token costs (attention scores aside)."""
    return 2.0 * (int(c["total_ut_steps"]) * int(c["num_hidden_layers"])
                  * block_matmul_params(c) + head_params(c))
