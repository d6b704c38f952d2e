"""Operations and bytes an expert model's serving step needs, from shapes.
The yardstick of the ``moe_*`` roofline shares, beside ``flops.py`` (which
counts a dense MLP from ``intermediate_size`` and would read an expert model
wrong).  Only what the algorithm needs is counted: the banks of the experts
a step really HIT and the rows it really routed, both as the program counted
them (``moe_experts_hit``, ``moe_rows`` on the ``engine.moe`` spans), never
the whole bank or a padded buffer, so that no share can pass 100 %.

All arguments are the configuration file's keys (the source's names):
``intermediate_size`` is ONE expert's width, ``num_experts`` the bank's
size.  Experts are SwiGLU: three matrices of ``hidden x width``.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark import flops


def expert_params(c: Dict[str, Any]) -> int:
    """One expert of one layer: gate, up and down projections."""
    return 3 * int(c["hidden_size"]) * int(c["intermediate_size"])


def dense_params(c: Dict[str, Any]) -> int:
    """Matmul parameters every step reads whatever it routes: attention
    and router of every layer, and the head (the embedding is a gather)."""
    e = int(c["hidden_size"])
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dh = e // h
    attention = e * h * dh + 2 * e * hkv * dh + h * dh * e
    router = e * int(c["num_experts"])
    return (int(c["num_hidden_layers"]) * (attention + router)
            + int(c["vocab_size"]) * e)


def decode_step_bytes(c: Dict[str, Any], experts_hit: float,
                      context_tokens: float,
                      bytes_per_param: int = 2) -> float:
    """Bytes one decode step must move: the dense weights once, the bank
    of every expert HIT (``experts_hit`` summed over the layers), and the
    keys and values of every live context token."""
    return (bytes_per_param * (dense_params(c)
                               + expert_params(c) * experts_hit)
            + flops.kv_bytes_per_token(c) * context_tokens)


def expert_matmul_flops(c: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls alone: ``rows`` token copies (summed over the
    layers), each through one expert's three matrices."""
    return 2.0 * expert_params(c) * rows


def expert_matmul_bytes(c: Dict[str, Any], rows: float, experts_hit: float,
                        bytes_per_value: int = 2) -> float:
    """What the grouped matmuls must move: the banks of the experts hit,
    each routed row in once and out once (the intermediate of width
    ``intermediate_size`` could stay on the chip and is not counted)."""
    return bytes_per_value * (expert_params(c) * experts_hit
                              + 2 * int(c["hidden_size"]) * rows)
