"""Operations and bytes the algorithm needs, from shapes.  The yardstick of
every roofline share; kept here so that no later PR can move it.

``train_flops_per_token`` is a copy of
``deepspeed_tpu/models/llama.py:flops_per_token`` (PaLM convention: 6 per
matmul parameter per token, forward and backward, recomputation not
counted) with ONE departure: attention is counted as causal, the half of
the PaLM term ``12 * L * H * Dh * S`` that a causal kernel must do.  The
program's function counts the whole square; a share of a roofline has to
count only what is needed, or it can pass 100 %.

All arguments are the configuration file's keys (the source's names).
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(c: Dict[str, Any]):
    e, i = int(c["hidden_size"]), int(c["intermediate_size"])
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return e, i, h, hkv, e // h, int(c["num_hidden_layers"])


def layer_matmul_params(c: Dict[str, Any]) -> int:
    e, i, h, hkv, dh, _ = _dims(c)
    return e * h * dh + 2 * e * hkv * dh + h * dh * e + 3 * e * i


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters that take part in matrix multiplications: the blocks and
    the head (the embedding is a gather)."""
    return (int(c["num_hidden_layers"]) * layer_matmul_params(c)
            + int(c["vocab_size"]) * int(c["hidden_size"]))


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, causal attention, no recomputation."""
    _, _, h, _, dh, layers = _dims(c)
    return 6.0 * matmul_params(c) + 6.0 * layers * h * dh * seq_len


def prefill_flops(c: Dict[str, Any], new_tokens: float,
                  context_tokens: float) -> float:
    """Forward of ``new_tokens`` prompt tokens through the blocks (the head
    runs once per sequence, not per prompt token, and is left out), their
    queries attending to ``context_tokens`` keys each on average."""
    _, _, h, _, dh, layers = _dims(c)
    return new_tokens * (2.0 * layers * layer_matmul_params(c)
                         + 4.0 * layers * h * dh * context_tokens)


def weight_bytes(c: Dict[str, Any], bytes_per_param: int = 2) -> int:
    """What one decode step must read of the weights: every block and the
    head (of the embedding only one row per sequence)."""
    return bytes_per_param * matmul_params(c)


def kv_bytes_per_token(c: Dict[str, Any], bytes_per_value: int = 2) -> int:
    _, _, _, hkv, dh, layers = _dims(c)
    return 2 * layers * hkv * dh * bytes_per_value


def decode_step_bytes(c: Dict[str, Any], context_tokens: float) -> float:
    """Bytes one decode step must move: the weights once, and the keys and
    values of every live context token."""
    return weight_bytes(c) + kv_bytes_per_token(c) * context_tokens
