"""Operations and bytes the serving step of a HYBRID stack needs, from
shapes: layers of two kinds (``layer_types``: a gated short convolution or
attention), leading dense SwiGLU layers (``num_dense_layers``,
``intermediate_size`` wide) before expert layers (``num_experts`` experts of
``moe_intermediate_size``), a tied head.  The yardstick of the ``hybrid_*``
roofline shares, beside ``flops.py`` and ``flops_moe.py``, which read
``intermediate_size`` and ``num_hidden_layers`` as ONE width and ONE kind of
layer and would count this model wrong (``benchmark/tests/
test_hybrid_metrics.py`` holds both facts at the published sizes).

Only what the algorithm needs is counted: the banks of the experts a step
really HIT and the rows it really routed (the program's own counts on its
``engine.moe`` spans), the keys and values of live context tokens at the
width THE POOL STORES them (``kv_head_dim_stored``: heads of 64 lie
zero-padded to 128, and the padding is read), the convolution state of live
rows.  The embedding is a gather; norms, taps and the selection bias are
kilobytes.  So no share computed from these can pass 100 %.

All arguments are the configuration file's keys (the source's names).
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(c: Dict[str, Any]):
    e = int(c["hidden_size"])
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return e, h, hkv, e // h


def conv_layers(c: Dict[str, Any]) -> int:
    return sum(t == "conv" for t in c["layer_types"])


def attention_layers(c: Dict[str, Any]) -> int:
    return sum(t == "full_attention" for t in c["layer_types"])


def dense_layers(c: Dict[str, Any]) -> int:
    return min(int(c["num_dense_layers"]), int(c["num_hidden_layers"]))


def expert_layers(c: Dict[str, Any]) -> int:
    return int(c["num_hidden_layers"]) - dense_layers(c)


def conv_mixer_params(c: Dict[str, Any]) -> int:
    """``W_in`` (hidden -> 3 x hidden) and ``W_out``."""
    e = _dims(c)[0]
    return 4 * e * e


def attention_mixer_params(c: Dict[str, Any]) -> int:
    e, h, hkv, dh = _dims(c)
    return e * h * dh + 2 * e * hkv * dh + h * dh * e


def dense_mlp_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["intermediate_size"])


def expert_params(c: Dict[str, Any]) -> int:
    """One expert of one layer: gate, up and down projections."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def head_params(c: Dict[str, Any]) -> int:
    return int(c["vocab_size"]) * int(c["hidden_size"])


def fixed_step_bytes(c: Dict[str, Any], bytes_per_param: int = 2) -> float:
    """What every decode step reads whatever it routes: both kinds of
    mixer, the dense MLPs, the routers (float32) and the head."""
    e = _dims(c)[0]
    return (bytes_per_param * (
        conv_layers(c) * conv_mixer_params(c)
        + attention_layers(c) * attention_mixer_params(c)
        + dense_layers(c) * dense_mlp_params(c) + head_params(c))
        + 4 * expert_layers(c) * e * int(c["num_experts"]))


def kv_bytes_per_token(c: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one cached token over the layers that keep them, at the
    width the pool stores a head."""
    _, _, hkv, dh = _dims(c)
    stored = int(c.get("kv_head_dim_stored") or dh)
    return 2 * attention_layers(c) * hkv * stored * bytes_per_value


def state_bytes_per_row(c: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """The convolution state one live row reads and writes a step:
    ``conv_L_cache - 1`` rows of ``hidden`` a conv layer, in and out."""
    e = _dims(c)[0]
    return (2 * conv_layers(c) * (int(c["conv_L_cache"]) - 1) * e
            * bytes_per_value)


def decode_step_bytes(c: Dict[str, Any], experts_hit: float,
                      context_tokens: float, rows: float) -> float:
    """Bytes one decode step must move: the fixed weights once, the bank
    of every expert HIT (``experts_hit`` summed over the expert layers),
    the keys and values of every live context token, the state of every
    live row."""
    return (fixed_step_bytes(c) + 2.0 * expert_params(c) * experts_hit
            + kv_bytes_per_token(c) * context_tokens
            + state_bytes_per_row(c) * rows)


def expert_matmul_flops(c: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls alone: ``rows`` token copies (summed over the
    layers), each through one expert's three matrices."""
    return 2.0 * expert_params(c) * rows


def expert_matmul_bytes(c: Dict[str, Any], rows: float, experts_hit: float,
                        bytes_per_value: int = 2) -> float:
    """What the grouped matmuls must move: the banks of the experts hit,
    each routed row in once and out once (the intermediate could stay on
    the chip and is not counted)."""
    return bytes_per_value * (expert_params(c) * experts_hit
                              + 2 * int(c["hidden_size"]) * rows)


def stage_params(c: Dict[str, Any]) -> int:
    """Every matrix of the stage as the configuration holds it (embedding
    once: the head is tied), routers included; norms, taps and biases are
    left out (under 0.001 %)."""
    e = _dims(c)[0]
    return (conv_layers(c) * conv_mixer_params(c)
            + attention_layers(c) * attention_mixer_params(c)
            + dense_layers(c) * dense_mlp_params(c)
            + expert_layers(c) * (int(c["num_experts"]) * expert_params(c)
                                  + e * int(c["num_experts"]))
            + head_params(c))
