"""Operations and bytes of the two things a SmallThinker layer spends its
time on (``benchmark/reference/smallthinker.py``), from shapes and the
program's own counts, under the SOURCE'S OWN KEYS, which ``flops_hybrid.py``
(``moe_intermediate_size``, ``num_experts``) and ``flops_trinity.py``
(``layer_types``, ``sliding_window``) do not know:

- the ReGLU expert matmuls (``moe_ffn_hidden_size`` wide, three banks an
  expert) from the ``engine.moe`` spans' ``moe_rows`` and
  ``moe_experts_hit``: the banks of the experts HIT once, each routed row in
  once and out once;
- attention at ``num_key_value_heads`` KV heads BY LAYER KIND
  (``sliding_window_layout``): a window layer's row at position ``p`` needs
  ``min(p + 1, sliding_window_size)`` keys, a full layer's ``p + 1``, from
  the ``engine.dispatch`` spans' key tokens (``flops_trinity.py``'s counts,
  given a kind a layer from this source's list).

Only what the algorithm needs is counted, so no share computed from these can
pass 100 %.  ``config`` is the configuration file's object."""
from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_trinity
from benchmark.flops_trinity import BF16, FULL, WINDOW, key_tokens  # noqa: F401


def _by_kind(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as ``flops_trinity.py`` reads one: a kind a layer
    from ``sliding_window_layout`` (heads and ``head_dim`` are named alike)."""
    return {**config, "layer_types": [
        WINDOW if int(w) else FULL for w in config["sliding_window_layout"]]}


def layers(config: Dict[str, Any], kind: str) -> int:
    return flops_trinity.layers(_by_kind(config), kind)


def pool_bytes_per_token(config: Dict[str, Any], kind: str) -> int:
    """What a cached token costs the pool of the layers of ``kind``: K and V
    of every KV head in bf16, a layer."""
    return flops_trinity.pool_bytes_per_token(_by_kind(config), kind)


def attention(config: Dict[str, Any], pairs: Dict[str, float],
              rows: float = 0.0) -> Dict[str, float]:
    """``flops_trinity.attention`` over ``pairs[kind]`` (row, key) pairs a
    layer of each kind, at this model's four KV heads."""
    return flops_trinity.attention(_by_kind(config), pairs, rows)


def expert_params(config: Dict[str, Any]) -> int:
    """One expert of one layer: gate, up and down projections."""
    return 3 * int(config["hidden_size"]) * int(config["moe_ffn_hidden_size"])


def expert_matmul_flops(config: Dict[str, Any], rows: float) -> float:
    """``rows`` token copies (summed over the layers), each through one
    expert's three matrices."""
    return 2.0 * expert_params(config) * rows


def expert_matmul_bytes(config: Dict[str, Any], rows: float,
                        experts_hit: float) -> float:
    """The banks of the experts hit (summed over the layers), each routed
    row in once and out once; the intermediate could stay on the chip."""
    return BF16 * (expert_params(config) * experts_hit
                   + 2 * int(config["hidden_size"]) * rows)
