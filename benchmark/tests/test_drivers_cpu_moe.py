"""The serving driver on the two new cells at a tiny size on the CPU:
``reasoning-closed`` on the dense configuration, and
``olmoe-reasoning-closed``, whose check goes through the OLMoE reference and
whose routes must name the grouped matmul."""
import json
import time

import pytest

from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_OLMOE = dict(hidden_size=64, intermediate_size=32, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=4,
                  vocab_size=256, num_experts=8, num_experts_per_tok=2,
                  max_position_embeddings=4096)


def _cell(name):
    cell = tiny.serve_cell(name)
    if cell.config["family"]["reference"] == "olmoe":
        for k in ("sliding_window",):
            cell.config.pop(k, None)
        cell.config.update(TINY_OLMOE)
    # 4 clients, a dozen short requests: the closed loop's control flow
    cell.traffic.update(clients=4, set_size=12)
    return cell


@pytest.mark.parametrize("name", ["reasoning-closed",
                                  "olmoe-reasoning-closed"])
def test_serve_driver_on_the_new_cells(name, capsys):
    from benchmark.drivers import serve

    cell = _cell(name)
    line = serve.run(cell, 2 ** 31 + 17, 3.0, False, time.monotonic(),
                     allow_cpu=True)
    _check_line(line, {"total_tokens_per_s", "setup_s"})
    window = [json.loads(l) for l in capsys.readouterr().out.splitlines()
              if l.startswith('{"phase": "window"')][0]
    assert window["loop"] == "closed" and window["failed"] == 0
    routed = "moe_grouped_matmul" in window["routes"]
    assert routed == (cell.config["family"]["reference"] == "olmoe")
