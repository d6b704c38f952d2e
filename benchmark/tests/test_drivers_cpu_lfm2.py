"""The serving driver on ``lfm2-reasoning-closed`` at a tiny size on the
CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the check goes through the LFM2 reference, the
routes name the padded pool's twin and the grouped matmul, and the engine
reports the layers of each kind."""
import json
import time

from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_LFM2 = dict(hidden_size=128, intermediate_size=96,
                 moe_intermediate_size=48, num_hidden_layers=6,
                 layer_types=["conv", "conv", "full_attention", "conv",
                              "conv", "conv"],
                 num_attention_heads=2, num_key_value_heads=1, vocab_size=256,
                 num_experts=8, num_experts_per_tok=2,
                 max_position_embeddings=4096)


def _cell():
    cell = tiny.serve_cell("lfm2-reasoning-closed")
    cell.config.pop("sliding_window", None)     # tiny.py's is Mistral's
    cell.config.update(TINY_LFM2)
    cell.traffic.update(clients=4, set_size=12)
    return cell


def test_serve_driver_on_the_hybrid_cell(capsys):
    from benchmark.drivers import serve

    cell = _cell()
    assert cell.workload["traffic"] == "reasoning-closed"
    assert cell.config["family"]["reference"] == "lfm2_moe"
    line = serve.run(cell, 2 ** 31 + 17, 3.0, False, time.monotonic(),
                     allow_cpu=True)
    _check_line(line, {"total_tokens_per_s", "setup_s"})
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["loop"] == "closed" and window["failed"] == 0
    assert window["evictions"] == 0
    assert window["routes"]["paged_attention"] == "xla-dense-pad128"
    assert "moe_grouped_matmul" in window["routes"]
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert check["ok"] and max(check["max_gap"]) < 1e-3
