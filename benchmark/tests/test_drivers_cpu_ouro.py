"""The serving driver on ``ouro-reasoning-closed`` at a tiny size on the
CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the check goes through the Ouro reference, the
engine reports the loop, and the traced readers find nothing to read
without a device trace and say so by leaving their metrics out."""
import json
import time

from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_OURO = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                 vocab_size=256, max_position_embeddings=4096,
                 total_ut_steps=3, layer_types=["full_attention"] * 2,
                 max_window_layers=2)


def _cell():
    cell = tiny.serve_cell("ouro-reasoning-closed")
    cell.config.update(TINY_OURO)
    cell.config["sliding_window"] = None    # tiny.py's is Mistral's
    cell.traffic.update(clients=4, set_size=12)
    return cell


def test_serve_driver_on_the_looped_cell(capsys):
    from benchmark.drivers import serve

    cell = _cell()
    assert cell.workload["traffic"] == "reasoning-short-closed"
    assert cell.config["family"]["reference"] == "ouro"
    line = serve.run(cell, 2 ** 31 + 17, 3.0, False, time.monotonic(),
                     allow_cpu=True)
    _check_line(line, {"total_tokens_per_s", "setup_s"})
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["loop"] == "closed" and window["failed"] == 0
    assert window["evictions"] == 0
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert check["ok"] and max(check["max_gap"]) < 1e-3
