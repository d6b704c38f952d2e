"""The serving driver on ``sala-longdoc16k-closed`` at a tiny size on the
CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the check goes through the MiniCPM-SALA
reference with prompts that cross the switch to chosen blocks, the routes
name the four twins and nothing per head, and no request fails."""
import json
import time

from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_SALA = dict(hidden_size=256, intermediate_size=128, num_hidden_layers=4,
                 mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                              "minicpm4"],
                 num_attention_heads=2, num_key_value_heads=1, vocab_size=256,
                 lightning_nh=2, lightning_nkv=2, dim_model_base=64,
                 max_position_embeddings=4096,
                 sparse_config=dict(kernel_size=8, kernel_stride=4,
                                    block_size=16, init_blocks=1,
                                    window_size=32, topk=5, dense_len=64))


def _cell():
    cell = tiny.serve_cell("sala-longdoc16k-closed")
    cell.config.pop("sliding_window", None)     # tiny.py's is Mistral's
    cell.config.update(TINY_SALA)
    cell.config["correct"].update(prompt_tokens=[150, 33, 6])
    cell.traffic.update(clients=3, set_size=9)
    return cell


def test_serve_driver_on_the_sparse_and_linear_cell(capsys):
    from benchmark.drivers import serve

    cell = _cell()
    assert cell.workload["traffic"] == "longdoc16k-closed"
    assert cell.config["family"]["reference"] == "minicpm_sala"
    line = serve.run(cell, 2 ** 31 + 17, 3.0, False, time.monotonic(),
                     allow_cpu=True)
    _check_line(line, {"total_tokens_per_s", "setup_s"})
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["loop"] == "closed" and window["failed"] == 0
    assert window["evictions"] == 0
    assert window["routes"] == {
        "linear_attention_scan": "xla", "linear_attention_decode": "xla",
        "sparse_attention_prefill": "xla", "sparse_attention_decode": "xla"}
    assert set(window["routes"]) == set(cell.config["expected_routes"])
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert check["ok"] and max(check["max_gap"]) < 1e-3
    assert check["prompt_tokens"] == [150, 33, 6]
