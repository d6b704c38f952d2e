"""The serving driver on ``glm-longdoc16k-closed`` at a tiny size on the
CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the check goes through the GLM reference, the
routes name the latent pool's twin and the grouped matmul and nothing per
head, and no request fails."""
import json
import time

from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_GLM = dict(hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=3, num_key_value_heads=3, vocab_size=256,
                n_routed_experts=8, num_experts=8, num_experts_per_tok=2,
                q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=12, latent_row_stored=128,
                max_position_embeddings=4096)


def _cell():
    cell = tiny.serve_cell("glm-longdoc16k-closed")
    cell.config.pop("sliding_window", None)     # tiny.py's is Mistral's
    cell.config.update(TINY_GLM)
    cell.config["correct"].update(prompt_tokens=[70, 33, 6])
    cell.traffic.update(clients=3, set_size=9)
    return cell


def test_serve_driver_on_the_latent_cell(capsys):
    from benchmark.drivers import serve

    cell = _cell()
    assert cell.workload["traffic"] == "longdoc16k-closed"
    assert cell.config["family"]["reference"] == "glm4_moe_lite"
    line = serve.run(cell, 2 ** 31 + 17, 3.0, False, time.monotonic(),
                     allow_cpu=True)
    _check_line(line, {"total_tokens_per_s", "setup_s"})
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["loop"] == "closed" and window["failed"] == 0
    assert window["evictions"] == 0
    assert window["routes"]["latent_attention_prefill"] == "xla-dense-latent"
    assert window["routes"]["latent_attention_decode"] == "xla-dense-latent"
    assert "paged_attention" not in window["routes"]
    assert "moe_grouped_matmul" in window["routes"]
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert check["ok"] and max(check["max_gap"]) < 1e-3
    assert check["prompt_tokens"] == [70, 33, 6]
