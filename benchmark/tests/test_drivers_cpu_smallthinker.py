"""The serving driver on ``smallthinker-reasoning8k-closed`` at a tiny size on
the CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the engine is built from the configuration's
``engine`` block with BOTH pools' sizes, the check goes through the
SmallThinker reference with prompts that cross the window while DECODING,
held by the MEAN gap of the served tokens (``logit_mean_margin``); the routes
name the twins, no request fails, nothing is evicted, the window pool gave
pages back, and one served token altered reads ``correct`` false."""
import json
import time

from benchmark import harness as H
from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]
TINY = dict(
    hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=8,
    rope_layout=LAYOUT, sliding_window_layout=LAYOUT, sliding_window_size=24,
    num_attention_heads=12, num_key_value_heads=4, head_dim=16,
    vocab_size=256, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, max_position_embeddings=4096)
TWINS = {"paged_attention_prefill": "xla-dense",
         "paged_attention_decode": "xla-dense",
         "moe_grouped_matmul": "ragged-dot-xla"}
# float32 pages of 16 tokens: 4 KV heads x 16 x K and V, two full layers and
# six window layers
FULL_PAGE, OWN_PAGE = 2 * 16 * 4 * 2 * 16 * 4, 6 * 16 * 4 * 2 * 16 * 4
SHIPPED = H.HERE + "/configs/smallthinker-21b-a3b-serve.json"


def _cell():
    cell = tiny.serve_cell("smallthinker-reasoning8k-closed")
    cell.config.update(TINY)
    # 4 slots of 16 pages and the trash page; 4 x (a window of 24 and a
    # tick of 36 rows: 5 pages) and the trash page
    cell.config["engine"].update(kv_pool_bytes=65 * FULL_PAGE,
                                 window_pool_bytes=21 * OWN_PAGE)
    assert "logit_margin" not in H.load_json(SHIPPED)["correct"]
    del cell.config["correct"]["logit_margin"]
    # 20 + 30 crosses the window of 24 while decoding
    cell.config["correct"].update(prompt_tokens=[150, 20, 6], new_tokens=30,
                                  logit_mean_margin=1e-4)
    cell.traffic.update(clients=3, set_size=9)
    return cell


def test_serve_driver_on_the_two_pool_cell(capsys, monkeypatch):
    from benchmark.drivers import serve
    from deepspeed_tpu.ops import routes

    # routes are a process's: another cell's test may have left its own
    monkeypatch.setattr(routes, "_taken", {})
    cell = _cell()
    assert cell.workload["traffic"] == "reasoning8k-closed"
    assert cell.config["family"]["reference"] == "smallthinker"
    line = serve.run(cell, 2 ** 31 + 17, 3.0, False, time.monotonic(),
                     allow_cpu=True)
    obj = _check_line(line, {"total_tokens_per_s", "setup_s"})
    assert "logit_gap_max" not in obj["check"]
    assert obj["check"]["logit_gap_mean"]["limit"] == 1e-4
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["loop"] == "closed" and window["failed"] == 0
    assert window["evictions"] == 0
    assert {k: window["routes"][k] for k in TWINS} == TWINS
    assert set(TWINS) == set(cell.config["expected_routes"])
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert check["ok"] and max(check["max_gap"]) < 1e-3
    assert check["prompt_tokens"] == [150, 20, 6]


def test_one_served_token_altered_is_not_correct_by_the_mean(monkeypatch):
    """ONE of the served tokens of the correctness requests is another
    token: the mean gap is over its limit, and over the SHIPPED limit too."""
    from benchmark import loadgen
    from benchmark.drivers import serve

    run_plan = loadgen.run_plan
    altered = []

    def alter(plan):
        out = run_plan(plan)
        for rec in out["records"]:
            if rec.get("generated") and not altered:
                rec["generated"][3] = 1 + (rec["generated"][3] + 7) % 200
                altered.append(rec["generated"][3])
        return out

    monkeypatch.setattr(loadgen, "run_plan", alter)
    line = serve.run(_cell(), 2 ** 31 + 18, 2.0, False, time.monotonic(),
                     allow_cpu=True)
    obj = json.loads(line)
    mean = obj["check"]["logit_gap_mean"]
    shipped = H.load_json(SHIPPED)
    assert altered and obj["correct"] is False and obj["failed"] == 0
    assert mean["value"] > 100 * mean["limit"]
    assert mean["value"] > float(shipped["correct"]["logit_mean_margin"])
