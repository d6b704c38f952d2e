"""The serving driver on ``keye-longdoc32k-closed`` at a tiny size on the
CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the check goes through the Keye-VL-2.0 reference
with prompts that cross the switch to chosen tokens, the routes name the
four twins, the gather by token and the grouped matmul's twin and nothing
per head, and no request fails.  The number compared is the configuration's:
the MEAN gap of the served tokens (``logit_mean_margin``), and one served
token altered reads ``correct`` false by it."""
import json
import time

from benchmark import harness as H
from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_KEYE = dict(hidden_size=64, intermediate_size=128,
                 moe_intermediate_size=32, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 num_experts=8, num_local_experts=8, num_experts_per_tok=2,
                 vocab_size=256, max_position_embeddings=4096,
                 rope_scaling=dict(mrope_section=[4, 6, 6],
                                   rope_type="default", type="default"),
                 sa_config=dict(indexer_num_heads=2, indexer_head_dim=16,
                                indexer_num_kv_heads=1, topk=24,
                                q_chunk_size=512, kv_chunk_size=512))


def _cell():
    cell = tiny.serve_cell("keye-longdoc32k-closed")
    cell.config.pop("sliding_window", None)     # tiny.py's is Mistral's
    cell.config.update(TINY_KEYE)
    # the shipped file states the mean's limit and no widest gap's; tiny.py
    # adds a ``logit_margin`` of its own, which is not this cell's number
    assert "logit_margin" not in H.load_json(
        H.HERE + "/configs/keye-vl2-30b-a3b-serve.json")["correct"]
    del cell.config["correct"]["logit_margin"]
    cell.config["correct"].update(prompt_tokens=[150, 33, 6],
                                  logit_mean_margin=1e-4)
    cell.traffic.update(clients=3, set_size=9)
    return cell


def test_serve_driver_on_the_token_selecting_cell(capsys):
    from benchmark.drivers import serve

    cell = _cell()
    assert cell.workload["traffic"] == "longdoc32k-closed"
    assert cell.config["family"]["reference"] == "keye_vl2"
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
    assert window["routes"] == {
        "token_select_index": "xla", "token_select_threshold": "xla",
        "token_select_prefill": "xla", "token_select_row_index": "xla",
        "token_select_decode": "xla-token-gather-24",
        "moe_grouped_matmul": "ragged-dot-xla",
        "moe_grouped_matmul_kernel": "ragged-dot"}
    assert set(cell.config["expected_routes"]) <= set(window["routes"])
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert check["ok"] and max(check["max_gap"]) < 1e-3
    assert check["mean_gap"] == obj["check"]["logit_gap_mean"]["value"]
    assert check["prompt_tokens"] == [150, 33, 6]


def test_one_served_token_altered_is_not_correct_by_the_mean(monkeypatch):
    """The rest of a run with the timed path broken underneath: ONE of the
    24 served tokens of the correctness requests is another token.  The mean
    gap is over its limit, and over the SHIPPED limit too (a token that the
    model did not choose lies units under the maximum, a 24th of that is
    the mean's share)."""
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
    shipped = H.load_json(H.HERE + "/configs/keye-vl2-30b-a3b-serve.json")
    assert altered and obj["correct"] is False and obj["failed"] == 0
    assert mean["value"] > 100 * mean["limit"]
    assert mean["value"] > float(shipped["correct"]["logit_mean_margin"])
