"""The serving driver on ``glm5-longdoc16k-closed`` at a tiny size on the
CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the check goes through the GLM-5 reference GIVEN
THE SAME SHARE (4 of 8 experts, the vocabulary's slice) with prompts that
cross the switch to chosen rows, the routes name the three twins of the
indexer, the chunk pass's twin, the gather by token and the grouped
matmul's twin and nothing per head, and no request fails.  The number
compared is the configuration's: the MEAN gap of the served tokens
(``logit_mean_margin``), and one served token altered reads ``correct``
false by it."""
import json
import time

from benchmark import harness as H
from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_GLM5 = dict(hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_hidden_layers=3,
                 first_k_dense_replace=1, num_attention_heads=3,
                 num_key_value_heads=3, n_routed_experts=4, expert_shards=2,
                 num_experts=4, num_dense_layers=1, num_experts_per_tok=2,
                 q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=12, qk_head_dim=24,
                 index_head_dim=16, index_n_heads=2, index_topk=24,
                 vocab_size=256, max_position_embeddings=4096)
SHIPPED = H.HERE + "/configs/glm-5-serve.json"


def _cell():
    cell = tiny.serve_cell("glm5-longdoc16k-closed")
    cell.config.pop("sliding_window", None)     # tiny.py's is Mistral's
    cell.config.update(TINY_GLM5)
    # the shipped file states the mean's limit and no widest gap's; tiny.py
    # adds a ``logit_margin`` of its own, which is not this cell's number
    assert "logit_margin" not in H.load_json(SHIPPED)["correct"]
    del cell.config["correct"]["logit_margin"]
    cell.config["correct"].update(prompt_tokens=[150, 33, 6],
                                  logit_mean_margin=1e-4)
    cell.traffic.update(clients=3, set_size=9)
    return cell


def test_serve_driver_on_the_chosen_latent_cell(capsys, monkeypatch):
    from benchmark.drivers import serve
    from deepspeed_tpu.ops import routes

    # routes are a process's: another cell's test may have left its own
    monkeypatch.setattr(routes, "_taken", {})
    cell = _cell()
    assert cell.workload["traffic"] == "longdoc16k-closed"
    assert cell.config["family"]["reference"] == "glm_moe_dsa"
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
        "token_select_row_index": "xla", "latent_select_prefill": "xla",
        "latent_select_decode": "xla-latent-token-gather-24",
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
    shipped = H.load_json(SHIPPED)
    assert altered and obj["correct"] is False and obj["failed"] == 0
    assert mean["value"] > 100 * mean["limit"]
    assert mean["value"] > float(shipped["correct"]["logit_mean_margin"])
