"""The serving driver on ``olmo-hybrid-longdoc16k-closed`` at a tiny size on
the CPU, through ``run.py``'s own path (``Cell`` -> driver by ``kind`` ->
reference by ``family``): the check goes through the Olmo-Hybrid reference
with prompts that cross chunks, pages and the scan's tiles, the routes name
the four twins, no request fails, and one served token altered reads
``correct`` false."""
import json
import time

from benchmark.tests import tiny
from benchmark.tests.test_drivers_cpu import _check_line

TINY_OLMO = dict(hidden_size=120, intermediate_size=128, num_hidden_layers=4,
                 layer_types=["linear_attention"] * 3 + ["full_attention"],
                 num_attention_heads=15, num_key_value_heads=15,
                 vocab_size=256, linear_num_key_heads=2,
                 linear_num_value_heads=2, linear_key_head_dim=8,
                 linear_value_head_dim=16, max_position_embeddings=4096)
TWINS = {"gated_delta_scan": "xla", "gated_delta_decode": "xla",
         "paged_attention_prefill": "xla-dense",
         "paged_attention_decode": "xla-dense"}


def _cell():
    cell = tiny.serve_cell("olmo-hybrid-longdoc16k-closed")
    cell.config.pop("sliding_window", None)     # tiny.py's is Mistral's
    cell.config.update(TINY_OLMO)
    cell.config["correct"].update(prompt_tokens=[150, 33, 6])
    cell.traffic.update(clients=3, set_size=9)
    return cell


def _run(capsys, cell):
    from benchmark.drivers import serve

    line = serve.run(cell, 2 ** 31 + 17, 3.0, False, time.monotonic(),
                     allow_cpu=True)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return line, lines


def test_serve_driver_on_the_gated_delta_cell(capsys, monkeypatch):
    from deepspeed_tpu.ops import routes

    # routes are a process's: another cell's test may have left its own
    monkeypatch.setattr(routes, "_taken", {})
    cell = _cell()
    assert cell.workload["traffic"] == "longdoc16k-closed"
    assert cell.config["family"]["reference"] == "olmo_hybrid"
    line, lines = _run(capsys, cell)
    _check_line(line, {"total_tokens_per_s", "setup_s"})
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["loop"] == "closed" and window["failed"] == 0
    assert window["evictions"] == 0
    assert {k: window["routes"][k] for k in TWINS} == TWINS
    assert set(TWINS) == set(cell.config["expected_routes"])
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert check["ok"] and max(check["max_gap"]) < 1e-3
    assert check["prompt_tokens"] == [150, 33, 6]


def test_one_served_token_altered_reads_not_correct(capsys, monkeypatch):
    """The reference's view of what the system generated, with one token
    of the first prompt's sixteen replaced: the gap there is far over the
    margin and the line says ``correct`` false."""
    from benchmark.reference import olmo_hybrid as ref

    sound = ref.generated_gaps
    calls = []

    def altered(params, config, prompt, generated, **kw):
        generated = list(generated)
        if not calls:
            generated[3] = (generated[3] + 1) % int(config["vocab_size"])
        calls.append(len(prompt))
        return sound(params, config, prompt, generated, **kw)

    monkeypatch.setattr(ref, "generated_gaps", altered)
    line, lines = _run(capsys, _cell())
    out = json.loads(line)
    assert out["correct"] is False and out["failed"] == 0
    check = [l for l in lines if l.get("phase") == "correct_alone"][0]
    assert not check["ok"] and max(check["max_gap"]) > float(check["margin"])
