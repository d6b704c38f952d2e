"""Each driver run in-process at a tiny size on the CPU, through a function
argument (no command-line switch, no environment variable lets ``run.py``
onto the CPU): control flow and the shape of the last line."""
import json
import subprocess
import sys
import time

import pytest

from benchmark import harness as H
from benchmark.tests import tiny

LAST_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _check_line(line, names):
    obj = json.loads(line)
    # every number compared for ``correct`` beside its limit, last in the line
    assert set(obj) == LAST_KEYS | {"check"} and list(obj)[-1] == "check"
    assert obj["check"] and all(set(v) == {"value", "limit"}
                                for v in obj["check"].values())
    assert set(obj["metrics"]) == set(names)
    for v in obj["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert set(obj["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0
    return obj


@pytest.mark.parametrize("name,names", [
    ("chat-open", {"tpot_p95_ms", "setup_s"}),
    ("longdoc-closed", {"total_tokens_per_s", "setup_s"})])
def test_serve_driver(name, names):
    from benchmark.drivers import serve

    line = serve.run(tiny.serve_cell(name), 2 ** 31 + 11, 3.0, False,
                     time.monotonic(), allow_cpu=True)
    _check_line(line, names)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                            capsys):
    """The rest of a run with the timed path broken underneath: one served
    token of a correctness request is another token.  ``correct`` comes out
    false, and the number over its limit is in the line and on stderr."""
    from benchmark import loadgen
    from benchmark.drivers import serve

    run_plan = loadgen.run_plan

    def altered(plan):
        out = run_plan(plan)
        for rec in out["records"]:
            if rec.get("generated"):
                rec["generated"][3] = 1 + (rec["generated"][3] + 7) % 200
        return out

    monkeypatch.setattr(loadgen, "run_plan", altered)
    line = serve.run(tiny.serve_cell("longdoc-closed"), 2 ** 31 + 12, 2.0,
                     False, time.monotonic(), allow_cpu=True)
    obj = json.loads(line)
    gap = obj["check"]["logit_gap_max"]
    assert obj["correct"] is False and obj["failed"] == 0
    assert gap["value"] > 100 * gap["limit"]
    last = capsys.readouterr().err.strip().splitlines()[-1]
    assert last.startswith("check ") and json.loads(last[6:]) == obj["check"]


def test_train_driver_on_four_virtual_devices():
    from benchmark.drivers import train

    line = train.run(tiny.train_cell("pretrain-zero3", 4), 2 ** 31 + 11,
                     2.0, False, time.monotonic(), allow_cpu=True)
    obj = _check_line(line, {"train_tokens_per_s", "setup_s"})
    assert obj["device"]["count"] == 4


def test_run_py_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, H.HERE + "/run.py", "--workload", "chat-open",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    out = subprocess.run(
        [sys.executable, H.HERE + "/run.py", "--workload", "no-such-cell",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and '"correct"' not in out.stdout
